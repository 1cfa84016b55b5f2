"""Device-time scopes: the one table of ``jax.named_scope`` names.

``jax.named_scope`` is JAX's own span for device work.  It runs at trace
time only (nothing is added to a compiled program), it is written into
every HLO instruction's ``op_name``, and the profiler carries that into
the trace beside the operation, so a fusion reads as the model part it
computes.  Every operation's path is ``<phase>/<part>``:

* the PHASE is opened once at the top of each trunk, INSIDE the jitted
  function (no program's name changes);
* the PART is opened in the shared helpers as a ``with`` block in the
  helper's body, so each is written once and every trunk gets it.  Never
  a decorator or a wrapper function: one more Python frame under the
  traced operations costs a fifth of a warm set-up (PERF.md §6, PR 32).
  Where parts nest (``mlp/moe.route``) the innermost one counts.

A path may hold ``jit(...)`` components, which name neither: a trunk's
per-layer body runs under its own ``jax.jit`` (``jit(_decode_layer)``,
``_ragged_``, ``_chunk_``, ``_prefill_``, ``_nocache_``: models/
transformer.py), as every Pallas kernel's wrapper does.  A cache body opens
its trunk's PHASE once more around itself, so a path may name its phase
twice (the first counts): the chip's compiler rebuilds the K/V row scatter
under the name it has inside the called function, without the call's
prefix.  And an operation that compiler makes itself inside a called
function is named after the call alone (``.../jit(_decode_layer)``: its
phase, no part; the reader counts it under ``trunk.unscoped_device_share``),
where in a flat module it carries no name:
tests/test_chip_compile_programs.py holds how few there are.

Read by ``benchmark/layer_metrics/_scope_trace.py``, which holds its own
copy of these names as a benchmark holds a kernel's (it also has to read
a program from before they existed);
``tests/benchmark/test_benchmark_scope_trace.py`` holds the two copies
together.

The table (scope -> where it opens -> which metric reads it):

    phases
    decode          decode_multi, decode_step                 trunk.decode_proj_ms, trunk.decode_head_ms,
                                                              trunk.decode_glue_ms; the step count they divide by
    prefill         forward_ragged, prefill                   step.prefill_device_share
    chunk           prefill_chunk                             step.prefill_device_share
    verify          decode_verify, decode_verify_sampled      (no cell speculates)
    draft           draft_propose                             "
    score           score_prompt, embed_forward               (no cell scores or embeds)

    parts
    embed           _embed                                    trunk.decode_glue_ms
    attn.qkv        _qkv, _mla_proj, _mla_decompress, _mla_absorb_q: the layer's
                    input norm, projections, q/k norm, rotary  trunk.decode_proj_ms;
                    (latent attention: the query's and the K/V's latents,
                    W_uk folded into the query)                with attn.out under decode/,
                                                              mla.proj_device_share
    attn.kv_write   ops/attention.py write_kv_entry (the row scatter, or for
                    a page-aligned prefill stream ops/pallas_kv_write.py's
                    copy a page), write_mla_entry (the latent
                    vector, either way)                        trunk.decode_glue_ms (decode/),
                                                              kv.prefill_write_device_share (prefill/, chunk/)
    attn.kernel     each Pallas or reference attention call (ops/attention.py,
                    ops/pallas_*attention*.py, _ragged_reference_attn); under
                    decode/ the paged decode kernel's calls over the attention
                    layers ARE the span's fused decode steps (its latent
                    entry too: mla.decode_attn_*; the ragged kernel's under
                    prefill/ and chunk/, mla.prefill_attn_device_share)
    attn.out        _attn_residual (the heads' output projection and its
                    add to the residual stream), _mla_unabsorb  trunk.decode_proj_ms
    mlp             _mlp_residual, _mlp (its norms, the dense gated or
                    plain MLP, its add to the residual stream)  trunk.decode_proj_ms
    moe.route       _moe_mlp: router, top-k, renormalisation,
                    group sizes, the stable sort; _moe_counts  moe.around_gmm_device_share
    moe.gather      _gather_rows                              "
    moe.experts     _moe_mlp: the three grouped products and
                    what lies between; _moe_dense_experts      (moe.gmm_* read the kernel by its name)
    moe.combine     _moe_mlp: the add-back's permutation, the
                    weighted sum over a token's picks (under a share,
                    _moe_held_experts: the weighted add to
                    each row's token)                          moe.around_gmm_device_share
    moe.shared      _moe_mlp: the shared expert beside the
                    routed ones (LATER_PARTS: the accepted
                    reader files it under mlp, which encloses
                    it)                                        moe.shared_device_share
    ssm.gate        _lin_project, _lin_inputs: a Kimi-delta layer's decay
                    gate, its projection (inside ssm.in_proj) and its
                    activation (inside ssm.conv) (LATER_PARTS: the
                    accepted reader files each under the part that
                    encloses it)                              kda.proj_device_share (through
                                                              the enclosing parts)
    attn.gate       _attn_residual: a latent layer's sigmoid gate a head,
                    its norm, projection and product (inside attn.out;
                    LATER_PARTS)                              trunk.decode_proj_ms (through attn.out)
    ssm.in_proj     _ssm_project, _lin_project                trunk.decode_proj_ms
    ssm.conv        ops/ssm.py causal_conv, next_tail; _ssm_inputs,
                    _lin_inputs; a prefill's read and write of the
                    memory (_keep_tails); under decode/ the memory's
                    step in place on the pool, ops/pallas_conv_tail.py
                    (the custom call _conv_tail_step, one a layer that
                    holds a state a step; no reader matches it by
                    name)                                      ssm.prefill_scan_device_share (prefill/, chunk/)
    ssm.scan        ops/ssm.py ssd_chunk_scan (body included); in
                    _ssm_decode the state update and its inputs;
                    the state's gather and write-back          ssm.prefill_scan_device_share (prefill/, chunk/)
    ssm.out         _ssm_output                               trunk.decode_proj_ms
    head            _unembed: the rows it is taken at, final norm,
                    head, softcap; embed_forward's pooling     trunk.decode_head_ms
    sample          window_sample, window_extras, window_guided_mask,
                    ops/sampling.py compute_logprobs; the argmax of
                    decode_verify and draft_propose, score_prompt's
                    log-softmax and ranks                      trunk.decode_head_ms
    carry           window_slot, window_count_update,
                    window_guided_advance, decode_multi's own step
                    arithmetic and what it lays out after the scan,
                    draft_propose's token fed back             trunk.decode_glue_ms

``trunk.unscoped_device_share`` is the self time under no part at all
(the instrument's error bar); ``step.prefill_device_share`` the self time
under ``prefill/`` and ``chunk/``.
"""

DECODE = "decode"
PREFILL = "prefill"
CHUNK = "chunk"
VERIFY = "verify"
DRAFT = "draft"
SCORE = "score"
PHASES = (DECODE, PREFILL, CHUNK, VERIFY, DRAFT, SCORE)

EMBED = "embed"
ATTN_QKV = "attn.qkv"
ATTN_KV_WRITE = "attn.kv_write"
ATTN_KERNEL = "attn.kernel"
ATTN_OUT = "attn.out"
MLP = "mlp"
MOE_ROUTE = "moe.route"
MOE_GATHER = "moe.gather"
MOE_EXPERTS = "moe.experts"
MOE_COMBINE = "moe.combine"
SSM_IN_PROJ = "ssm.in_proj"
SSM_CONV = "ssm.conv"
SSM_SCAN = "ssm.scan"
SSM_OUT = "ssm.out"
HEAD = "head"
SAMPLE = "sample"
CARRY = "carry"
MOE_SHARED = "moe.shared"
SSM_GATE = "ssm.gate"
ATTN_GATE = "attn.gate"
# the parts ``_scope_trace.py`` files time under: its copy is the
# benchmark's, and only a PR to the benchmark changes it
PARTS = (EMBED, ATTN_QKV, ATTN_KV_WRITE, ATTN_KERNEL, ATTN_OUT, MLP,
         MOE_ROUTE, MOE_GATHER, MOE_EXPERTS, MOE_COMBINE, SSM_IN_PROJ,
         SSM_CONV, SSM_SCAN, SSM_OUT, HEAD, SAMPLE, CARRY)
# parts opened since that copy was taken, each read by a reader of its
# own; that copy files their time under the part that encloses them
LATER_PARTS = (MOE_SHARED, SSM_GATE, ATTN_GATE)
