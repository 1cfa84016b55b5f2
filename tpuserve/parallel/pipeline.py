"""Pipeline parallelism: GPipe-style microbatch pipelining over the 'pp'
mesh axis.

The reference has no pipeline parallelism anywhere (SURVEY.md §2.3 — PP is
"absent everywhere"); this closes that last strategy row the TPU-native
way.  Instead of per-stage processes exchanging activations over NCCL
p2p (the GPU framework idiom), the whole pipeline is ONE jitted SPMD
program: layers are stacked per stage and sharded over the mesh ``pp``
axis, and a ``lax.scan`` over pipeline ticks moves activations
stage-to-stage with ``lax.ppermute`` — XLA schedules the transfer on ICI
between neighbouring devices (the pp axis is placed next to tp in the
grid, parallel/mesh.py).  Each stage holds only its layer slice of the
weights AND of the paged KV cache, so PP divides both per-device weight
and cache footprint by the stage count — the reason to use it: models too
big for one chip even with int8 + TP.

Design notes (why it looks like this):
- **Embed/unembed run outside the shard_map region**, replicated.  They
  are tiny next to the trunk and keeping them out makes the pipelined
  region a pure layer trunk with one carry type.
- **Microbatches, not batch splits**: the batch is cut into M
  microbatches; a scan over M + S - 1 ticks keeps every stage busy once
  the pipeline fills (utilization M / (M + S - 1)).  Decode fills fast:
  S is small (2–8) and M defaults to S.
- **Bubble ticks compute garbage and write nothing**: a stage whose
  microbatch index is out of range runs its layers on whatever is in the
  buffer but its cache writes are masked to ``PAD_SLOT`` (the paged
  scatter drops out-of-range slots — ops/attention.write_kv_entry), so
  correctness needs no control flow, only masking — the XLA-friendly
  form.
- **Uniform-layer models only**: the per-stage trunk is a ``lax.scan``
  over stacked layer params, so per-layer *static* configuration
  (sliding windows, per-layer rope) must be constant across layers.
  Qwen2/3, Llama, Phi-3, OPT qualify; Gemma2/3 and Mistral-window models
  are rejected at stacking time (:func:`check_pipeline_compatible`).

The reference delegates all model parallelism to the vLLM container
(reference: SURVEY.md §2.2 "Tensor/model parallelism" row — vLLM TP via
NCCL); PP here is a from-scratch TPU design, not a port.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuserve.parallel.compat import CHECK_KWARG, shard_map

from tpuserve.models import transformer as tf
from tpuserve.models.config import ModelConfig
from tpuserve.ops import attention as attn_ops
from tpuserve.parallel.mesh import AXIS_PP


def check_pipeline_compatible(cfg: ModelConfig, pp: int) -> None:
    """Raise ValueError unless ``cfg`` can be stage-stacked for ``pp``."""
    if pp < 1:
        raise ValueError(f"pp must be >= 1, got {pp}")
    if cfg.num_layers % pp:
        raise ValueError(
            f"{cfg.name}: num_layers={cfg.num_layers} not divisible by "
            f"pp={pp}")
    windows = {cfg.layer_window(i) for i in range(cfg.num_layers)}
    ropes = {cfg.layer_rope(i) for i in range(cfg.num_layers)}
    if len(windows) > 1 or len(ropes) > 1:
        raise ValueError(
            f"{cfg.name}: per-layer attention windows/rope vary across "
            f"layers (windows={windows}); the pipeline trunk scans a "
            "stacked uniform layer — use tp/ep for this family")
    if cfg.num_experts:
        raise ValueError(
            f"{cfg.name}: MoE + pipeline is not supported (shard experts "
            "over the ep axis instead)")


def _stack_layers(layers: list, pp: int, sharding=None):
    """[L × layer-pytree] -> one pytree with (pp, L/pp, ...) leaves.

    With ``sharding``, the stack runs under jit with ``out_shardings`` so
    the stacked copy is BORN stage-sharded — stacking on the default
    device first would materialise a full second copy of the layers on
    one chip, exactly what pp exists to avoid."""
    def stack(ls):
        st = jax.tree.map(lambda *xs: jnp.stack(xs), *ls)
        return jax.tree.map(
            lambda x: x.reshape(pp, len(ls) // pp, *x.shape[1:]), st)

    if sharding is None:
        return stack(layers)

    K = len(layers) // pp
    def build(*xs):
        # write each layer into a born-sharded zero buffer via
        # dynamic-update-slice: stacking with jnp.stack/concatenate under
        # out_shardings psums the replica axes when the mesh carries
        # dp/ep/tp next to pp (each replica group contributes its copy to
        # the stacked dim), silently scaling every weight by the replica
        # count.  The .at[].set form partitions correctly on every mesh.
        out = jnp.zeros((pp, K) + xs[0].shape, xs[0].dtype)
        for i, x in enumerate(xs):
            out = out.at[divmod(i, K)].set(x)
        return out

    return jax.jit(lambda ls: jax.tree.map(build, *ls),
                   out_shardings=sharding)(layers)


def stack_pipeline_params(params, cfg: ModelConfig, mesh):
    """Split params into (head, stages): ``head`` is the embed / final-norm
    / lm-head pytree (replicated); ``stages`` is the layer stack with
    (pp, L/pp, ...) leaves placed with the stage dim sharded over 'pp'."""
    pp = mesh.shape[AXIS_PP]
    check_pipeline_compatible(cfg, pp)
    head = {k: v for k, v in params.items() if k != "layers"}
    stages = _stack_layers(params["layers"], pp,
                           sharding=NamedSharding(mesh, P(AXIS_PP)))
    head = jax.device_put(head, NamedSharding(mesh, P()))
    return head, stages


def stack_pipeline_cache(kv_cache: list, mesh):
    """Per-layer [{"k","v",...}] cache -> stage-stacked pytree with
    (pp, L/pp, num_blocks, block_size, Hkv, D) leaves sharded over 'pp'.
    Each stage materialises only its slice — per-device cache bytes are
    the full cache divided by the stage count."""
    pp = mesh.shape[AXIS_PP]
    if len(kv_cache) % pp:
        raise ValueError(f"{len(kv_cache)} cache layers not divisible by "
                         f"pp={pp}")
    return _stack_layers(kv_cache, pp,
                         sharding=NamedSharding(mesh, P(AXIS_PP)))


def create_stacked_cache(model_cfg: ModelConfig, cache_cfg, mesh):
    """Allocate a zeroed stage-stacked cache directly as sharded buffers —
    never materialising the full cache on one device (the whole point of
    pp is that it doesn't fit there; an auto-sized pp cache is budgeted at
    ~pp × one device's HBM)."""
    from tpuserve.runtime.kv_cache import create_kv_cache
    pp = mesh.shape[AXIS_PP]
    tmpl = jax.eval_shape(lambda: create_kv_cache(model_cfg, cache_cfg))
    if len(tmpl) % pp:
        raise ValueError(f"{len(tmpl)} cache layers not divisible by "
                         f"pp={pp}")
    K = len(tmpl) // pp
    sh = NamedSharding(mesh, P(AXIS_PP))
    return {key: jnp.zeros((pp, K) + tuple(leaf.shape), leaf.dtype,
                           device=sh)
            for key, leaf in tmpl[0].items()}


def unstack_pipeline_cache(stacked) -> list:
    """Inverse of :func:`stack_pipeline_cache` (tests / cache migration)."""
    flat = jax.tree.map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]), stacked)
    L = jax.tree.leaves(flat)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], flat) for i in range(L)]


def _split_micro(x, M):
    return x.reshape(M, x.shape[0] // M, *x.shape[1:])


def _auto_microbatches(B: int, S: int) -> int:
    """Largest divisor of the batch not exceeding the stage count — the
    most pipeline overlap a clean split allows.  Engine batches are
    power-of-two buckets, but a pp=3 mesh (or an odd caller batch) must
    degrade to fewer microbatches, not crash mid-serving."""
    return max(d for d in range(1, min(S, B) + 1) if B % d == 0)


def _decode_layer(h, lp, entry, cfg, positions, slots, block_tables,
                  seq_lens):
    """One decode layer against the paged cache — the scan body of a
    stage's trunk.  Mirrors transformer._decode_body's inner loop
    (reference attention; Pallas-under-pp is future work — the kernel
    call sites are shared, so it slots in here)."""
    sw = cfg.layer_window(0)
    q, k, v, _ = tf._qkv(h, lp, cfg, positions, 0)
    entry = attn_ops.write_kv_entry(entry, k, v, slots)
    out = attn_ops.paged_decode_attention(
        q, entry["k"], entry["v"], block_tables, seq_lens, cfg.attn_scale,
        k_scale=entry.get("ks"), v_scale=entry.get("vs"),
        sliding_window=sw, logit_softcap=cfg.attn_logit_softcapping)
    h = tf._attn_residual(h, out, lp, cfg)
    h = tf._mlp_residual(h, lp, cfg)
    return h, entry


def _prefill_layer(h, lp, entry, cfg, positions, prompt_lens, slots):
    """One prefill layer: write the prompt's KV, attend causally within
    the (micro)batch — transformer.prefill's inner loop."""
    sw = cfg.layer_window(0)
    q, k, v, _ = tf._qkv(h, lp, cfg, positions, 0)
    entry = attn_ops.write_kv_entry(entry, k, v, slots)
    out = attn_ops.prefill_attention(
        q, k, v, prompt_lens, cfg.attn_scale, sliding_window=sw,
        logit_softcap=cfg.attn_logit_softcapping)
    h = tf._attn_residual(h, out, lp, cfg)
    h = tf._mlp_residual(h, lp, cfg)
    return h, entry


def _pipeline_trunk(mesh, cfg, M, layer_fn, finalize=None):
    """Build the shard_map'd GPipe trunk.

    ``layer_fn(h, lp, entry, mb_meta) -> (h, entry)`` runs one layer on
    one microbatch; ``mb_meta`` is the tuple of per-microbatch metadata
    arrays already indexed to the stage's current microbatch, with cache
    slots masked to PAD_SLOT on bubble ticks.  ``finalize(h_out, meta_t)``
    reduces the last stage's output BEFORE it enters the cross-stage
    broadcast — prefill keeps only each row's last hidden vector, so the
    closing psum moves (mb, H), not the full (mb, T, H) activations.
    """
    S = mesh.shape[AXIS_PP]
    fwd = [(i, i + 1) for i in range(S - 1)]

    def trunk(stage_p, stage_c, h_mb, slots_mb, *meta_mb):
        # local views: strip the size-1 sharded stage dim
        sp = jax.tree.map(lambda x: x[0], stage_p)
        sc = jax.tree.map(lambda x: x[0], stage_c)
        s = jax.lax.axis_index(AXIS_PP)
        fin = finalize or (lambda h, meta: h)
        fin_sd = jax.eval_shape(fin, h_mb[0], tuple(m[0] for m in meta_mb))
        out0 = jnp.zeros((M,) + fin_sd.shape, fin_sd.dtype)
        recv0 = jnp.zeros_like(h_mb[0])                 # (mb, ..., H)

        def tick(carry, t):
            recv, cache, out = carry
            mb_i = t - s
            cl = jnp.clip(mb_i, 0, M - 1)
            valid = (mb_i >= 0) & (mb_i < M)
            x = jnp.where(s == 0, h_mb[cl], recv)
            # bubble ticks must not touch the cache: PAD_SLOT slots are
            # dropped by the paged scatter
            slots_t = jnp.where(valid, slots_mb[cl], attn_ops.PAD_SLOT)
            meta_t = tuple(m[cl] for m in meta_mb)

            def layer(h, xs):
                lp, entry = xs
                return layer_fn(h, lp, entry, slots_t, meta_t)

            h_out, cache = jax.lax.scan(layer, x, (sp, cache))
            keep = fin(h_out, meta_t)
            out = out.at[cl].set(
                jnp.where((s == S - 1) & valid, keep, out[cl]))
            recv = jax.lax.ppermute(h_out, AXIS_PP, fwd) if S > 1 else h_out
            return (recv, cache, out), None

        (_, sc, out), _ = jax.lax.scan(
            tick, (recv0, sc, out0), jnp.arange(M + S - 1))
        # only the last stage holds real outputs; broadcast to every stage
        out = jax.lax.psum(
            jnp.where(s == S - 1, out, jnp.zeros_like(out)), AXIS_PP)
        return out, jax.tree.map(lambda x: x[None], sc)

    specs_in = (P(AXIS_PP), P(AXIS_PP))         # stage params, stage cache
    return partial(shard_map, mesh=mesh, **CHECK_KWARG), trunk, specs_in


@partial(jax.jit, static_argnames=("cfg", "mesh", "num_microbatches"),
         donate_argnames=("stage_cache",))
def pp_decode_step(head, stages, cfg: ModelConfig, tokens, positions,
                   slot_ids, block_tables, seq_lens, stage_cache, *,
                   mesh, num_microbatches: int = 0):
    """One pipelined decode step.

    tokens/positions/slot_ids/seq_lens: (B,); block_tables:
    (B, max_blocks); ``stage_cache`` from :func:`stack_pipeline_cache`.
    Returns (logits (B, V), stage_cache).  ``num_microbatches`` 0 picks
    the stage count (the smallest M that can fill the pipeline).
    """
    S = mesh.shape[AXIS_PP]
    M = num_microbatches or _auto_microbatches(tokens.shape[0], S)
    if tokens.shape[0] % M:
        raise ValueError(f"batch {tokens.shape[0]} not divisible by "
                         f"microbatches {M}")
    h = tf._embed(head, cfg, tokens, positions)            # (B, H)
    h_mb = _split_micro(h, M)
    meta = tuple(_split_micro(x, M)
                 for x in (positions, block_tables, seq_lens))
    slots_mb = _split_micro(slot_ids, M)

    def layer_fn(h, lp, entry, slots_t, meta_t):
        pos_t, bt_t, sl_t = meta_t
        return _decode_layer(h, lp, entry, cfg, pos_t, slots_t, bt_t, sl_t)

    wrap, trunk, specs_in = _pipeline_trunk(mesh, cfg, M, layer_fn)
    out, new_cache = wrap(
        trunk,
        in_specs=specs_in + (P(),) * (2 + len(meta)),
        out_specs=(P(), P(AXIS_PP)),
    )(stages, stage_cache, h_mb, slots_mb, *meta)
    h_out = out.reshape(-1, out.shape[-1])                 # (B, H)
    return tf._unembed(head, cfg, h_out), new_cache


@partial(jax.jit,
         static_argnames=("cfg", "mesh", "steps", "mode", "logprobs_n",
                          "num_microbatches"),
         donate_argnames=("stage_cache",))
def pp_decode_multi(head, stages, cfg: ModelConfig, tokens, positions,
                    block_tables, seq_lens, active, keys, temperature,
                    stage_cache, *, mesh, steps: int, mode: str = "greedy",
                    top_k=None, top_p=None, min_p=None, logprobs_n: int = 0,
                    counts=None, presence=None, frequency=None,
                    repetition=None, bias=None, floor_bias=None,
                    floor_remaining=None,
                    num_microbatches: int = 0):
    """``steps`` fused decode+sample iterations through the staged trunk
    in ONE dispatch — transformer.decode_multi's contract over a pp mesh.

    Each iteration is a full pipeline pass (M microbatches overlap across
    stages); the sampled token feeds the next iteration entirely on
    device, so the host syncs once per window instead of once per token —
    the same S-fold host-round-trip saving as on the single-device engine
    (not measured on the current code).  Sampling runs on the replicated logits
    outside the shard_map region.  Slot ids are derived on device from
    ``block_tables`` and the advancing positions; the window's KV slots
    must be pre-reserved (engine._try_reserve_window).
    """
    S = mesh.shape[AXIS_PP]
    B = tokens.shape[0]
    M = num_microbatches or _auto_microbatches(B, S)
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    block_size = jax.tree.leaves(stage_cache)[0].shape[3]

    def layer_fn(h, lp, entry, slots_t, meta_t):
        pos_t, bt_t, sl_t = meta_t
        return _decode_layer(h, lp, entry, cfg, pos_t, slots_t, bt_t, sl_t)

    wrap, trunk, specs_in = _pipeline_trunk(mesh, cfg, M, layer_fn)
    run_trunk = wrap(trunk, in_specs=specs_in + (P(),) * 5,
                     out_specs=(P(), P(AXIS_PP)))
    bt_mb = _split_micro(block_tables, M)

    def one(carry, s):
        toks, pos, lens, cache, cnt = carry
        # slot derivation + sampling + extras shared with decode_multi
        # (models/transformer.py window_slot/window_sample/window_extras)
        # — the two fused-window implementations must not drift.  The
        # logits are replicated outside the shard_map region, so the
        # extras apply exactly as on the single-device trunk.
        slot = tf.window_slot(block_tables, pos, active, block_size)
        h = tf._embed(head, cfg, toks, pos)
        out, cache = run_trunk(stages, cache, _split_micro(h, M),
                               _split_micro(slot, M), _split_micro(pos, M),
                               bt_mb, _split_micro(lens, M))
        logits = tf._unembed(head, cfg, out.reshape(B, -1))
        logits = tf.window_extras(logits, s, cnt, presence, frequency,
                                  repetition, bias, floor_bias,
                                  floor_remaining)
        nxt = tf.window_sample(logits, keys, temperature, s, mode,
                               top_k=top_k, top_p=top_p, min_p=min_p)
        cnt = tf.window_count_update(cnt, nxt)
        ys = nxt
        if logprobs_n:
            from tpuserve.ops.sampling import compute_logprobs
            ys = (nxt, compute_logprobs(logits, nxt, logprobs_n))
        return (nxt, pos + 1, lens + 1, cache, cnt), ys

    carry = (tokens, positions, seq_lens, stage_cache, counts)
    (_, _, _, stage_cache, _), outs = jax.lax.scan(
        one, carry, jnp.arange(steps, dtype=jnp.int32))
    if logprobs_n:
        out, lp = tf.window_unpack_lp(outs)
        return out, stage_cache, lp
    return jnp.swapaxes(outs, 0, 1), stage_cache


@partial(jax.jit, static_argnames=("cfg", "mesh", "num_microbatches"),
         donate_argnames=("stage_cache",))
def pp_prefill(head, stages, cfg: ModelConfig, tokens, prompt_lens,
               slot_ids, stage_cache, *, mesh, num_microbatches: int = 0):
    """Pipelined prefill: (B, T) right-padded prompts through the staged
    trunk; writes each stage's KV slice and returns (last_logits (B, V),
    stage_cache) — transformer.prefill's contract."""
    S = mesh.shape[AXIS_PP]
    B, T = tokens.shape
    M = num_microbatches or _auto_microbatches(B, S)
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    positions = jnp.arange(T)[None, :].repeat(B, axis=0)
    h = tf._embed(head, cfg, tokens, positions)            # (B, T, H)
    h_mb = _split_micro(h, M)
    slots_mb = _split_micro(slot_ids, M)
    meta = (_split_micro(positions, M), _split_micro(prompt_lens, M))

    def layer_fn(h, lp, entry, slots_t, meta_t):
        pos_t, plens_t = meta_t
        return _prefill_layer(h, lp, entry, cfg, pos_t, plens_t, slots_t)

    def finalize(h_out, meta_t):
        # keep each row's last valid hidden vector only: the closing
        # cross-stage broadcast then moves (mb, H) instead of (mb, T, H)
        _, plens_t = meta_t
        last = jnp.maximum(plens_t - 1, 0)
        return jnp.take_along_axis(h_out, last[:, None, None], axis=1)[:, 0]

    wrap, trunk, specs_in = _pipeline_trunk(mesh, cfg, M, layer_fn,
                                            finalize=finalize)
    out, new_cache = wrap(
        trunk,
        in_specs=specs_in + (P(),) * (2 + len(meta)),
        out_specs=(P(), P(AXIS_PP)),
    )(stages, stage_cache, h_mb, slots_mb, *meta)
    h_last = out.reshape(B, -1)
    return tf._unembed(head, cfg, h_last), new_cache
