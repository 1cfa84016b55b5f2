"""The Pallas kernels compile for the chip — asked of the chip's own
compiler, with no chip attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
a slice that is not tile-aligned, or more scoped VMEM than a kernel may
use.  Here each kernel of the serving path is AOT-compiled for a described
TPU v5e at the widths of the models the engine serves (Qwen3-0.6B, the
flagship; Llama-3.1-8B, the registered model that needs tp) and at the
sizes the engine dispatches, with ``interpret=False``.  Nothing runs: a
compile that passes says nothing about results or times.

All AOT compiles live in this ONE file: only one process may load the
TPU's library, so the topology is described inside a fixture, by the one
xdist worker that is given this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# (num_q_heads, num_kv_heads, head_dim)
WIDTHS = {
    "qwen3-0.6b": (16, 8, 128),
    "llama-8b": (32, 8, 128),        # Mistral-7B's widths too
    "llama-8b-tp4": (8, 2, 128),     # one shard of the four-chip smoke
    # five query heads a KV head: the first group that is not a power of
    # two (the block-size clamps halve rows, never heads, so it needs no
    # rule of its own; these compiles are the check)
    "falcon-h1-34b": (20, 4, 128),
    # eight query heads a KV head, nine layers in twelve behind a 1,024
    # window: the decode kernel alone (its other kernels compile at these
    # head counts inside the 12-layer trunks compiled by hand, PR 35)
    "mellum2-12b": (32, 4, 128),
    # thirty KV heads of ONE query head each (plain multi-head attention),
    # which reach the kernels as 32 and 32 (ModelConfig.cache_kv_heads: a
    # page row of 30 heads is not whole sublane tiles): a page of 32
    # tokens is a (1024, 128) slab, 262 KB a side, where the widths above
    # have 4 to 8 KV heads of 2 to 8 query heads each
    "olmo-hybrid-7b": (32, 32, 128),
}
PAGE = 32            # server default --block-size
NUM_BLOCKS = 2048    # server default --num-blocks
MAX_PAGES = 128      # 4096-token sequences
MAX_NUM_SEQS = 64    # SchedulerConfig.max_num_seqs
CHUNK = 2048         # SchedulerConfig.prefill_chunk_size
MIN_BUCKET = 32      # SchedulerConfig.min_prefill_bucket
MIXED_BUDGET = 512   # SchedulerConfig.mixed_token_budget
PREFILL_SEQS = 8     # SchedulerConfig.max_prefill_seqs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep it out of these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _decode(S, hq, hkv, d, quantized, window=None):
    from tpuserve.ops.pallas_paged_attention import paged_decode_attention
    B = MAX_NUM_SEQS
    pages, scales = _cache(S, hkv, d, quantized)
    return (lambda q, k, v, bt, sl, *s: paged_decode_attention(
        q, k, v, bt, sl, d ** -0.5, interpret=False, sliding_window=window,
        **_scales(s)),
        [S((B, hq, d), jnp.bfloat16), *pages, S((B, MAX_PAGES), jnp.int32),
         S((B,), jnp.int32), *scales])


def _flash(S, hq, hkv, d, quantized):
    from tpuserve.ops.pallas_flash_attention import flash_prefill_attention
    B, T = 8, 1024       # max_prefill_seqs x (max_prefill_tokens / 8)
    kv = S((B, T, hkv, d), jnp.bfloat16)
    return (lambda q, k, v, n: flash_prefill_attention(
        q, k, v, n, d ** -0.5, interpret=False),
        [S((B, T, hq, d), jnp.bfloat16), kv, kv, S((B,), jnp.int32)])


def _window(S, hq, hkv, d, quantized, C=CHUNK):
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    lens = S((1,), jnp.int32)       # the engine chunks one sequence at a time
    pages, scales = _cache(S, hkv, d, quantized)
    return (lambda q, k, v, bt, cx, ck, *s: paged_window_attention(
        q, k, v, bt, cx, ck, d ** -0.5, interpret=False, **_scales(s)),
        [S((1, C, hq, d), jnp.bfloat16), *pages,
         S((1, MAX_PAGES), jnp.int32), lens, lens, *scales])


def _decode_windowed(S, hq, hkv, d, quantized):
    """The decode kernel behind Mellum 2's sliding window: pages before
    the window are skipped by the same DMA chain."""
    return _decode(S, hq, hkv, d, quantized, window=1024)


def _tail(S, hq, hkv, d, quantized):
    """The window kernel at the smallest chunk bucket: the tail of a long
    prompt, or the few tokens a prefix-cache hit leaves to compute."""
    return _window(S, hq, hkv, d, quantized, C=MIN_BUCKET)


def _ragged(S, hq, hkv, d, quantized, monkeypatch, T=MIXED_BUDGET,
            B=MAX_NUM_SEQS, decode_rows=True):
    """The ragged kernel at a mixed step's shape (the default), or at a
    packed batched prefill's: any rung T of the engine's flat-token
    ladder, the descriptors PREFILL_SEQS wide, built without the decode
    part (``decode_rows=False``)."""
    from tpuserve.ops import pallas_ragged_attention as ragged
    # ragged_block() asks jax.default_backend(), which is the CPU here:
    # steer it to the block the engine packs with on a TPU
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        blk = ragged.ragged_block()
    seq = S((B,), jnp.int32)
    pages, scales = _cache(S, hkv, d, quantized)
    return (lambda q, k, v, bt, kl, qs, ql, m, bs, *s:
            ragged.ragged_paged_attention(
                q, k, v, bt, kl, qs, ql, m, bs, d ** -0.5, interpret=False,
                blk_q=blk, decode_rows=decode_rows, **_scales(s)),
            [S((T, hq, d), jnp.bfloat16), *pages,
             S((B, MAX_PAGES), jnp.int32), seq, seq, seq,
             S((2,), jnp.int32), S((T // blk,), jnp.int32), *scales])


def _cache(S, hkv, d, quantized):
    """([k, v] page arrays, [k_scale, v_scale] — empty unless int8)."""
    from tpuserve.ops.attention import SCALE_LANES
    page = S((NUM_BLOCKS, PAGE, hkv, d),
             jnp.int8 if quantized else jnp.bfloat16)
    scale = S((NUM_BLOCKS, PAGE, SCALE_LANES), jnp.float32)
    return [page, page], [scale, scale] if quantized else []


def _scales(s):
    return dict(k_scale=s[0], v_scale=s[1]) if s else {}


CASES = [(kernel, width, False)
         for kernel in ("decode", "flash", "window", "tail", "ragged")
         for width in WIDTHS
         # the ragged kernel has no tp wrapper (mixed steps run reference
         # attention under a mesh), so a tp shard never reaches it
         if (kernel, width) != ("ragged", "llama-8b-tp4")
         and (kernel == "decode" or width != "mellum2-12b")]
CASES += [("decode-w1024", "mellum2-12b", False)]
CASES += [(kernel, width, True)
          for kernel in ("decode", "window", "ragged")
          for width in ("qwen3-0.6b", "llama-8b")]
# the hybrid models never take a mesh, an int8 cache is not in their cells
CASES = [c for c in CASES if c[1] != "falcon-h1-34b" or not c[2]]


@pytest.mark.parametrize(
    "kernel,width,quantized", CASES,
    ids=[f"{k}-{w}{'-int8kv' if q else ''}" for k, w, q in CASES])
def test_kernel_compiles_for_v5e(kernel, width, quantized, one_chip,
                                 monkeypatch):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    build = {"decode": _decode, "decode-w1024": _decode_windowed,
             "flash": _flash, "window": _window, "tail": _tail,
             "ragged": _ragged}[kernel]
    extra = (monkeypatch,) if kernel == "ragged" else ()
    fn, args = build(S, *WIDTHS[width], quantized, *extra)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if kernel.startswith("decode"):
        # the kernel reads a page as the (page x Hkv, D) slab it is stored
        # as: the view is a bitcast of the cache, never a copy of it
        import re
        assert re.search(rf"\[{NUM_BLOCKS},{PAGE * WIDTHS[width][1]},128\]"
                         r"[^\n]* bitcast\(", text)
        assert not re.search(rf"\[{NUM_BLOCKS},[^\n]* copy\(", text)


@pytest.mark.parametrize("width", ["qwen3-0.6b", "llama-8b",
                                   "falcon-h1-34b", "olmo-hybrid-7b"])
@pytest.mark.parametrize("rows", [128, 1792, 8192])
def test_ragged_kernel_compiles_at_the_packed_prefill_ladder(
        rows, width, one_chip, monkeypatch):
    """A packed batched prefill (engine ``_run_prefill``) dispatches the
    ragged kernel at a rung of ``packed_prefill_bucket``'s ladder, not at
    a power of two: its ends and a middle rung, at both cells' widths.
    The kernel raises, rather than shrink its block, when the VMEM budget
    is short, so this is also the check that 128-row blocks fit 32 query
    heads."""
    from tpuserve.runtime.scheduler import packed_prefill_bucket

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert packed_prefill_bucket(rows, 128) == rows
    fn, args = _ragged(S, *WIDTHS[width], False, monkeypatch, T=rows,
                       B=PREFILL_SEQS, decode_rows=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _ssm_update(S, rows=MAX_NUM_SEQS, heads=32, head=128, state=256,
                groups=2):
    """The decode-time state update at Falcon-H1-34B's sizes: a full
    decode batch on a pool of one seat a row and the trash seat."""
    from tpuserve.ops.pallas_ssm_update import ssm_state_update
    f32 = jnp.float32
    return (lambda pool, seats, decay, dtx, b, c: ssm_state_update(
        pool, seats, decay, dtx, b, c, interpret=False),
        [S((MAX_NUM_SEQS + 1, heads, head, state), f32),
         S((rows,), jnp.int32), S((rows, heads), f32),
         S((rows, heads, head), f32), S((rows, groups, state), f32),
         S((rows, groups, state), f32)])


@pytest.mark.parametrize("rows", [4, MAX_NUM_SEQS])
def test_the_state_update_kernel_compiles_for_v5e(rows, one_chip):
    """``_ssm_state_update`` at the smallest and the largest decode bucket:
    compiled, named as the benchmark's ``ssm.*`` readers match it, and in
    place — the pool's bytes are aliased from input to output, not
    copied (65 seats x 4 MiB would be 273 MB a layer a step)."""
    import re

    from tpuserve.ops.pallas_ssm_update import KERNEL_NAME

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert KERNEL_NAME == "_ssm_state_update"
    fn, args = _ssm_update(S, rows)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", compiled.as_text())
    pool_bytes = (MAX_NUM_SEQS + 1) * 32 * 128 * 256 * 4
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


def _olmo_hybrid(**cut):
    import dataclasses

    from tpuserve.models.config import get_model_config
    return dataclasses.replace(get_model_config("allenai/Olmo-Hybrid-7B"),
                               **cut)


@pytest.mark.parametrize("rows", [4, MAX_NUM_SEQS])
def test_the_gdn_state_update_kernel_compiles_for_v5e(rows, one_chip):
    """``_gdn_state_update`` at Olmo-Hybrid-7B's sizes (30 heads of 96 x
    192, two a slab) at the smallest and the largest decode bucket:
    compiled, named as the benchmark's ``lin.*`` readers match it, and in
    place -- the pool's bytes are aliased from input to output, not copied
    -- and the pool holds no padding: 65 seats x 2,211,840 B."""
    import re

    from tpuserve.ops.pallas_gdn_update import (KERNEL_NAME, gdn_state_update,
                                                heads_per_slab)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert KERNEL_NAME == "_gdn_state_update"
    H, dk, dv, f32 = 30, 96, 192, jnp.float32
    hp = heads_per_slab(H, dv)
    pool = S((MAX_NUM_SEQS + 1, H // hp, dk, hp * dv), f32)
    compiled = jax.jit(
        lambda pool, seats, q, k, v, g, b: gdn_state_update(
            pool, seats, q, k, v, g, b, interpret=False),
        donate_argnums=(0,)).lower(
            pool, S((rows,), jnp.int32), S((rows, H, dk), f32),
            S((rows, H, dk), f32), S((rows, H, dv), f32), S((rows, H), f32),
            S((rows, H), f32)).compile()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", compiled.as_text())
    pool_bytes = (MAX_NUM_SEQS + 1) * H * dk * dv * 4
    assert pool_bytes == 65 * 2_211_840
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


# the convolution memory's decode step at both families' published sizes:
# (channels, the pool's dtype, a bias or none)
CONV_TAILS = {"olmo-hybrid-7b": (11520, jnp.float32, False),
              "falcon-h1-34b": (5120, jnp.bfloat16, True)}


@pytest.mark.parametrize("rows", [4, MAX_NUM_SEQS])
@pytest.mark.parametrize("family", sorted(CONV_TAILS))
def test_the_conv_tail_kernel_compiles_for_v5e(family, rows, one_chip):
    """``_conv_tail_step`` at the smallest and the largest decode bucket:
    compiled, named, in place -- the pool's bytes are aliased from input
    to output, as the chip tiles them (90 sublanes of float32 stored as
    96; 40 of bfloat16, two a word, as 40) -- and the pool operand is left
    in HBM (no ``S(1)`` in its layout: the compiler stages a 9 MB operand
    of a custom call through its faster memory otherwise)."""
    from tpuserve.ops.pallas_conv_tail import (KERNEL_NAME, conv_tail_step,
                                               tail_slab)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert KERNEL_NAME == "_conv_tail_step"
    C, dtype, biased = CONV_TAILS[family]
    W = 4
    pool = S((MAX_NUM_SEQS + 1, W - 1, *tail_slab(C)), dtype)
    args = [pool, S((rows,), jnp.int32), S((rows, C), dtype),
            S((W, C), jnp.bfloat16)] + ([S((C,), jnp.bfloat16)] * biased)
    compiled = jax.jit(
        lambda pool, seats, x, k, b=None: conv_tail_step(
            pool, seats, x, k, b, interpret=False),
        donate_argnums=(0,)).lower(*args).compile()
    call = re.search(rf"%{KERNEL_NAME}(\.\d+)? = ([^\n]*)custom-call\([^\n]*"
                     r"tpu_custom_call", compiled.as_text())
    assert call
    pool_out = re.findall(r"[a-z0-9]+\[65,3,\d+,128\]\{[^}]*\}", call.group(2))
    assert pool_out and "S(1)" not in pool_out[0], call.group(2)
    sublanes = {jnp.float32: 96, jnp.bfloat16: 40}[dtype]
    assert compiled.memory_analysis().alias_size_in_bytes == (
        65 * 3 * sublanes * 128 * jnp.dtype(dtype).itemsize)


# the flat-token rungs of a packed prefill at 128-row ragged blocks
# (scheduler.packed_prefill_bucket: every rung to the budget of 8,192)
LIN_TOKENS = [128, 256, 512, 768, 1024, 1280, 1536, 1792, 2048, 3072, 4096,
              6144, 8192]


@pytest.mark.parametrize("tokens", LIN_TOKENS)
def test_a_linear_layer_compiles_for_v5e_at_every_rung(tokens, one_chip):
    """One linear-attention layer of Olmo-Hybrid-7B at the published
    widths over a packed prefill of ``tokens`` flat rows, eight prompts:
    its projections, the convolution, the chunked scan (chunk 64: the
    triangular solve a chunk and the ``lax.scan`` over chunks), the gated
    norm and the write of the seats' state and memory into the pool,
    which stays in place."""
    from tpuserve.models import transformer
    from tpuserve.models.weights import init_params
    from tpuserve.runtime.kv_cache import create_ssm_state
    from tpuserve.runtime.scheduler import packed_prefill_bucket

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def place(tree):
        return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)

    assert packed_prefill_bucket(tokens, 128) == tokens
    cfg = _olmo_hybrid(num_layers=1)
    lp = place(jax.eval_shape(lambda: init_params(cfg, 0))["layers"][0])
    assert "lin" in lp and "q_proj" not in lp and "o_proj" not in lp
    entry = place(jax.eval_shape(
        lambda: create_ssm_state(cfg, MAX_NUM_SEQS))[0])
    i32, seqs = jnp.int32, S((PREFILL_SEQS,), jnp.int32)

    def layer(h, lp, positions, slots, blk_seq, q_starts, q_lens, entry,
              seats):
        h, entry = transformer._lin_packed(h, lp, cfg, positions, slots,
                                           blk_seq, q_starts, q_lens, 128,
                                           entry, seats)
        return transformer._mlp_residual(h, lp, cfg), entry

    compiled = jax.jit(layer, donate_argnums=(7,)).lower(
        S((tokens, cfg.hidden_size), jnp.bfloat16), lp, S((tokens,), i32),
        S((tokens,), i32), S((tokens // 128,), i32), seqs, seqs, entry,
        seqs).compile()
    mem = compiled.memory_analysis()
    # (the convolution's three rows a seat, 90 sublanes of whole lane
    # tiles each, are stored as 96)
    pool_bytes = 65 * (2_211_840 + 3 * 96 * 128 * 4)
    assert mem.alias_size_in_bytes == pool_bytes
    # what the layer holds beside its weights and the pool: activations a
    # few times the stream's q, k, v in float32, never a copy of the pool
    assert mem.temp_size_in_bytes < 40 * tokens * 11520 * 4 + (64 << 20)


# a cell's three served trunks: (program, flat tokens; 0 = one ragged block)
CELL_PROGRAMS = [("decode_multi", 0), ("forward_ragged", 8192),
                 ("prefill_chunk", 0)]


def _compile_cell_program(cfg, program, tokens, num_blocks, one_chip,
                          monkeypatch):
    """One served trunk of a cell at its published widths, compiled for
    the described chip: a fused window of 64 rows and 8 steps, the top
    rung of the packed prefill or a chunk, beside ``num_blocks`` pages of
    32 tokens and the seat pool."""
    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def place(tree):
        return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    blk = ragged_block_for(cfg.cache_q_heads, cfg.cache_kv_heads,
                           cfg.head_dim, PAGE, 2, 2)
    assert blk == 128
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=MAX_NUM_SEQS, steps=8, tokens=tokens or blk,
        blk=blk, prompts=PREFILL_SEQS, chunk=CHUNK, block_size=PAGE,
        num_blocks=num_blocks, max_blocks=MAX_PAGES,
        attn_impl="pallas")[program]
    return fn.lower(*args, **kwargs).compile()


@pytest.mark.parametrize("program,tokens", CELL_PROGRAMS)
def test_the_olmo_hybrid_cell_fits_the_chip(program, tokens, one_chip,
                                            monkeypatch):
    """The cell's whole trunks at the published widths: 16 layers (12
    linear, 4 full), a fused decode window of 64 rows, the top rung of the
    packed-prefill ladder and a chunk, beside a pool of 2,560 pages of 32
    tokens for the 4 attention layers (what 0.9 of the chip leaves after
    8.2 GB of weights and 1.83 GB of state).  The chip's compiler refuses
    what does not fit 16 GB; 30 query heads on 30 KV heads reach the
    kernels as 32 on 32 and keep the 128-row ragged block."""
    cfg = _olmo_hybrid(num_layers=16)
    assert (cfg.cache_q_heads, cfg.cache_kv_heads) == (32, 32)
    compiled = _compile_cell_program(cfg, program, tokens, 2560, one_chip,
                                     monkeypatch)
    mem = compiled.memory_analysis()
    weights = mem.argument_size_in_bytes - mem.alias_size_in_bytes
    assert 8.1e9 < weights < 8.3e9, weights
    # pages and pool stay in place, whole, in every program: 4 layers'
    # pages and 12 layers' seats (trunk_programs gives each program the
    # same pool: the window's 64 rows, one seat more and the trash seat;
    # a seat's three convolution rows, 90 sublanes each, stored as 96)
    pages = 4 * 2 * 2560 * PAGE * 32 * 128 * 2
    seat = 2_211_840 + 3 * 96 * 128 * 4
    assert mem.alias_size_in_bytes == pages + 12 * 66 * seat
    # beside them what a dispatch holds of its own stays under the tenth
    # of the chip the cache's sizer leaves free
    assert mem.temp_size_in_bytes < 1.4e9, mem.temp_size_in_bytes
    # 16.91 GB less the runtime's own 0.27: what the compiler itself
    # holds a program to (2,560 pages here; the sizer gives ~2,470)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.6e9
    text = compiled.as_text()
    if program == "decode_multi":
        assert "_paged_decode_attention" in text
        # the convolution's memory moves once a linear layer a step, by
        # its kernel, as the state beside it does by its own ...
        calls = {k: re.findall(rf"%{k}(?:\.\d+)? = ([^\n]*?)custom-call\(",
                               text)
                 for k in ("_conv_tail_step", "_gdn_state_update")}
        assert len(calls["_conv_tail_step"]) == 12
        assert len(calls["_gdn_state_update"]) == 12
        # ... on a pool the compiler leaves in HBM: no operand or result of
        # the call in its faster memory space, and no asynchronous copy of
        # anything of the pool's shape (it staged each layer's 9 MB there
        # and back around the gather and scatter this kernel replaced,
        # every step: PERF.md §6, PR 46)
        of_pool = r"f32\[6[456],3,(?:90,128|11520)\]"
        for out in calls["_conv_tail_step"]:
            assert not re.search(of_pool + r"\{[^}]*S\(1\)", out), out
        staged = [line for line in text.split("\n")
                  if re.search(r" (copy|slice)-start\(", line)
                  and re.search(of_pool, line)]
        assert not staged, staged[:2]


# what the three trunks of ``falcon-h1-34b-l6.reason`` held at the parent of
# PR 46 (the convolution's memory as ``(66, 3, 5120)``, stepped by XLA's
# gather, taps and scatter), compiled as below: (argument, temporary) bytes
FALCON_H1_BEFORE = {"decode_multi": (15_244_633_600, 55_074_304),
                    "forward_ragged": (15_244_704_256, 1_630_251_008),
                    "prefill_chunk": (15_244_616_704, 318_360_064)}


@pytest.mark.parametrize("program,tokens", CELL_PROGRAMS)
def test_the_falcon_h1_cell_holds_no_more_than_before(program, tokens,
                                                      one_chip, monkeypatch):
    """The served programs of the Falcon-H1 cell at the published widths
    (6 layers, a fused window of 64 rows, the top rung of the packed
    prefill and a chunk, beside the 7,785 pages the sizer gives the cell:
    PERF.md §4) hold no more of the chip with the convolution's memory as
    ``(seats, 3, 40, 128)`` than with ``(seats, 3, 5120)``: arguments and
    temporaries at or under the parent's, pages and pool whole in place.
    (The cell's ``memory_peak_bytes`` reads 381 MB higher since PR 46:
    not in these programs, PERF.md §7 row 27.)"""
    import dataclasses

    from tpuserve.models.config import get_model_config
    cfg = dataclasses.replace(
        get_model_config("tiiuae/Falcon-H1-34B-Instruct"), num_layers=6)
    compiled = _compile_cell_program(cfg, program, tokens, 7785, one_chip,
                                     monkeypatch)
    mem = compiled.memory_analysis()
    argument, temp = FALCON_H1_BEFORE[program]
    assert mem.argument_size_in_bytes <= argument
    assert mem.temp_size_in_bytes <= temp
    # 6 layers' pages (4 KV heads) and 6 layers' seats: 32 heads of 128 x
    # 256 float32 and three rows of 40 bfloat16 sublanes, stored as 40
    pages = 6 * 2 * 7785 * PAGE * 4 * 128 * 2
    seat = 32 * 128 * 256 * 4 + 3 * 40 * 128 * 2
    assert mem.alias_size_in_bytes == pages + 6 * 66 * seat
    text = compiled.as_text()
    staged = [line for line in text.split("\n")
              if re.search(r" (copy|slice)-start\(", line)
              and re.search(r"bf16\[6[456],3,(?:40,128|5120)\]", line)]
    assert not staged, staged[:2]
    if program == "decode_multi":
        for kernel in ("_conv_tail_step", "_ssm_state_update"):
            assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == 6


@pytest.mark.parametrize("rows", [128, 2048, 8192])
@pytest.mark.parametrize("kv_heads", [8, 4, 32])
def test_the_page_writer_compiles_for_v5e(kv_heads, rows, one_chip):
    """``_paged_kv_write`` at the configurations' KV widths (8, 4 and 32
    heads of 128), from one ragged block to the packed ladder's top and at
    the chunk size: compiled, named, and in place: both caches' bytes are
    aliased from input to output and the program holds no other buffer of
    their size (the benchmark's caches fill the chip: a copy cannot
    exist), and the stream's rows reach the kernel without a relayout."""
    import re

    from tpuserve.ops.pallas_kv_write import KERNEL_NAME, paged_kv_write

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert KERNEL_NAME == "_paged_kv_write"
    bf16 = jnp.bfloat16
    page = S((NUM_BLOCKS, PAGE, kv_heads, 128), bf16)
    new = S((rows, kv_heads, 128), bf16)
    compiled = jax.jit(
        lambda kc, vc, k, v, slots: paged_kv_write(kc, vc, k, v, slots,
                                                   interpret=False),
        donate_argnums=(0, 1)).lower(
            page, page, new, new, S((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", text)
    assert "scatter" not in text
    cache_bytes = 2 * NUM_BLOCKS * PAGE * kv_heads * 128 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    # what else the program holds: the zeroed rows, never a page array
    assert mem.temp_size_in_bytes <= 2 * rows * kv_heads * 128 * 2 + 65536


# (rows, contraction, output columns) of the expert layer's grouped
# products at Mellum2-12B-A2.5B's widths (64 experts of width 896 on a
# hidden size of 2,304, 8 a token): the smallest and the largest decode
# bucket, and packed prefills of 512 and 8,192 tokens (max_prefill_tokens)
MOE_EXPERTS = 64
MOE_SHAPES = {
    "decode-8.up": (8 * 8, 2304, 896),
    "decode-64.up": (64 * 8, 2304, 896),
    "decode-64.down": (64 * 8, 896, 2304),
    "prefill-512.up": (512 * 8, 2304, 896),
    "prefill-8192.up": (8192 * 8, 2304, 896),
    "prefill-8192.down": (8192 * 8, 896, 2304),
}


@pytest.mark.parametrize("shape", sorted(MOE_SHAPES))
def test_the_grouped_product_compiles_for_v5e(shape, one_chip):
    """``_moe_grouped_matmul`` at the tiles ``tiling`` picks for each
    regime: compiled (the blocks fit the VMEM limit the kernel asks for),
    and named as the benchmark's ``moe.*`` readers match it."""
    import re

    from benchmark.layer_metrics import _moe_trace
    from tpuserve.ops.pallas_moe_gmm import KERNEL_NAME, grouped_matmul

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert KERNEL_NAME == _moe_trace.KERNEL == "_moe_grouped_matmul"
    m, k, n = MOE_SHAPES[shape]
    text = jax.jit(lambda lhs, rhs, sizes: grouped_matmul(
        lhs, rhs, sizes, interpret=False)).lower(
            S((m, k), jnp.bfloat16), S((MOE_EXPERTS, k, n), jnp.bfloat16),
            S((MOE_EXPERTS,), jnp.int32)).compile().as_text()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", text)


# EVERY flat-token rung a packed prefill of the benchmark's cells can take
# (scheduler.packed_prefill_bucket: 13 rungs to max_prefill_tokens; all 13
# and the chunk program were also compiled as whole 12-layer trunks by
# hand, PRs 35 and 42) and the largest decode bucket
MOE_TOKENS = [128, 256, 512, 768, 1024, 1280, 1536, 1792, 2048, 3072, 4096,
              6144, 8192, 64]


@pytest.mark.parametrize("tokens", MOE_TOKENS)
def test_the_expert_layer_compiles_for_v5e_at_every_rung(tokens, one_chip,
                                                         monkeypatch):
    """The whole sparse expert layer (router, sort, the rows' gather, three
    grouped products, the add-back) at Mellum2-12B-A2.5B's widths.  What
    this guards: the TPU compiler refuses the PLAIN row gather of 1,536
    tokens into 12,288 rows for the grouped product (scoped VMEM, by
    0.4 MB; found on the chip, PR 35) and no other rung; what
    ``_gather_rows`` chooses compiles at all of them: the rows go into
    expert order plain from 16,384 rows (then no ``(rows, 18, 128)``
    array and none of its relayout copies is left in the program) and as
    ``(tiles, 128)`` slices under that, and come back plain wherever a
    prefill permutes them (PR 42)."""
    import dataclasses
    import re

    from tpuserve.models import transformer
    from tpuserve.models.config import get_model_config
    from tpuserve.ops.pallas_moe_gmm import grouped_matmul

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the kernel's wrapper asks jax.default_backend(), which is the CPU
    # here: steer it to the compiled kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        get_model_config("JetBrains/Mellum2-12B-A2.5B-Instruct"),
        num_layers=1)
    H, I, E = cfg.hidden_size, cfg.expert_intermediate_size, cfg.num_experts
    bf16 = jnp.bfloat16
    p = {"router": {"kernel": S((H, E), bf16)},
         "experts": {"gate_proj": {"kernel": S((E, H, I), bf16)},
                     "up_proj": {"kernel": S((E, H, I), bf16)},
                     "down_proj": {"kernel": S((E, I, H), bf16)}}}
    compiled = jax.jit(lambda x, p: transformer._moe_mlp(x, p, cfg)).lower(
        S((tokens, H), bf16), p).compile()
    text = compiled.as_text()
    assert text.count("_moe_grouped_matmul") >= 3
    # the experts' kernels go to the custom calls as they are: no copy
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 5 * tokens * 8 * H * 2 + (64 << 20)
    rows = tokens * cfg.num_experts_per_tok
    into, back = transformer.moe_plain_moves(cfg, tokens)
    assert (into, back) == (rows >= 16384, rows > 1024)
    sliced = len(re.findall(rf"= bf16\[{rows},18,128\][^\n]* fusion\(", text))
    assert sliced == (not into) + (not back), (sliced, into, back)
    if tokens == 1536:
        k = cfg.num_experts_per_tok
        with pytest.raises(Exception, match="vmem"):
            jax.jit(lambda x, order, w, sizes:
                    grouped_matmul(x[order // k], w, sizes, interpret=False)
                    ).lower(S((tokens, H), bf16), S((tokens * k,), jnp.int32),
                            S((E, H, I), bf16), S((E,), jnp.int32)).compile()


# K-EXAONE-236B-A23B's share of the benchmark's cell: 16 of 128 experts of
# width 2,048 on a hidden size of 6,144, 8 a token; the flat-token rungs
# of its packed prefills (64-row ragged blocks, so multiples of 128 up to
# 1,024, of 512 up to 2,048, of 1,024 above) and the largest decode bucket
HELD_TOKENS = [64, 128, 512, 1024, 1536, 2048, 3072, 4096, 8192]


def _k_exaone_share(**cut):
    import dataclasses

    from tpuserve.models.config import get_model_config
    return dataclasses.replace(
        get_model_config("LGAI-EXAONE/K-EXAONE-236B-A23B"),
        moe_experts_held=16, vocab_size=19200, **cut)


@pytest.mark.parametrize("tokens", HELD_TOKENS)
def test_the_expert_layer_under_a_share_compiles_for_v5e_at_every_rung(
        tokens, one_chip, monkeypatch):
    """The whole expert layer told it holds 16 of 128 experts (router over
    all 128, the sort of the picks, the loop over pieces with the rows'
    gather, three grouped products and the add to the tokens, the shared
    expert) at K-EXAONE's widths.  What this guards: the layer's transient
    memory follows what lands here.  A buffer of ``T k`` rows of 6,144
    bf16 values is 805 MB at the top rung, and a layer that moved every
    pick would hold three of them (the gathered rows, the products' output,
    that output back in token order) and the activations between; the
    whole layer here stays about ONE such buffer at every rung (820 MB at
    the top: a piece's rows, their output and its weighted float32 copy,
    and the float32 sum over the tokens)."""
    from tpuserve.models import transformer

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _k_exaone_share(num_layers=2)
    H, I, E = cfg.hidden_size, cfg.expert_intermediate_size, cfg.num_experts
    held, bf16 = cfg.moe_experts_held, jnp.bfloat16
    p = {"router": {"kernel": S((H, E), bf16)},
         "router_bias": {"bias": S((E,), jnp.float32)},
         "experts": {"gate_proj": {"kernel": S((held, H, I), bf16)},
                     "up_proj": {"kernel": S((held, H, I), bf16)},
                     "down_proj": {"kernel": S((held, I, H), bf16)}},
         "shared": {"gate_proj": {"kernel": S((H, I), bf16)},
                    "up_proj": {"kernel": S((H, I), bf16)},
                    "down_proj": {"kernel": S((I, H), bf16)}}}
    compiled = jax.jit(lambda x, p: transformer._moe_mlp(x, p, cfg)).lower(
        S((tokens, H), bf16), p).compile()
    text = compiled.as_text()
    assert text.count("_moe_grouped_matmul") >= 3
    assert " while(" in text            # the pieces: a trip count from data
    every_pick = tokens * cfg.num_experts_per_tok * H * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.25 * every_pick + (16 << 20), (temp, every_pick)


@pytest.mark.parametrize("program,tokens", [("decode_multi", 0),
                                            ("forward_ragged", 8192)])
def test_the_k_exaone_cell_fits_the_chip(program, tokens, one_chip,
                                         monkeypatch):
    """The cell's whole trunks at the published widths: 8 layers, 16 of
    128 experts, 19,200 vocabulary rows, a fused decode window of 64 rows
    and the top rung of the packed-prefill ladder, beside a pool of 3,072
    pages of 32 tokens (what 0.9 of the chip leaves after 11.96 GB of
    weights).  The chip's compiler refuses what does not fit 16 GB; 64
    query heads take a ragged block of 64 rows, as the engine finds."""
    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def place(tree):
        return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _k_exaone_share(num_layers=8)
    blk = ragged_block_for(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                           PAGE, 2, 2)
    assert blk == 64
    # and the accepted cells' shapes keep their 128 rows
    for hq, hkv, d in WIDTHS.values():
        assert ragged_block_for(hq, hkv, d, PAGE, 2, 2) == 128
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=MAX_NUM_SEQS, steps=8, tokens=tokens or blk,
        blk=blk, prompts=PREFILL_SEQS, block_size=PAGE, num_blocks=3072,
        max_blocks=MAX_PAGES, attn_impl="pallas")[program]
    mem = fn.lower(*args, **kwargs).compile().memory_analysis()
    weights = mem.argument_size_in_bytes - mem.alias_size_in_bytes
    assert 11.9e9 < weights < 12.1e9, weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.5e9


@pytest.mark.parametrize("kernel", ["decode", "flash", "ragged"])
def test_the_custom_call_carries_the_name_the_benchmark_matches(
        kernel, one_chip, monkeypatch):
    """A profiler trace prints a Pallas kernel as its HLO instruction, and
    ``benchmark/harness`` reduces the trace by that name: it is
    ``pallas_call(name=KERNEL_NAME)``, not whatever function happens to
    wrap the call."""
    import re

    from tpuserve.ops import (pallas_flash_attention, pallas_paged_attention,
                              pallas_ragged_attention)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    build, name, want = {
        "decode": (_decode, pallas_paged_attention.KERNEL_NAME,
                   "_paged_decode_attention"),
        "flash": (_flash, pallas_flash_attention.KERNEL_NAME,
                  "_flash_prefill_attention"),
        "ragged": (_ragged, pallas_ragged_attention.KERNEL_NAME,
                   "_ragged_paged_attention")}[kernel]
    assert name == want
    extra = (monkeypatch,) if kernel == "ragged" else ()
    fn, args = build(S, *WIDTHS["qwen3-0.6b"], False, *extra)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", text)



# ---- every device operation of a decode window names its model part ------

# operations that do work on the chip (a bitcast, a tuple, a parameter do
# none), with the asynchronous halves the compiler splits a copy or a
# slice into
DEVICE_WORK = {"fusion", "convolution", "custom-call", "copy", "copy-start",
               "copy-done", "slice", "slice-start", "slice-done",
               "dynamic-slice", "dynamic-update-slice", "sort", "gather",
               "scatter"}
# the ONLY operations that carry an op_name and no part of the table: what
# lax.scan itself emits around the window's body (its stacked outputs'
# buffers and the write of a step's row into them), and one index clamp
# of the expert layer's row gather that XLA names outside every path
NO_PART = {
    "jit(decode_multi)/decode/broadcast_in_dim",
    "jit(decode_multi)/decode/while/body/broadcast_in_dim",
    "jit(decode_multi)/decode/while/body/dynamic_update_slice",
    "gather",
}


def _scheduled(text):
    """The compiled module's instructions that run as operations of their
    own (those of fused computations and reducers left out), by
    computation, in schedule order: ``{computation: [(name, opcode,
    op_name, operand names)]}``."""
    import re
    comps, cur, name = {}, None, None
    for line in text.split("\n"):
        if cur is None:
            m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
            if m and not line.startswith(" "):
                name, cur = m.group(1), []
        elif line.startswith("}"):
            comps[name], cur = cur, None
        else:
            cur.append(line)
    inner = set()
    for lines in comps.values():
        for line in lines:
            inner.update(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line))
    inner -= {c for lines in comps.values() for line in lines
              for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", line)}
    out = {}
    for comp, lines in comps.items():
        if comp in inner:
            continue
        rows = []
        for line in lines:
            m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\((.*)$",
                         line)
            if not m:
                continue
            op_name = re.search(r'op_name="([^"]*)"', line)
            rows.append((m.group(1), m.group(2),
                         op_name.group(1) if op_name else "",
                         set(re.findall(r"%([\w.\-]+)",
                                        m.group(3).split("metadata=")[0]))))
        out[comp] = rows
    return out


def _two_layers(model: str):
    """Two layers of a family at its published widths."""
    import dataclasses

    from tpuserve.models.config import get_model_config
    if "+share" in model:
        # a dense layer, then an expert layer told its share (the loop
        # over pieces is a computation of its own inside the window's)
        return _k_exaone_share(num_layers=2)
    if "+last2" in model:
        # a linear-attention layer, then a full one (the last two of a
        # period)
        return _olmo_hybrid(num_layers=2, linear_layers=(True, False))
    return dataclasses.replace(get_model_config(model), num_layers=2)


# what the chip's compiler makes ITSELF inside a called function (a
# trunk's layer body under its own jax.jit, models/transformer.py) before
# it inlines the call, it names after the CALL: a phase and no part, where
# in a flat module it carries no name at all.  The benchmark's reader
# files such an operation under no part (trunk.unscoped_device_share), so
# they are counted here and held to the few there are: index arithmetic of
# an expert layer's row moves, (64, 1) int32 a piece
LAYER_CALL = re.compile(r"/jit\(_(prefill|chunk|decode|ragged|nocache)"
                        r"_layer\)$")


@pytest.mark.parametrize("model,kernels,by_call", [
    ("Qwen/Qwen3-0.6B", {"_paged_decode_attention": "attn.kernel"}, 0),
    ("JetBrains/Mellum2-12B-A2.5B-Instruct",
     {"_paged_decode_attention": "attn.kernel",
      "_moe_grouped_matmul": "moe.experts"}, 10),
    ("LGAI-EXAONE/K-EXAONE-236B-A23B+share",
     {"_paged_decode_attention": "attn.kernel",
      "_moe_grouped_matmul": "moe.experts"}, 0),
    ("allenai/Olmo-Hybrid-7B+last2",
     {"_paged_decode_attention": "attn.kernel",
      "_gdn_state_update": "ssm.scan", "_conv_tail_step": "ssm.conv"}, 0),
])
def test_every_operation_of_a_decode_window_names_its_part(
        model, kernels, by_call, one_chip, monkeypatch):
    """``decode_multi`` at published widths, two layers, 64 rows, compiled
    for the chip: whatever carries an ``op_name`` carries a part of the
    scope table (``tpuserve/ops/scopes.py``), the exceptions listed above
    by name, so that an unscoped operation cannot come back unseen.  What
    the compiler makes itself carries no ``op_name`` at all; the benchmark
    files it under the next operation of its program that names a PART
    (``benchmark/layer_metrics/_scope_trace.py``), and here that rule is
    held to the compiled text: such an operation is followed by one, and
    for the wait on a prefetched weight slice (``slice-done``, the one
    that costs time) the next operation with a part IS its consumer.

    Every layer's operations come through a ``jax.jit`` of their own
    (``jit(_decode_layer)`` in their paths): phase and part are found as
    before, the K/V row scatter included, which the compiler names without
    the call's prefix; ``by_call`` operations are named after the call
    alone (``LAYER_CALL``)."""
    from test_scopes import scope_of, trunk_programs
    from tpuserve.ops import scopes

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def place(tree):
        return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, kwargs = trunk_programs(
        _two_layers(model), S, place, rows=MAX_NUM_SEQS, steps=8,
        block_size=PAGE, num_blocks=NUM_BLOCKS, max_blocks=MAX_PAGES,
        attn_impl="pallas")["decode_multi"]
    comps = _scheduled(fn.lower(*args, **kwargs).compile().as_text())
    seen, unscoped, called, waits, ahead = set(), [], [], 0, 0
    for comp, rows in comps.items():
        scoped = [scope_of(op_name)[1] and scope_of(op_name)
                  for _, _, op_name, _ in rows]
        users = {}
        for i, (_, _, _, operands) in enumerate(rows):
            for operand in operands:
                users.setdefault(operand, []).append(i)

        def consumers(i, depth=0):
            found = set()
            for j in users.get(rows[i][0], ()):
                if j > i and scoped[j]:
                    found.add(scoped[j])
                elif j > i and depth < 6:
                    found |= consumers(j, depth + 1)
            return found

        for i, (name, opcode, op_name, _) in enumerate(rows):
            if opcode not in DEVICE_WORK:
                continue
            for kernel, part in kernels.items():
                if name.split(".")[0] == kernel:
                    assert scope_of(op_name) == (scopes.DECODE, part), op_name
                    seen.add(kernel)
            # (inside a pipelined loop the compiler names its waits after
            # the loop itself: the reader takes those for the compiler's)
            if op_name and not (op_name.endswith("/while")
                                and opcode != "while"):
                if LAYER_CALL.search(op_name):
                    called.append(name)
                elif not scoped[i] and op_name not in NO_PART:
                    unscoped.append((name, op_name))
                elif scoped[i]:
                    # no part without its phase: the reader divides
                    # decode/'s parts by decode/'s steps
                    assert scope_of(op_name)[0] == scopes.DECODE, op_name
                continue
            nxt = next((s for s in scoped[i + 1:] if s), None)
            if opcode == "slice-done" and not consumers(i):
                # a POOL the window carries that the compiler keeps in its
                # faster memory space from step to step, copied there in
                # slices at the end of the loop's body: its consumer is
                # the NEXT step (the carry), so the reader would file the
                # wait with whatever part follows it.  The convolution's
                # memory of a model with linear layers was one (9 MB a
                # layer, PERF.md §7 row 24) until its kernel declared the
                # pool in HBM (ops/pallas_conv_tail.py): none is left
                ahead += 1
            elif opcode == "slice-done":
                waits += 1
                assert nxt in consumers(i), (name, nxt, consumers(i))
            elif not comp.startswith("main"):
                # a loop body ends in scoped work; only the entry's own
                # first and last copies have nothing scoped behind them
                assert nxt or not consumers(i), name
    assert not unscoped, unscoped
    assert len(called) <= by_call, called
    assert seen == set(kernels)
    assert any("/jit(_decode_layer)/" in op_name for rows in comps.values()
               for _, _, op_name, _ in rows)
    assert waits >= 8       # the layers' weight matrices are prefetched
    assert ahead == 0, ahead


# ---- a layer under its own jax.jit is inlined into the program -----------

# the kernels of two layers of each family's decode window and packed
# prefill, by the name of their custom call (the counts the trunks held
# when every layer was written out in the loop): an expert layer runs
# three grouped products, under a share inside one loop over pieces
LAYER_KERNELS = {
    "Qwen/Qwen3-0.6B": ({"_paged_decode_attention": 2},
                        {"_ragged_paged_attention": 2, "_paged_kv_write": 2}),
    "mistralai/Mistral-7B-Instruct-v0.1": (
        {"_paged_decode_attention": 2},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2}),
    "tiiuae/Falcon-H1-34B-Instruct": (
        {"_paged_decode_attention": 2, "_ssm_state_update": 2,
         "_conv_tail_step": 2},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2}),
    "JetBrains/Mellum2-12B-A2.5B-Instruct": (
        {"_paged_decode_attention": 2, "_moe_grouped_matmul": 6},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2,
         "_moe_grouped_matmul": 6}),
    "LGAI-EXAONE/K-EXAONE-236B-A23B+share": (
        {"_paged_decode_attention": 2, "_moe_grouped_matmul": 3},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2,
         "_moe_grouped_matmul": 3}),
    "allenai/Olmo-Hybrid-7B+last2": (
        {"_paged_decode_attention": 1, "_gdn_state_update": 1,
         "_conv_tail_step": 1},
        {"_ragged_paged_attention": 1, "_paged_kv_write": 1}),
}


@pytest.mark.parametrize("program", ["decode_multi", "forward_ragged"])
@pytest.mark.parametrize("model", sorted(LAYER_KERNELS))
def test_a_layer_under_its_own_jit_is_inlined_into_the_program(
        model, program, one_chip, monkeypatch):
    """The module a trunk lowers to CALLS one private function a kind of
    layer (``jax.jit`` inside a trace); the chip's compiler inlines every
    call before it optimises, so the compiled program holds no call to a
    layer function and the kernels it held when the layers were written
    out in the trunk's loop."""
    import collections

    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def place(tree):
        return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _two_layers(model)
    blk = ragged_block_for(cfg.cache_q_heads, cfg.cache_kv_heads,
                           cfg.head_dim, PAGE, 2, 2)
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=MAX_NUM_SEQS, steps=8, tokens=2048, blk=blk,
        prompts=PREFILL_SEQS, block_size=PAGE, num_blocks=NUM_BLOCKS,
        max_blocks=MAX_PAGES, attn_impl="pallas")[program]
    lowered = fn.lower(*args, **kwargs)
    layer = "_decode_layer" if program == "decode_multi" else "_ragged_layer"
    assert len(re.findall(rf"call @{layer}(_\d+)?\(", lowered.as_text())) == 2
    text = lowered.compile().as_text()
    assert not re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? call\(", text,
                          re.M)
    kernels = collections.Counter(
        re.sub(r"\.\d+$", "", name) for name in re.findall(
            r"%([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call", text))
    assert kernels == LAYER_KERNELS[model][program == "forward_ragged"]
