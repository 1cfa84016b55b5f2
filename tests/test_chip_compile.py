"""The Pallas kernels compile for the chip — asked of the chip's own
compiler, with no chip attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
a slice that is not tile-aligned, or more scoped VMEM than a kernel may
use.  Here each kernel of the serving path is AOT-compiled for a described
TPU v5e at the widths of the models the engine serves (Qwen3-0.6B, the
flagship; Llama-3.1-8B, the registered model that needs tp) and at the
sizes the engine dispatches, with ``interpret=False``.  Nothing runs: a
compile that passes says nothing about results or times.

The AOT compiles are six files by what they compile (this one: the
attention kernels and the page writer; ``_experts``, ``_recurrent``,
``_cells``, ``_programs``, ``_latent``), on as many xdist workers: ``tests/chip_v5e.py``
holds what they share.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_v5e import (CHUNK, MAX_NUM_SEQS, MAX_PAGES, MIN_BUCKET, MIXED_BUDGET,
                      NUM_BLOCKS, PAGE, PREFILL_SEQS, WIDTHS, shapes_on)
from chip_v5e import (  # noqa: F401  (fixtures, found by name)
    _no_persistent_cache, one_chip, topo)


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
    S, _ = shapes_on(one_chip)
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

    S, _ = shapes_on(one_chip)
    assert packed_prefill_bucket(rows, 128) == rows
    fn, args = _ragged(S, *WIDTHS[width], False, monkeypatch, T=rows,
                       B=PREFILL_SEQS, decode_rows=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [128, 2048, 8192])
@pytest.mark.parametrize("kv_heads", [8, 4, 32])
def test_the_page_writer_compiles_for_v5e(kv_heads, rows, one_chip):
    """``_paged_kv_write`` at the configurations' KV widths (8, 4 and 32
    heads of 128), from one ragged block to the packed ladder's top and at
    the chunk size: compiled, named, and in place: both caches' bytes are
    aliased from input to output and the program holds no other buffer of
    their size (the benchmark's caches fill the chip: a copy cannot
    exist), and the stream's rows reach the kernel without a relayout."""
    from tpuserve.ops.pallas_kv_write import KERNEL_NAME, paged_kv_write

    S, _ = shapes_on(one_chip)
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


@pytest.mark.parametrize("kernel", ["decode", "flash", "ragged"])
def test_the_custom_call_carries_the_name_the_benchmark_matches(
        kernel, one_chip, monkeypatch):
    """A profiler trace prints a Pallas kernel as its HLO instruction, and
    ``benchmark/harness`` reduces the trace by that name: it is
    ``pallas_call(name=KERNEL_NAME)``, not whatever function happens to
    wrap the call."""
    from tpuserve.ops import (pallas_flash_attention, pallas_paged_attention,
                              pallas_ragged_attention)

    S, _ = shapes_on(one_chip)
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
