"""Pallas kernel correctness vs the pure-JAX reference (interpret mode on CPU
— the fake-backend strategy of SURVEY.md §4)."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.ops import attention as ref_ops
from tpuserve.ops.pallas_flash_attention import flash_prefill_attention
from tpuserve.ops.pallas_paged_attention import paged_decode_attention


@pytest.mark.parametrize("B,T,Hq,Hkv,D,blk", [
    (2, 64, 4, 2, 16, 32),
    (1, 128, 8, 8, 64, 128),
    (2, 48, 4, 4, 32, 32),     # T not a multiple of the block
])
def test_flash_prefill_matches_reference(B, T, Hq, Hkv, D, blk):
    rng = np.random.default_rng(B * T)
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    lens = jnp.asarray(rng.integers(1, T + 1, (B,)), jnp.int32)
    ref = ref_ops.prefill_attention(q, k, v, lens, D ** -0.5)
    out = flash_prefill_attention(q, k, v, lens, D ** -0.5, blk_q=blk, blk_k=blk,
                                  interpret=True)
    for b in range(B):
        L = int(lens[b])
        np.testing.assert_allclose(np.asarray(out[b, :L]), np.asarray(ref[b, :L]),
                                   atol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,nb,mp", [
    (2, 4, 2, 16, 4, 16, 4),
    (3, 8, 8, 64, 16, 32, 8),
    (1, 16, 2, 128, 32, 64, 4),
    # the benchmark cells' head shapes at the server's page size: 2, 4, 8
    # and 5 query heads a KV head against a (page x Hkv, D) slab
    (3, 16, 8, 128, 32, 64, 20),
    (3, 32, 8, 128, 32, 64, 20),
    (3, 32, 4, 128, 32, 64, 20),
    (3, 20, 4, 128, 32, 64, 20),
])
def test_paged_decode_matches_reference(B, Hq, Hkv, D, page, nb, mp):
    rng = np.random.default_rng(B + Hq)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(nb)[:B * mp].reshape(B, mp), jnp.int32)
    sl = jnp.asarray(rng.integers(1, page * mp + 1, (B,)), jnp.int32)
    ref = ref_ops.paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5)
    out = paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("pages_per_group", [1, 2, 3])
def test_paged_decode_multi_group(pages_per_group):
    """Force the multi-group online-softmax path (num_groups > 1) with a
    ragged tail: the default pages_per_group covers small shapes in one
    group, so the cross-group accumulation needs explicit coverage."""
    B, Hq, Hkv, D, page, nb, mp = 2, 4, 2, 32, 4, 32, 8
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(nb)[:B * mp].reshape(B, mp), jnp.int32)
    sl = jnp.asarray([page * mp, page * mp - 3], jnp.int32)  # full + ragged
    ref = ref_ops.paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5)
    out = paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5, interpret=True,
                                 pages_per_group=pages_per_group)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("B,seqs_pp", [(5, 2), (11, 8), (4, 4)])
def test_paged_decode_multi_seq_programs(B, seqs_pp):
    """Multi-sequence grid programs (cross-sequence DMA pipeline): batch not
    divisible by seqs_per_program exercises the zero-length padding path,
    and mixed lengths exercise per-sequence group counts within a program."""
    Hq, Hkv, D, page, nb, mp = 4, 2, 32, 4, 64, 8
    rng = np.random.default_rng(B * 13 + seqs_pp)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    sl = np.asarray(rng.integers(1, page * mp + 1, (B,)), np.int32)
    sl[0] = 1                       # single-token and full-length extremes
    sl[-1] = page * mp
    sl = jnp.asarray(sl)
    ref = ref_ops.paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5)
    out = paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5, interpret=True,
                                 pages_per_group=2, seqs_per_program=seqs_pp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# (query heads, KV heads) of the four benchmark cells
CELL_HEADS = [(16, 8), (32, 8), (32, 4), (20, 4)]


@pytest.mark.parametrize("Hq,Hkv", CELL_HEADS)
@pytest.mark.parametrize("case", ["edges", "window", "int8", "odd_batch",
                                  "stale_nan"])
def test_paged_decode_at_the_cells_head_shapes(case, Hq, Hkv):
    """The slab body at the cells' shapes (page 32, head size 128, the
    default 512-token chunk, tiles of 2,048 key columns): lengths at every edge
    of a page, a tile and a chunk; the 1,024 window with contexts on both
    sides of it; an int8 cache; a batch that is not a whole number of
    programs; and NaN in every cache slot no sequence attends, which must
    not reach the output."""
    from tpuserve.ops.attention import pad_scale_lanes, quantize_kv
    D, page = 128, 32
    lens = {"window": [500, 1024, 1025, 1500, 2100],
            "stale_nan": [1, 31, 513, 1025, 1500]}.get(
                case, [1, 31, 128, 129, 512, 513, 700])
    window = 1024 if case in ("window", "stale_nan") else None
    B, mp = len(lens), -(-max(lens) // page) + 2
    nb = B * mp
    rng = np.random.default_rng(Hq * Hkv + len(case))
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = rng.standard_normal((nb, page, Hkv, D)).astype(np.float32)
    vc = rng.standard_normal((nb, page, Hkv, D)).astype(np.float32)
    bt = rng.permutation(nb).reshape(B, mp).astype(np.int32)
    sl = jnp.asarray(lens, jnp.int32)
    kw, kernel_kw = {}, {}
    if case == "int8":
        kq, ks = quantize_kv(jnp.asarray(kc))
        vq, vs = quantize_kv(jnp.asarray(vc))
        kw = dict(k_scale=pad_scale_lanes(ks), v_scale=pad_scale_lanes(vs))
        kc, vc = kq, vq
    if case == "odd_batch":
        kernel_kw = dict(seqs_per_program=4)        # 7 rows: 4 + 3 + pad
    ref = ref_ops.paged_decode_attention(
        q, jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(bt), sl, D ** -0.5,
        sliding_window=window, **kw)
    if case == "stale_nan":
        attended = np.zeros((nb, page), bool)
        for b, n in enumerate(lens):
            for pos in range(max(n - window, 0), n):
                attended[bt[b, pos // page], pos % page] = True
        kc = np.where(attended[:, :, None, None], kc, np.nan)
        vc = np.where(attended[:, :, None, None], vc, np.nan)
    out = paged_decode_attention(
        q, jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(bt), sl, D ** -0.5,
        interpret=True, sliding_window=window, **kw, **kernel_kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("Hq", [16, 128])
@pytest.mark.parametrize("case", ["edges", "stale_nan", "window",
                                  "chunk_of_32"])
def test_the_latent_decode_entry_matches_reference(case, Hq, monkeypatch):
    """The latent entry's own kernel (one KV head, V the first ``v_lanes``
    lanes of the K page; every chunk starts all its pages and is waited
    for once, two chunks ahead over three slots) at DeepSeek-V2-Lite's 16
    heads and openPangu's 128, two programs a batch, chunks of 16 tokens:
    rows of length 0 (a padded row: zeros), 1, exactly a chunk, exactly
    two, two and one more, and 49, whose last chunk holds ONE attended row
    beside slots the cache never wrote and pages that are not the
    sequence's (``stale_nan``: NaN wherever no row attends, which must not
    reach the output; the table's entries past a sequence's end name pages
    of NaN).  ``window``: the last 20 positions, so whole chunks before
    the window are never started.  ``chunk_of_32``: the chunk as asked for,
    not cut to the tile."""
    from tpuserve.ops import pallas_paged_attention as ppa
    D, v_lanes, page, mp = 256, 128, 8, 8
    if case != "chunk_of_32":
        monkeypatch.setattr(ppa, "DECODE_TILE_COLUMNS", 16)
    window = 20 if case == "window" else None
    lens = [0, 1, 16, 32, 33, 49]
    B, nb = len(lens), len(lens) * mp
    rng = np.random.default_rng(Hq + len(case))
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = rng.standard_normal((nb, page, 1, D)).astype(np.float32)
    kc[..., 200:] = 0.0             # a page is whole lane tiles: zeros past
    bt = rng.permutation(nb).reshape(B, mp).astype(np.int32)
    sl = jnp.asarray(lens, jnp.int32)
    ref = ref_ops.paged_decode_attention(
        q, jnp.asarray(kc), jnp.asarray(kc), jnp.asarray(bt), sl,
        D ** -0.5, sliding_window=window)[..., :v_lanes]
    if case == "stale_nan":
        attended = np.zeros((nb, page), bool)
        for b, n in enumerate(lens):
            for pos in range(n):
                attended[bt[b, pos // page], pos % page] = True
        kc = np.where(attended[:, :, None, None], kc, np.nan)
    out = np.asarray(paged_decode_attention(
        q, jnp.asarray(kc), None, jnp.asarray(bt), sl, D ** -0.5,
        interpret=True, v_lanes=v_lanes, pages_per_group=4,
        seqs_per_program=3, sliding_window=window))
    assert out.shape == (B, Hq, v_lanes)
    assert not out[0].any()
    np.testing.assert_allclose(out[1:], np.asarray(ref)[1:], atol=2e-5)


def test_paged_decode_int8_matches_reference():
    """int8 cache path: the Pallas kernel DMAs int8 pages + scale blocks
    and dequantizes in VMEM; must match the reference impl fed the same
    quantized cache bit-for-bit (both dequantize identically)."""
    from tpuserve.ops.attention import pad_scale_lanes, quantize_kv
    B, Hq, Hkv, D, page, nb, mp = 5, 4, 2, 128, 8, 64, 8
    rng = np.random.default_rng(23)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    ks, vs = pad_scale_lanes(ks), pad_scale_lanes(vs)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    sl = jnp.asarray(rng.integers(1, page * mp + 1, (B,)), jnp.int32)
    ref = ref_ops.paged_decode_attention(q, kq, vq, bt, sl, D ** -0.5,
                                         k_scale=ks, v_scale=vs)
    out = paged_decode_attention(q, kq, vq, bt, sl, D ** -0.5,
                                 interpret=True, pages_per_group=2,
                                 seqs_per_program=2, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # and the quantization error itself is small relative to fp attention
    fp = ref_ops.paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5)
    err = np.abs(np.asarray(out) - np.asarray(fp)).max()
    assert err < 0.05, f"int8 KV error {err} too large"


def test_paged_window_int8_matches_reference():
    """int8 cache in the chunked-prefill/verify window kernel."""
    from tpuserve.ops.attention import pad_scale_lanes, quantize_kv
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    B, C, Hq, Hkv, D, page, nb, mp = 2, 16, 4, 2, 128, 8, 64, 8
    rng = np.random.default_rng(29)
    q = jnp.asarray(rng.standard_normal((B, C, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    ks, vs = pad_scale_lanes(ks), pad_scale_lanes(vs)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    ctx = jnp.asarray([9, 0], jnp.int32)
    chunk = jnp.asarray([C, C - 3], jnp.int32)
    ref = ref_ops.chunked_prefill_attention(q, kq, vq, bt, ctx, chunk,
                                            D ** -0.5, k_scale=ks,
                                            v_scale=vs)
    out = paged_window_attention(q, kq, vq, bt, ctx, chunk, D ** -0.5,
                                 interpret=True, blk_q=8, pages_per_group=2,
                                 k_scale=ks, v_scale=vs)
    o, r = np.asarray(out), np.asarray(ref)
    for b_i in range(B):
        n = int(chunk[b_i])
        np.testing.assert_allclose(o[b_i, :n], r[b_i, :n], atol=2e-5)


def test_paged_decode_vmem_clamp():
    """Knob combinations whose footprint would blow the VMEM limit clamp
    (with a warning) instead of reaching the compiler; the clamped sizes
    fit the same model the compiler's limit is set from."""
    from tpuserve.ops.pallas_paged_attention import (
        VMEM_LIMIT_BYTES, _clamp_to_vmem_budget, vmem_footprint)
    # fp32 KV, page 32, 8 kv heads, D 128: one (K+V, double-buffered) page
    # group of 64 pages is 2*2*64*32*8*128*4 = 64 MiB >> any budget
    shape = dict(page_size=32, num_kv_heads=8, head_dim=128, kv_itemsize=4,
                 num_q_heads=16, q_itemsize=4)
    pg, sp = _clamp_to_vmem_budget(64, 8, **shape)
    assert pg < 64
    assert vmem_footprint(pg, sp, 1, decode=True, **shape) <= VMEM_LIMIT_BYTES
    # in-budget knobs pass through untouched
    assert _clamp_to_vmem_budget(4, 8, 32, 8, 128, 2, 16, 2) == (4, 8)


@pytest.mark.parametrize("hq,expect", [
    (16, (16, 128)),     # Qwen3-0.6B: the default group and block fit
    (32, (4, 128)),      # Llama-8B: twice the score rows, a shorter group
    (64, (1, 64)),       # 70B-class: shrinks by the same rule, no constant
])
def test_window_clamp_shrinks_with_q_heads(hq, expect):
    """The window/ragged clamp counts the f32 score tiles, so wider-q
    models get a shorter page group from the shapes alone."""
    from tpuserve.ops.pallas_paged_attention import _clamp_to_vmem_budget
    assert _clamp_to_vmem_budget(16, 128, 32, 8, 128, 2, hq, 2,
                                 rows_per_dot=True) == expect


def test_paged_decode_vmem_clamp_end_to_end(caplog):
    """The clamp engages inside paged_decode_attention (oversized
    pages_per_group arg), warns, and the clamped kernel still matches the
    reference."""
    import logging
    B, Hq, Hkv, D, page, nb, mp = 3, 4, 2, 128, 16, 512, 1024
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    sl = jnp.asarray(rng.integers(1, page * mp + 1, (B,)), jnp.int32)
    ref = ref_ops.paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5)
    with caplog.at_level(logging.WARNING, "tpuserve.ops.paged_attention"):
        # 1,024-page groups of fp32 slabs = 64 MiB of double-buffered
        # scratch (a page lands unpadded: 256 would fit)
        out = paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5,
                                     interpret=True, pages_per_group=1024)
    assert any("clamped" in r.message for r in caplog.records)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_window_vmem_clamp(caplog):
    """The window kernel clamps oversized knob/shape combinations against
    the same VMEM budget as the decode kernel (wide-Hkv models blow the
    default group size), and the clamped kernel stays correct."""
    import logging

    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    B, C, Hq, Hkv, D, page, nb, mp = 1, 8, 4, 2, 128, 16, 256, 256
    rng = np.random.default_rng(31)
    q = jnp.asarray(rng.standard_normal((B, C, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    ctx = jnp.asarray([40], jnp.int32)
    chunk = jnp.asarray([C], jnp.int32)
    ref = ref_ops.chunked_prefill_attention(q, kc, vc, bt, ctx, chunk,
                                            D ** -0.5)
    # 256-page groups of fp32 KV = 64 MiB of double-buffered scratch:
    # over the VMEM limit, must clamp
    with caplog.at_level(logging.WARNING, "tpuserve.ops.paged_attention"):
        out = paged_window_attention(q, kc, vc, bt, ctx, chunk, D ** -0.5,
                                     interpret=True, pages_per_group=256)
    assert any("clamped" in r.message for r in caplog.records)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_decode_single_token_sequence():
    # seq_len == 1: only the freshly written token is attended to.
    D = 16
    q = jnp.ones((1, 2, D), jnp.float32)
    kc = jnp.zeros((4, 4, 2, D), jnp.float32).at[2, 0].set(1.0)
    vc = jnp.zeros((4, 4, 2, D), jnp.float32).at[2, 0].set(7.0)
    bt = jnp.asarray([[2, 0]], jnp.int32)
    sl = jnp.asarray([1], jnp.int32)
    out = paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 7.0, atol=1e-5)


# ---------------------------------------------------------------------------
# Paged window attention (chunked prefill / spec verify)
# ---------------------------------------------------------------------------

def _window_setup(rng, B, C, Hq, Hkv, D, page, nb, mp, max_ctx):
    """Random cache + a written window at ctx_lens..ctx_lens+chunk_lens."""
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    q = jnp.asarray(rng.standard_normal((B, C, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    # disjoint block tables per sequence
    bt = np.zeros((B, mp), np.int32)
    for b in range(B):
        bt[b] = np.arange(b * mp, (b + 1) * mp) % nb
    ctx = rng.integers(0, max_ctx + 1, (B,)).astype(np.int32)
    chunk = rng.integers(1, C + 1, (B,)).astype(np.int32)
    # keep every window inside the block table
    cap = mp * page
    for b in range(B):
        ctx[b] = min(ctx[b], cap - int(chunk[b]))
    return (paged_window_attention, q, kc, vc, jnp.asarray(bt),
            jnp.asarray(ctx), jnp.asarray(chunk))


@pytest.mark.parametrize("B,C,Hq,Hkv,D,page,nb,mp,max_ctx,blk_q", [
    (2, 16, 4, 2, 16, 4, 24, 8, 12, 8),    # GQA, chunk crosses q blocks
    (1, 32, 8, 8, 64, 16, 16, 8, 90, 16),  # MHA, long context
    (3, 8, 16, 2, 128, 32, 16, 4, 50, 8),  # deep GQA group, one q block
])
def test_paged_window_matches_reference(B, C, Hq, Hkv, D, page, nb, mp,
                                        max_ctx, blk_q):
    rng = np.random.default_rng(B * C + Hq)
    fn, q, kc, vc, bt, ctx, chunk = _window_setup(
        rng, B, C, Hq, Hkv, D, page, nb, mp, max_ctx)
    ref = ref_ops.chunked_prefill_attention(q, kc, vc, bt, ctx, chunk,
                                            D ** -0.5)
    out = fn(q, kc, vc, bt, ctx, chunk, D ** -0.5, interpret=True,
             blk_q=blk_q)
    for b in range(B):
        n = int(chunk[b])           # rows past chunk_lens are never read
        np.testing.assert_allclose(np.asarray(out[b, :n]),
                                   np.asarray(ref[b, :n]), atol=2e-5)


def test_paged_window_zero_context():
    # first chunk of a prompt: pure causal within the window
    rng = np.random.default_rng(7)
    fn, q, kc, vc, bt, _, chunk = _window_setup(
        rng, 2, 16, 4, 2, 32, 4, 16, 8, 0)
    ctx = jnp.zeros((2,), jnp.int32)
    ref = ref_ops.chunked_prefill_attention(q, kc, vc, bt, ctx, chunk,
                                            32 ** -0.5)
    out = fn(q, kc, vc, bt, ctx, chunk, 32 ** -0.5, interpret=True, blk_q=8)
    for b in range(2):
        n = int(chunk[b])
        np.testing.assert_allclose(np.asarray(out[b, :n]),
                                   np.asarray(ref[b, :n]), atol=2e-5)


def test_paged_window_multi_group():
    # context long enough to span several DMA page groups
    rng = np.random.default_rng(11)
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    B, C, Hq, Hkv, D, page, nb, mp = 1, 8, 4, 2, 32, 4, 64, 32
    fn, q, kc, vc, bt, ctx, chunk = _window_setup(
        rng, B, C, Hq, Hkv, D, page, nb, mp, 100)
    ctx = jnp.asarray([100], jnp.int32)
    chunk = jnp.asarray([8], jnp.int32)
    ref = ref_ops.chunked_prefill_attention(q, kc, vc, bt, ctx, chunk,
                                            D ** -0.5)
    out = paged_window_attention(q, kc, vc, bt, ctx, chunk, D ** -0.5,
                                 interpret=True, blk_q=8, pages_per_group=3)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# Sliding-window attention (Mistral): every kernel must match the windowed
# reference, including the page-skip paths that never DMA out-of-window KV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [4, 16, 40])
def test_flash_prefill_sliding_window(W):
    B, T, Hq, Hkv, D = 2, 48, 4, 2, 128
    rng = np.random.default_rng(41 + W)
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    lens = jnp.asarray([T, T - 5], jnp.int32)
    ref = ref_ops.prefill_attention(q, k, v, lens, D ** -0.5,
                                    sliding_window=W)
    out = flash_prefill_attention(q, k, v, lens, D ** -0.5, blk_q=16,
                                  blk_k=16, interpret=True,
                                  sliding_window=W)
    for b in range(B):
        n = int(lens[b])
        np.testing.assert_allclose(np.asarray(out)[b, :n],
                                   np.asarray(ref)[b, :n], atol=2e-5)


@pytest.mark.parametrize("W,spp", [(8, 1), (24, 2), (100, 2)])
def test_paged_decode_sliding_window(W, spp):
    """Windowed decode: out-of-window pages are skipped entirely (the
    perf point) and results still match the windowed reference across
    mixed lengths, incl. sequences shorter than the window."""
    B, Hq, Hkv, D, page, nb, mp = 5, 4, 2, 128, 4, 128, 24
    rng = np.random.default_rng(W + spp)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    sl = np.asarray(rng.integers(1, page * mp + 1, (B,)), np.int32)
    sl[0] = 3                          # shorter than any window
    sl[-1] = page * mp                 # full context, deep page skip
    sl = jnp.asarray(sl)
    ref = ref_ops.paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5,
                                         sliding_window=W)
    out = paged_decode_attention(q, kc, vc, bt, sl, D ** -0.5,
                                 interpret=True, pages_per_group=2,
                                 seqs_per_program=spp, sliding_window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_decode_sliding_window_int8():
    """Window + int8 cache compose (both alter the DMA schedule)."""
    from tpuserve.ops.attention import pad_scale_lanes, quantize_kv
    B, Hq, Hkv, D, page, nb, mp = 3, 4, 2, 128, 4, 64, 16
    rng = np.random.default_rng(53)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    ks, vs = pad_scale_lanes(ks), pad_scale_lanes(vs)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    sl = jnp.asarray([3, 30, page * mp], jnp.int32)
    ref = ref_ops.paged_decode_attention(q, kq, vq, bt, sl, D ** -0.5,
                                         k_scale=ks, v_scale=vs,
                                         sliding_window=12)
    out = paged_decode_attention(q, kq, vq, bt, sl, D ** -0.5,
                                 interpret=True, pages_per_group=2,
                                 seqs_per_program=2, k_scale=ks, v_scale=vs,
                                 sliding_window=12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("W", [6, 20])
def test_paged_window_sliding_window(W):
    """Chunked-prefill window kernel under a sliding window: deep context
    beyond the window exercises the group-skip start."""
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    B, C, Hq, Hkv, D, page, nb, mp = 2, 8, 4, 2, 128, 4, 128, 24
    rng = np.random.default_rng(W)
    q = jnp.asarray(rng.standard_normal((B, C, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, (B, mp)), jnp.int32)
    ctx = jnp.asarray([60, 0], jnp.int32)   # deep context + fresh prompt
    chunk = jnp.asarray([C, C - 3], jnp.int32)
    ref = ref_ops.chunked_prefill_attention(q, kc, vc, bt, ctx, chunk,
                                            D ** -0.5, sliding_window=W)
    out = paged_window_attention(q, kc, vc, bt, ctx, chunk, D ** -0.5,
                                 interpret=True, blk_q=4, pages_per_group=2,
                                 sliding_window=W)
    o, r = np.asarray(out), np.asarray(ref)
    for b in range(B):
        n = int(chunk[b])
        np.testing.assert_allclose(o[b, :n], r[b, :n], atol=2e-5)


# --------------------------------------------------------------------------
# Ragged mixed prefill+decode kernel (ops/pallas_ragged_attention.py):
# tier-1 interpret-mode parity so the mixed path gates without a chip.
# --------------------------------------------------------------------------

def _ragged_case(rng, n_dec, chunk_shapes, blk, Hq=4, Hkv=2, D=16, page=4,
                 nb=64, mp=8, int8=False, max_kv=None):
    """Build a mixed flat layout (decode rows first, blk-aligned prefill
    chunks) + descriptors, the way engine._run_mixed packs them.  Returns
    everything both the kernel and the reference need, plus the valid-row
    mask (padding rows are unspecified by contract)."""
    from tpuserve.ops.attention import pad_scale_lanes, quantize_kv
    max_kv = max_kv or page * mp
    kc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, page, Hkv, D)), jnp.float32)
    scales = {}
    if int8:
        kc, ks = quantize_kv(kc)
        vc, vs = quantize_kv(vc)
        scales = dict(k_scale=pad_scale_lanes(ks),
                      v_scale=pad_scale_lanes(vs))
    kv_dec = rng.integers(1, max_kv + 1, size=n_dec)
    B = n_dec + len(chunk_shapes)
    starts, cursor = [], -(-n_dec // blk) * blk if n_dec else 0
    for ql, _ in chunk_shapes:
        starts.append(cursor)
        cursor += -(-ql // blk) * blk
    T = max(-(-max(cursor, 1) // blk) * blk, blk)
    bt = jnp.asarray(rng.integers(0, nb, (max(B, 1), mp)), jnp.int32)
    kv_lens = np.zeros((max(B, 1),), np.int32)
    q_starts = np.full((max(B, 1),), T, np.int32)
    q_lens = np.zeros((max(B, 1),), np.int32)
    row_seq = np.zeros((T,), np.int32)
    row_pos = np.zeros((T,), np.int32)
    valid = np.zeros((T,), bool)
    for i in range(n_dec):
        kv_lens[i] = kv_dec[i]
        q_starts[i] = i
        q_lens[i] = 1
        row_seq[i] = i
        row_pos[i] = kv_dec[i] - 1
        valid[i] = True
    blk_seq = np.full((T // blk,), -1, np.int32)
    for si, ((ql, kl), st) in enumerate(zip(chunk_shapes, starts),
                                        start=n_dec):
        kv_lens[si] = kl
        q_starts[si] = st
        q_lens[si] = ql
        row_seq[st:st + ql] = si
        row_pos[st:st + ql] = kl - ql + np.arange(ql)
        valid[st:st + ql] = True
        blk_seq[st // blk:(st + -(-ql // blk) * blk) // blk] = si
    q = jnp.asarray(rng.standard_normal((T, Hq, D)), jnp.float32)
    meta = jnp.asarray([n_dec, -(-n_dec // blk) if n_dec else 0], jnp.int32)
    return dict(q=q, kc=kc, vc=vc, bt=bt, kv_lens=jnp.asarray(kv_lens),
                q_starts=jnp.asarray(q_starts), q_lens=jnp.asarray(q_lens),
                meta=meta, blk_seq=jnp.asarray(blk_seq),
                row_seq=row_seq, row_pos=row_pos, valid=valid,
                scale=D ** -0.5, scales=scales)


def _ragged_ref(c, sliding_window=None):
    kw = dict(c["scales"])
    if sliding_window is not None:
        kw["sliding_window"] = sliding_window
    return ref_ops.ragged_attention(
        c["q"], c["kc"], c["vc"],
        c["bt"][np.clip(c["row_seq"], 0, c["bt"].shape[0] - 1)],
        jnp.asarray(c["row_pos"] + 1), c["scale"], seg_size=8, **kw)


def _ragged_out(c, blk, ppg=2, sliding_window=None, **kw):
    from tpuserve.ops.pallas_ragged_attention import ragged_paged_attention
    kw.update(c["scales"])
    if sliding_window is not None:
        kw["sliding_window"] = sliding_window
    return ragged_paged_attention(
        c["q"], c["kc"], c["vc"], c["bt"], c["kv_lens"], c["q_starts"],
        c["q_lens"], c["meta"], c["blk_seq"], c["scale"], interpret=True,
        blk_q=blk, pages_per_group=ppg, **kw)


@pytest.mark.parametrize("n_dec,chunks,blk", [
    (3, [(5, 9), (12, 12)], 8),      # mixed: decode rows + two chunks
    (8, [], 4),                      # pure decode, exact block multiple
    (0, [(13, 20)], 8),              # pure prefill, deep cached context
    (5, [(7, 7)], 4),                # fresh prompt chunk (ctx 0)
])
def test_ragged_kernel_matches_reference(n_dec, chunks, blk):
    rng = np.random.default_rng(n_dec * 31 + len(chunks))
    c = _ragged_case(rng, n_dec, chunks, blk)
    ref = _ragged_ref(c)
    out = _ragged_out(c, blk)
    np.testing.assert_allclose(np.asarray(out)[c["valid"]],
                               np.asarray(ref)[c["valid"]], atol=2e-5)


@pytest.mark.parametrize("chunks,blk,W", [
    ([(13, 20)], 8, None),               # one prompt on a cached prefix
    ([(21, 21), (3, 3), (9, 17)], 8, None),   # a packed batch of three
    ([(7, 25), (12, 12)], 4, 5),         # under a sliding window
])
def test_ragged_kernel_without_its_decode_part(chunks, blk, W):
    """A packed batched prefill dispatches no decode rows and builds the
    kernel without the decode part (``decode_rows=False``): the prefill
    part alone gives what the whole kernel gives."""
    rng = np.random.default_rng(len(chunks) * 13 + blk)
    c = _ragged_case(rng, 0, chunks, blk, page=4, mp=12, max_kv=40)
    ref = _ragged_ref(c, sliding_window=W)
    out = _ragged_out(c, blk, sliding_window=W, decode_rows=False)
    whole = _ragged_out(c, blk, sliding_window=W)
    np.testing.assert_allclose(np.asarray(out)[c["valid"]],
                               np.asarray(ref)[c["valid"]], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[c["valid"]],
                                  np.asarray(whole)[c["valid"]])


def test_ragged_kernel_matches_phase_split_kernels():
    """The fused kernel must agree with the two kernels it replaces,
    composed: paged decode over the decode rows, the chunked-prefill
    window kernel over each chunk."""
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    rng = np.random.default_rng(77)
    n_dec, chunks, blk = 3, [(6, 14), (9, 9)], 8
    c = _ragged_case(rng, n_dec, chunks, blk)
    out = np.asarray(_ragged_out(c, blk))
    dec = paged_decode_attention(c["q"][:n_dec], c["kc"], c["vc"],
                                 c["bt"][:n_dec], c["kv_lens"][:n_dec],
                                 c["scale"], interpret=True)
    np.testing.assert_allclose(out[:n_dec], np.asarray(dec), atol=2e-5)
    si = n_dec
    for ql, kl in chunks:
        st = int(c["q_starts"][si])
        win = paged_window_attention(
            c["q"][None, st:st + ql], c["kc"], c["vc"], c["bt"][si:si + 1],
            jnp.asarray([kl - ql], jnp.int32), jnp.asarray([ql], jnp.int32),
            c["scale"], interpret=True, blk_q=blk)
        np.testing.assert_allclose(out[st:st + ql], np.asarray(win[0]),
                                   atol=2e-5)
        si += 1


def test_ragged_kernel_multi_group():
    """Page-group online-softmax accumulation in both kernel parts
    (pages_per_group=1 forces many groups per sequence)."""
    rng = np.random.default_rng(91)
    c = _ragged_case(rng, 4, [(10, 26)], 8, page=4, mp=8)
    ref = _ragged_ref(c)
    out = _ragged_out(c, 8, ppg=1)
    np.testing.assert_allclose(np.asarray(out)[c["valid"]],
                               np.asarray(ref)[c["valid"]], atol=2e-5)


def test_ragged_kernel_int8():
    """int8 KV: pages DMA as int8 with per-page scale blocks, dequantized
    in VMEM — both the decode and prefill parts."""
    rng = np.random.default_rng(101)
    c = _ragged_case(rng, 3, [(6, 11)], 8, D=128, page=8, int8=True)
    ref = _ragged_ref(c)
    out = _ragged_out(c, 8)
    np.testing.assert_allclose(np.asarray(out)[c["valid"]],
                               np.asarray(ref)[c["valid"]], atol=2e-5)


@pytest.mark.parametrize("W", [5, 16])
def test_ragged_kernel_sliding_window(W):
    """Sliding-window page-skip carries over: decode rows skip pages
    before their window, prefill rows mask per-row."""
    rng = np.random.default_rng(W * 7)
    c = _ragged_case(rng, 4, [(7, 25)], 8, page=4, mp=12, max_kv=40)
    ref = _ragged_ref(c, sliding_window=W)
    out = _ragged_out(c, 8, sliding_window=W)
    np.testing.assert_allclose(np.asarray(out)[c["valid"]],
                               np.asarray(ref)[c["valid"]], atol=2e-5)


def test_ragged_reference_degenerates_to_phase_split_refs():
    """ops/attention.ragged_attention == paged_decode_attention on
    decode rows and chunked_prefill_attention on chunk rows — the
    semantic spec of the mixed path."""
    rng = np.random.default_rng(7)
    n_dec, chunks, blk = 3, [(5, 9), (12, 12)], 8
    c = _ragged_case(rng, n_dec, chunks, blk)
    ref = np.asarray(_ragged_ref(c))
    dec = ref_ops.paged_decode_attention(
        c["q"][:n_dec], c["kc"], c["vc"], c["bt"][:n_dec],
        c["kv_lens"][:n_dec], c["scale"])
    np.testing.assert_allclose(ref[:n_dec], np.asarray(dec), atol=2e-5)
    si = n_dec
    for ql, kl in chunks:
        st = int(c["q_starts"][si])
        ck = ref_ops.chunked_prefill_attention(
            c["q"][None, st:st + ql], c["kc"], c["vc"], c["bt"][si:si + 1],
            jnp.asarray([kl - ql], jnp.int32), jnp.asarray([ql], jnp.int32),
            c["scale"])
        np.testing.assert_allclose(ref[st:st + ql], np.asarray(ck[0]),
                                   atol=2e-5)
        si += 1
