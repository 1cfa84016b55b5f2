"""Pallas TPU paged attention for single-token decode.

One grid program walks up to ``MAX_SEQS_PER_PROGRAM`` sequences (a decode
batch is one program): the per-(sequence, page-group) KV chunks are DMA'd
from HBM into a double-buffered VMEM scratch using the block table
(scalar-prefetched so page addresses are known before the kernel body
runs), with the prefetch pipeline running *across sequence boundaries* —
while sequence ``s``'s last chunk is contracting, sequence ``s+1``'s first
chunk is already in flight, so HBM reads stay continuous for the batch.

This is the TPU-native replacement for the CUDA paged-attention kernels
inside the vLLM image the reference deploys (reference:
kubernetes-single-node.yaml:14; SURVEY.md §2.2, §7 "hard parts" — see also
PAPERS.md "Ragged Paged Attention").

**A page is contracted in the layout it is stored in** (PR 37).  The cache
is ``(num_blocks, page, Hkv, D)``; a page is ``page x Hkv`` contiguous rows
of ``D``, token-major.  The kernel sees it as that ``(page x Hkv, D)`` slab
(a bitcast of the cache, never a copy), lands it with one linear DMA into
full tiles of the scratch, and contracts ALL of a sequence's query heads
against the slab's ``rows x Hkv`` keys in one dot.  Heads are told apart on
the float32 score tile — a column whose key head is not the query row's is
masked like a position past the sequence's end, its probability is exactly
0 and it drops out of the ``P x V`` contraction over the same slab — not by
moving K and V.  What is left per row is sized by what is valid: a chunk is
contracted a tile of ``DECODE_TILE_COLUMNS`` key columns at a time, only
tiles that hold an attended position run, and V is rewritten only in a
sequence's first and last tile, the only ones that can hold a row outside
the sequence.

Why the stored layout (measured on a v5e at the benchmark cells' shapes,
B = 64, mean context 770; PERF.md §6, PR 37 has the table): landing a page
as ``(page, Hkv, D)`` pads 4 or 8 KV heads to bf16's 16 sublanes, and a dot
batched over KV heads needs K and V relaid head-major in every group.  A
body built that way reads 8.1-9.2 ns a cached token a layer where its DMAs
alone take 6.2 (8 KV heads) and 3.55 (4): its time follows the rows it
walks, not the bytes it reads.  This body reads 6.5 and 4.4-4.5; what
bounds it is its DMAs (two descriptors a page: 658 and 576 GB/s alone).

- **Native-dtype MXU dots.**  The QK and PV contractions consume q/k/v in
  their stored dtype (bf16 KV cache) with fp32 accumulation
  (``preferred_element_type``) — upcasting to fp32 *before* the dot runs
  the MXU at its slow fp32 rate for no accuracy gain.
- **Chunks and tiles.**  A DMA chunk is ``TARGET_GROUP_ROWS`` tokens deep
  (the prefetch distance); a compute tile ``DECODE_TILE_COLUMNS`` key
  columns wide.  Both are
  :func:`decode_tiling`'s, a static function of the shapes.

- **Which operand the MXU holds, in both entries: the cached tokens.**
  A tile of K (then of V) is the held operand and the sequence's query
  heads are the rows that stream past it, in the K/V entry (16-32 rows)
  and in the LATENT entry (:func:`_latent_decode_kernel`: one KV head,
  128 rows, V the first ``v_lanes`` lanes of the landed K page) alike.
  Holding the query heads instead (``s^T = K q^T``, ``o^T = V^T p^T``) was
  measured on a v5e at the latent cell's shape and is no faster: 5.97 ns
  a cached token a layer against this form's 5.95, scores alone
  transposed 7.17 (PERF.md section 6, PR 51): with no page copy at all
  the products, the softmax and the per-sequence work take 3.87 ns in
  this form (the two products ~2.2, against 1.81 at the MXU's peak for
  the tokens a chunk pads to) and 4.19 in the transposed one, which pays
  V's turn through the transposition unit.
- **What the latent entry does not share is the page pipeline.**  The
  K/V entry's (a loop over a chunk's attended pages to start them,
  another to wait for them) is scalar code that runs BETWEEN the
  products, not beside them: 3.0 ns a cached token a layer with no
  product at all, which the K/V entry hides (its 16-32 query rows make
  short products and its time is its copies') and the latent entry
  pays in full on top of 3.9 of products.  So the latent entry starts a
  chunk's pages straight-line, all of them, waits for a chunk once by
  its bytes and runs two chunks ahead over three slots: 4.8 ns.

Semantics match ``tpuserve.ops.attention.paged_decode_attention``; verified
against it in interpret mode on CPU.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuserve.ops import scopes
from tpuserve.ops.attention import SCALE_LANES

#: what the kernel's custom call is called in a profiler trace (the HLO
#: instruction's name); the benchmark's trace readers match it
KERNEL_NAME = "_paged_decode_attention"

logger = logging.getLogger("tpuserve.ops.paged_attention")

NEG_INF = -1e30

# Scoped VMEM the three paged kernels (decode, chunk window, ragged) are
# sized for.  It is BOTH the budget the block-size clamp below holds the
# footprint model to AND the ``vmem_limit_bytes`` handed to Mosaic, so the
# two cannot drift: a v5e core has 128 MiB of VMEM, but a kernel that does
# not say what it needs gets the compiler's 16 MiB default scope — which
# the chunk-window and ragged kernels overflow at Qwen3-0.6B widths
# through their f32 score tiles (tests/test_chip_compile.py pins the
# compiles).
VMEM_LIMIT_BYTES = 32 * 2**20

MIN_SUBLANES = {1: 32, 2: 16, 4: 8}   # Mosaic min tile rows by itemsize


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic params shared by the paged kernels: the grid semantics plus
    the VMEM scope the clamp sized the kernel for."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def vmem_footprint(pages_g: int, block_rows: int, dot_rows: int,
                   page_size: int, num_kv_heads: int, head_dim: int,
                   kv_itemsize: int, num_q_heads: int, q_itemsize: int,
                   quantized: bool = False, decode: bool = False,
                   flat_page: bool = False, v_lanes: int | None = None) -> int:
    """Upper bound on the scoped VMEM one program of a paged kernel needs.

    ``block_rows``: q rows in the pipelined q/out block (seqs_per_program
    for decode, blk_q for the window/ragged kernels); ``dot_rows``: q rows
    contracted against one page group at a time (blk_q for the window and
    ragged kernels; ``decode`` walks its block a sequence at a time).

    Counts what Mosaic allocates, not dense bytes — the trailing two dims
    of every VMEM array pad to the dtype's minimum tile:
      - KV scratch: 2 slots (double buffer) x {K,V} x rows_g tokens.  The
        window and ragged kernels land a page as (page, Hkv, D), whose
        Hkv pads to 32 rows for int8, 16 for bf16, 8 for f32 — which is
        why an 8-kv-head int8 cache does NOT shrink their scratch 2x
        (``flat_page``: the ragged kernel lands ONE KV head's pages as
        (page, D), with no head row to pad).
        ``decode`` lands it as the (page x Hkv, D) slab it is in HBM:
        full tiles, no padding, a half (8 kv heads) or a quarter (4) of
        the padded scratch in bf16;
      - int8 scale scratch: 2 x {K,V} x rows_g x SCALE_LANES f32;
      - q/out pipeline blocks: 2 buffers each (Pallas double-buffers
        grid-indexed blocks) x block_rows x padded(Hq) x D;
      - what the body keeps live while it contracts, which the compiler
        places on the same scoped stack.  Window / ragged: Mosaic
        walks the key axis a 128-lane tile at a time, so the live set is
        about eleven (Hkv, rows_q, 128) f32 slabs (score, exp, mask and
        key positions of the tile, the accumulator and its update) plus
        half the full (Hkv, rows_q, rows_g) f32 score tile, next to the
        relayouted K and V with one transposition temporary (and their
        f32 dequantization when the cache is int8).  The factors are
        fitted to the least ``vmem_limit_bytes`` the v5e compiler
        accepted over 54 shapes — Hq 4..64, GQA and MHA, pages_g 4..16,
        blk_q 32..128, bf16 and int8 — and bound every one from above
        (by 0..18 MiB).  This term, not the scratch, is what overflowed
        the default scope.  ``decode``: one tile of ``DECODE_TILE_COLUMNS``
        key columns at a time — the (Hq, columns) f32 score tile, its
        exponentials, the head mask and a spare, the K and V tiles as
        values, and an int8 tile's f32 dequantization.  ``decode`` with
        ``v_lanes`` (a LATENT entry, :func:`_latent_decode_kernel`): the
        same body on a whole chunk, over ``LATENT_SLOTS`` slots of K
        pages and no V scratch."""
    from tpuserve.utils import round_up
    lanes = round_up(head_dim, 128)   # lane dim pads to the 128 width too
    rows_g = pages_g * page_size
    q_heads = round_up(num_q_heads, MIN_SUBLANES.get(q_itemsize, 8))
    scales = 2 * 2 * round_up(rows_g, 8) * SCALE_LANES * 4 if quantized else 0
    qo = 2 * 2 * block_rows * q_heads * lanes * q_itemsize
    if decode:
        slab_g = round_up(rows_g * num_kv_heads,
                          MIN_SUBLANES.get(kv_itemsize, 8))
        slab_t = min(rows_g * num_kv_heads, DECODE_TILE_COLUMNS)
        kv = 2 * 2 * slab_g * lanes * kv_itemsize
        if v_lanes:
            kv = LATENT_SLOTS * slab_g * lanes * kv_itemsize
        score_tile = round_up(num_q_heads, 8) * round_up(slab_t, 128) * 4
        kv_values = 2 * slab_t * lanes * (q_itemsize
                                          + (2 * 4 if quantized else 0))
        return kv + scales + qo + 4 * score_tile + kv_values
    kv_rows = 1 if flat_page else round_up(
        num_kv_heads, MIN_SUBLANES.get(kv_itemsize, 8))
    rows_q = round_up(dot_rows * (num_q_heads // num_kv_heads), 8)
    kv = 2 * 2 * rows_g * kv_rows * lanes * kv_itemsize
    slab = num_kv_heads * rows_q * 128 * 4
    score_tile = slab * round_up(rows_g, 128) // 128
    kv_values = num_kv_heads * rows_g * lanes * (
        3 * q_itemsize + (2 * 4 if quantized else 0))
    # a head wider than one lane tile (a latent entry's 640): what the
    # slabs above count once a tile, the accumulator, its update and the
    # P x V product hold once a LANE TILE of the head, beside the
    # relayouted q
    wide = num_kv_heads * rows_q * (lanes - 128) * (3 * 4 + q_itemsize)
    return kv + scales + qo + 11 * slab + score_tile // 2 + kv_values + wide


def _clamp_to_vmem_budget(pages_g: int, block_rows: int, page_size: int,
                          num_kv_heads: int, head_dim: int,
                          kv_itemsize: int, num_q_heads: int,
                          q_itemsize: int, quantized: bool = False,
                          rows_per_dot: bool = False,
                          flat_page: bool = False,
                          v_lanes: int | None = None) -> tuple[int, int]:
    """Shrink (pages_g, block_rows) until :func:`vmem_footprint` fits
    ``VMEM_LIMIT_BYTES``.  ``rows_per_dot``: the kernel contracts its
    whole q block at once (window/ragged) rather than a sequence at a
    time (decode).  Where the pipelined q and out blocks ALONE pass half
    the budget (128 query heads of a 640-lane latent: 64 rows are 42 MiB)
    block_rows halves first, until they do not; then pages_g halves (it
    dominates at every other shape and shrinking it only shortens the DMA
    pipeline), then block_rows — so wide models get smaller blocks by
    rule, not by a per-model constant."""
    def footprint(pg: int, br: int) -> int:
        return vmem_footprint(pg, br, br if rows_per_dot else 1, page_size,
                              num_kv_heads, head_dim, kv_itemsize,
                              num_q_heads, q_itemsize, quantized,
                              decode=not rows_per_dot, flat_page=flat_page,
                              v_lanes=v_lanes)

    orig = (pages_g, block_rows)
    from tpuserve.utils import round_up
    row = 2 * 2 * round_up(num_q_heads, MIN_SUBLANES.get(q_itemsize, 8)) \
        * round_up(head_dim, 128) * q_itemsize      # q and out, one row
    while block_rows * row > VMEM_LIMIT_BYTES // 2 and block_rows > 1:
        block_rows //= 2
    while footprint(pages_g, block_rows) > VMEM_LIMIT_BYTES and pages_g > 1:
        pages_g //= 2
    while footprint(pages_g, block_rows) > VMEM_LIMIT_BYTES and block_rows > 1:
        block_rows //= 2
    if (pages_g, block_rows) != orig:
        logger.warning(
            "paged-attention blocks (pages_per_group=%d, q rows=%d) need "
            "%.1f MiB of VMEM (limit %.1f MiB); clamped to (%d, %d)",
            orig[0], orig[1], footprint(*orig) / 2**20,
            VMEM_LIMIT_BYTES / 2**20, pages_g, block_rows)
    return pages_g, block_rows


# Target K rows per page group: G = ceil(TARGET_GROUP_ROWS / page).  The
# window and ragged kernels contract a whole group in one dot; the decode
# kernel lands a group in one DMA chunk and contracts it a tile at a time.
TARGET_GROUP_ROWS = 512

# Key columns (cached tokens x KV heads) the decode kernel contracts at a
# time, the width of its float32 score tile: 2,048 read fastest at 8 KV
# heads (256 tokens) and at 4 (512) on a v5e; 1,024 reads 1-6 % slower,
# 512 a third slower, 4,096 7-9 % slower (PERF.md §6, PR 37).
DECODE_TILE_COLUMNS = 2048

# Most sequences one decode program walks.  The cross-sequence DMA chain
# restarts at every program, so a batch is one program where it fits
# (64 rows in one program read 2 % faster than in eight).
MAX_SEQS_PER_PROGRAM = 64

# Slots of the latent entry's page pipeline: while one chunk is contracted
# the next has landed or is landing, and the one after it is being started.
LATENT_SLOTS = 3


def decode_tiling(page_size: int, num_kv_heads: int, max_pages: int,
                  batch: int) -> tuple[int, int, int]:
    """``(pages_g, pages_t, seqs_pp)`` of the decode kernel, a static
    function of the shapes as measured on a v5e (PERF.md §6, PR 37): a DMA
    chunk of ``TARGET_GROUP_ROWS`` tokens (256 and 1,024 read within 3 %
    of it), compute tiles of ``DECODE_TILE_COLUMNS`` key columns (a whole
    number of them a chunk), the whole batch in one program up to
    ``MAX_SEQS_PER_PROGRAM`` rows.  One KV head (a latent entry) makes a
    tile as wide as the chunk, which its kernel contracts whole: measured
    at 128 query heads on 640 lanes with a wait a page, a chunk of 512
    tokens reads 5.0 ns a cached token a layer, 256 and 1,024 tokens 5.3
    and 5.4-5.5 (more chunks, or more pages started past a sequence's
    end; PERF.md section 6, PR 51)."""
    pages_g = min(max(1, -(-TARGET_GROUP_ROWS // page_size)), max_pages)
    pages_t = max(1, DECODE_TILE_COLUMNS // (page_size * num_kv_heads))
    return pages_g, _tile_pages(pages_g, pages_t), min(
        batch, MAX_SEQS_PER_PROGRAM)


def _tile_pages(pages_g: int, pages_t: int) -> int:
    """The largest whole divisor of a chunk's pages up to ``pages_t``."""
    pages_t = max(1, min(pages_t, pages_g))
    while pages_g % pages_t:
        pages_t -= 1
    return pages_t


def _scale_rows(scr, num_kv_heads: int):
    """One slot's landed scale pages (pages_g, page, SCALE_LANES) ->
    (Hkv, rows_g): the kv heads sit in the first lanes of each row.  For
    the window and ragged kernels, which relayout K and V head-major."""
    rows = scr.reshape(-1, scr.shape[-1])[:, :num_kv_heads]
    return jnp.swapaxes(rows, 0, 1)


def _paged_decode_kernel(bt_ref, sl_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_scr, v_scr, sems, *, scale, page_size, pages_g,
                         pages_t, num_kv_heads, group, head_dim, seqs_pp,
                         ks_hbm=None, vs_hbm=None, ks_scr=None, vs_scr=None,
                         sliding_window=None, logit_softcap=None):
    """A page is contracted in the layout it is stored in: ``k_hbm`` /
    ``v_hbm`` are the cache seen as ``(num_blocks, page x Hkv, D)`` slabs
    (row ``t x Hkv + h``), a page lands in full tiles of the scratch as
    one linear piece, and ALL query heads of a sequence meet ``rows x
    Hkv`` key columns in one dot.  Heads are told apart on the score tile:
    a column whose key head is not the query row's is masked like a
    position past the sequence's end, its probability is exactly 0 and it
    drops out of the ``P x V`` contraction over the same slab.

    ``ks_hbm``/``vs_hbm`` present = int8 cache: value pages DMA as int8
    beside their scale pages and are dequantized in the slab layout.

    ``sliding_window`` (static): attend only the last W cached positions;
    pages entirely BEFORE the window are never DMA'd and tiles entirely
    before it never contracted."""
    quantized = ks_hbm is not None
    base = pl.program_id(0) * seqs_pp
    num_q_heads = num_kv_heads * group
    rows_g = pages_g * page_size        # tokens a DMA chunk
    rows_t = pages_t * page_size        # tokens a compute tile
    slab_p = page_size * num_kv_heads   # slab rows a page
    slab_t = rows_t * num_kv_heads      # slab rows (key columns) a tile

    def win_start(s):
        # first attended position (0 without a window)
        if sliding_window is None:
            return jnp.int32(0)
        return jnp.maximum(sl_ref[base + s] - sliding_window, 0)

    def num_groups(s):
        # >= 1 so padded/empty sequences keep the chunk pipeline uniform
        # (their zero pages mean no DMAs start and no waits happen).
        return jnp.maximum(pl.cdiv(sl_ref[base + s], rows_g), 1)

    def first_group(s):
        return win_start(s) // rows_g

    def page_range(s, g, lo, n):
        """The pages of chunk ``g``'s slots ``[lo, lo + n)`` that hold an
        attended position, as slot indices.  MUST be the same for start
        and wait, or the semaphores desync."""
        first = jnp.maximum(win_start(s) // page_size - g * pages_g, lo)
        last = jnp.minimum(pl.cdiv(sl_ref[base + s], page_size)
                           - g * pages_g, lo + n)
        return first, last

    def _copies(s, g, slot, j):
        page = bt_ref[base + s, g * pages_g + j]
        rows = pl.ds(pl.multiple_of(j * slab_p, slab_p), slab_p)
        copies = [pltpu.make_async_copy(k_hbm.at[page], k_scr.at[slot, rows],
                                        sems.at[0, slot, j]),
                  pltpu.make_async_copy(v_hbm.at[page], v_scr.at[slot, rows],
                                        sems.at[1, slot, j])]
        if quantized:
            copies += [
                pltpu.make_async_copy(ks_hbm.at[page], ks_scr.at[slot, j],
                                      sems.at[2, slot, j]),
                pltpu.make_async_copy(vs_hbm.at[page], vs_scr.at[slot, j],
                                      sems.at[3, slot, j]),
            ]
        return copies

    def each_copy(s, g, slot, lo, n, act):
        def one(j, _):
            for c in _copies(s, g, slot, j):
                act(c)
            return 0
        jax.lax.fori_loop(*page_range(s, g, lo, n), one, 0)

    def start_chunk(s, g, slot):
        each_copy(s, g, slot, 0, pages_g, lambda c: c.start())

    # which key columns of a tile belong to which query row: column c of
    # the slab is key head c % Hkv, query row i reads key head i // group
    # (one KV head: every column is every row's)
    own_head = True
    if num_kv_heads > 1:
        col_head = jax.lax.rem(jax.lax.broadcasted_iota(
            jnp.int32, (num_q_heads, slab_t), 1), num_kv_heads)
        row_head = jax.lax.broadcasted_iota(
            jnp.int32, (num_q_heads, slab_t), 0) // group
        own_head = col_head == row_head

    def dequant(vals, scr, slot, t, keep=None):
        """int8 slab rows of tile ``t`` x their (token, head) scales, in
        the slab's own layout: (rows_t x Hkv, D) in q's dtype; zeros
        where ``keep`` (slab rows, 1) is false."""
        sc = scr[slot, pl.ds(t * pages_t, pages_t)]
        sc = sc.reshape(rows_t, sc.shape[-1])[:, :num_kv_heads]
        vals = vals.astype(jnp.float32).reshape(rows_t, num_kv_heads,
                                                head_dim)
        vals = (vals * sc[:, :, None]).reshape(slab_t, head_dim)
        if keep is not None:
            vals = jnp.where(keep, vals, 0.0)
        return vals.astype(q_ref.dtype)

    start_chunk(0, first_group(0), 0)

    def seq_body(s, parity0):
        seq_len = sl_ref[base + s]
        g0 = first_group(s)
        neff = num_groups(s) - g0       # chunks this sequence lands
        ws = win_start(s)
        q = q_ref[s]                    # (Hq, D), stored dtype

        m0 = jnp.full((num_q_heads, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((num_q_heads, 1), jnp.float32)
        acc0 = jnp.zeros((num_q_heads, head_dim), jnp.float32)

        def chunk_body(i, carry):
            g = g0 + i
            slot = jax.lax.rem(parity0 + i, 2)

            # Prefetch the pipeline's next chunk into the other slot:
            # this sequence's next one, or the next sequence's first
            # IN-WINDOW one.
            @pl.when(i + 1 < neff)
            def _prefetch_group():
                start_chunk(s, g + 1, 1 - slot)

            @pl.when((i + 1 == neff) & (s + 1 < seqs_pp))
            def _prefetch_seq():
                start_chunk(s + 1, first_group(s + 1), 1 - slot)

            # the chunk's attended positions, relative to its first
            lo = jnp.maximum(ws - g * rows_g, 0)
            hi = jnp.minimum(seq_len - g * rows_g, rows_g)

            def tile_body(t, carry):
                m_prev, l_prev, acc_prev = carry
                each_copy(s, g, slot, t * pages_t, pages_t,
                          lambda c: c.wait())
                rows = pl.ds(pl.multiple_of(t * slab_t, slab_t), slab_t)
                # attended key columns / slab rows of this tile
                c_lo = (lo - t * rows_t) * num_kv_heads
                c_hi = (hi - t * rows_t) * num_kv_heads

                # A row outside [lo, hi) is a page that was never DMA'd
                # (unspecified scratch) or a slot the cache never wrote:
                # its probability is exactly 0, but 0 x NaN would poison
                # the accumulator.  Only a sequence's first and last
                # tile can hold one, so only they are rewritten (an int8
                # tile is rewritten anyway: the select rides on that).
                def attended_rows():
                    r = jax.lax.broadcasted_iota(jnp.int32, (slab_t, 1), 0)
                    return (r >= c_lo) & (r < c_hi)

                if not quantized:
                    @pl.when((c_lo > 0) | (c_hi < slab_t))
                    def _clean_v():
                        v_t = v_scr[slot, rows]
                        v_scr[slot, rows] = jnp.where(attended_rows(), v_t,
                                                      jnp.zeros_like(v_t))

                k = k_scr[slot, rows]
                v = v_scr[slot, rows]
                if quantized:
                    k = dequant(k, ks_scr, slot, t)
                    v = dequant(v, vs_scr, slot, t, keep=attended_rows())
                # (Hq, D) x (rows_t x Hkv, D) -> (Hq, rows_t x Hkv); MXU
                # inputs in the stored dtype, fp32 accumulation; scale on
                # the fp32 product.
                sc = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if logit_softcap is not None:
                    sc = logit_softcap * jnp.tanh(sc / logit_softcap)
                c = jax.lax.broadcasted_iota(jnp.int32, (1, slab_t), 1)
                sc = jnp.where(own_head & (c >= c_lo) & (c < c_hi),
                               sc, NEG_INF)

                m_new = jnp.maximum(m_prev,
                                    jnp.max(sc, axis=1, keepdims=True))
                pr = jnp.exp(sc - m_new)
                correction = jnp.exp(m_prev - m_new)
                l_new = l_prev * correction + jnp.sum(pr, axis=1,
                                                      keepdims=True)
                # masked columns have pr == 0 exactly; pr in V's dtype
                # keeps the second contraction on the fast MXU path
                pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                return m_new, l_new, acc_prev * correction + pv

            return jax.lax.fori_loop(lo // rows_t, pl.cdiv(hi, rows_t),
                                     tile_body, carry)

        m, l, acc = jax.lax.fori_loop(0, neff, chunk_body, (m0, l0, acc0))
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[s] = (acc / safe_l).astype(o_ref.dtype)
        return parity0 + neff

    jax.lax.fori_loop(0, seqs_pp, seq_body, 0)


def _latent_decode_kernel(bt_ref, sl_ref, q_ref, k_hbm, o_ref, k_scr, sems, *,
                          scale, page_size, pages_g, seqs_pp, v_lanes,
                          sliding_window=None, logit_softcap=None):
    """The LATENT entry (MLA's absorbed form): ONE KV head, all the query
    heads (128 at the published widths) against it, V the first
    ``v_lanes`` lanes of the K page as it landed, so no V page exists and
    the output is ``v_lanes`` wide.  ``k_hbm`` is the cache seen as
    ``(num_blocks, page, D)``.

    The contraction is the K/V entry's (``s = q K^T``, ``o = p V``, bf16
    MXU inputs, float32 accumulation, the scale on the float32 product)
    with nothing to tell heads apart; the PAGE PIPELINE is this entry's
    own, because at 128 query rows the products are as long as the page
    copies' issue, and the K/V entry's pipeline (a loop over a chunk's
    attended pages to start them, another to wait for them) runs as
    scalar code between the products, which it does not overlap:

    - a chunk starts ALL its ``pages_g`` pages, straight-line, and a
      sequence's last chunk reads whatever pages the table names past its
      end (their rows are masked like any position past the end, and V's
      are zeroed before the product);
    - every page of a chunk signals the slot's one semaphore and the
      chunk is waited for ONCE, by its bytes;
    - the pipeline is two chunks deep over ``LATENT_SLOTS`` slots, across
      sequence boundaries, so a chunk's copies have two products to land
      behind.

    Measured on a v5e at the openPangu cell's shape (128 rows, contexts
    256-3,072, PERF.md section 6, PR 51): 4.8 ns a cached token a layer
    against the shared pipeline's 5.95; the same call with no copies at
    all 3.8, with the copies alone 3.4."""
    base = pl.program_id(0) * seqs_pp
    num_q_heads = q_ref.shape[1]
    rows_g = pages_g * page_size        # tokens a chunk
    last_page, last_col = k_hbm.shape[0] - 1, bt_ref.shape[1] - 1

    def win_start(s):
        # first attended position (0 without a window)
        if sliding_window is None:
            return jnp.int32(0)
        return jnp.maximum(sl_ref[base + s] - sliding_window, 0)

    def first_chunk(s):
        return win_start(s) // rows_g

    def num_chunks(s):
        # >= 1 so a padded or empty row keeps the pipeline uniform
        return jnp.maximum(pl.cdiv(sl_ref[base + s], rows_g),
                           1) - first_chunk(s)

    def start(s, i, slot, straight_line=True):
        first = (first_chunk(s) + i) * pages_g

        def one(j, _=None):
            page = bt_ref[base + s, jnp.minimum(first + j, last_col)]
            rows = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            pltpu.make_async_copy(k_hbm.at[jnp.clip(page, 0, last_page)],
                                  k_scr.at[slot, rows], sems.at[slot]).start()

        if straight_line:
            for j in range(pages_g):
                one(j)
        else:   # before a program's first product a loop is as fast, and
            # the program smaller than the K/V entry's was (it lives in HBM)
            jax.lax.fori_loop(0, pages_g, one, None)

    def wait(slot):
        # the semaphore counts bytes: one wait for the chunk's, whatever
        # the descriptor's source says
        pltpu.make_async_copy(k_scr.at[slot], k_scr.at[slot],
                              sems.at[slot]).wait()

    def succ(s, i):
        """The chunk after chunk ``i`` of sequence ``s``; past the
        program's last chunk, its last sequence's first again (started
        like any other, waited for after the loop)."""
        last = i + 1 >= num_chunks(s)
        return (jnp.where(last, jnp.minimum(s + 1, seqs_pp - 1), s),
                jnp.where(last, 0, i + 1))

    chunk = (jnp.int32(0), jnp.int32(0))
    for slot in range(LATENT_SLOTS - 1):
        start(*chunk, slot, straight_line=False)
        chunk = succ(*chunk)

    def seq_body(s, count0):
        seq_len = sl_ref[base + s]
        ws = win_start(s)
        g0, n = first_chunk(s), num_chunks(s)
        q = q_ref[s]                    # (Hq, D), stored dtype

        m0 = jnp.full((num_q_heads, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((num_q_heads, 1), jnp.float32)
        acc0 = jnp.zeros((num_q_heads, v_lanes), jnp.float32)

        def chunk_body(i, carry):
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(count0 + i, LATENT_SLOTS)
            wait(slot)
            ahead = (s, i)
            for _ in range(LATENT_SLOTS - 1):
                ahead = succ(*ahead)
            # the chunk's attended positions, relative to its first
            g = g0 + i
            lo = jnp.maximum(ws - g * rows_g, 0)
            hi = jnp.minimum(seq_len - g * rows_g, rows_g)

            # V is this page: a row outside [lo, hi) is a slot the cache
            # never wrote or another sequence's, its probability is
            # exactly 0, but 0 x NaN would poison the accumulator.  Only
            # a sequence's first and last chunk can hold one.
            @pl.when((lo > 0) | (hi < rows_g))
            def _clean_v():
                r = jax.lax.broadcasted_iota(jnp.int32, (rows_g, 1), 0)
                k_c = k_scr[slot]
                k_scr[slot] = jnp.where((r >= lo) & (r < hi), k_c,
                                        jnp.zeros_like(k_c))

            start(*ahead, jax.lax.rem(count0 + i + LATENT_SLOTS - 1,
                                      LATENT_SLOTS))
            k = k_scr[slot]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if logit_softcap is not None:
                sc = logit_softcap * jnp.tanh(sc / logit_softcap)
            c = jax.lax.broadcasted_iota(jnp.int32, (1, rows_g), 1)
            sc = jnp.where((c >= lo) & (c < hi), sc, NEG_INF)

            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            pr = jnp.exp(sc - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(pr, axis=1, keepdims=True)
            v = k[:, :v_lanes]
            pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            return m_new, l_new, acc_prev * correction + pv

        m, l, acc = jax.lax.fori_loop(0, n, chunk_body, (m0, l0, acc0))
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[s] = (acc / safe_l).astype(o_ref.dtype)
        return count0 + n

    count = jax.lax.fori_loop(0, seqs_pp, seq_body, 0)
    for d in range(LATENT_SLOTS - 1):   # the starts past the last chunk
        wait(jax.lax.rem(count + d, LATENT_SLOTS))


def paged_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                           seq_lens: jnp.ndarray, scale: float,
                           interpret: bool | None = None,
                           pages_per_group: int | None = None,
                           seqs_per_program: int | None = None,
                           k_scale: jnp.ndarray | None = None,
                           v_scale: jnp.ndarray | None = None,
                           sliding_window: int | None = None,
                           logit_softcap: float | None = None,
                           v_lanes: int | None = None) -> jnp.ndarray:
    """q: (B, Hq, D); k_cache/v_cache: (num_blocks, page, Hkv, D);
    block_tables: (B, max_pages) int32; seq_lens: (B,). -> (B, Hq, D).
    A LATENT entry (MLA's absorbed form) hands ``v_cache=None`` and
    ``v_lanes``: V is the first ``v_lanes`` lanes of the K page as it
    landed, no V page exists or is read, and the result is
    (B, Hq, v_lanes).
    ``k_scale``/``v_scale``: (num_blocks, page, SCALE_LANES) f32 when the
    cache stores int8 (ops/attention.py pad_scale_lanes) — value pages
    then move over HBM at half the bytes and dequantize on the VPU inside
    the kernel.
    ``sliding_window``: attend only the last W positions; out-of-window
    pages are never DMA'd.

    The block sizes are :func:`decode_tiling`'s, held to the VMEM budget;
    ``pages_per_group`` / ``seqs_per_program`` override them for the
    tests (a tile is then the largest whole divisor of the group up to
    ``DECODE_TILE_COLUMNS`` key columns)."""
    with jax.named_scope(scopes.ATTN_KERNEL):
        page_size = k_cache.shape[1]
        max_pages = block_tables.shape[1]
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        pages_g, pages_t, seqs_pp = decode_tiling(page_size, k_cache.shape[2],
                                                  max_pages, q.shape[0])
        if pages_per_group:
            pages_g = min(pages_per_group, max_pages)
        if seqs_per_program:
            seqs_pp = min(seqs_per_program, q.shape[0])
        pages_g, seqs_pp = _clamp_to_vmem_budget(
            pages_g, seqs_pp, page_size, k_cache.shape[2], k_cache.shape[3],
            k_cache.dtype.itemsize, q.shape[1], q.dtype.itemsize,
            quantized=k_scale is not None, v_lanes=v_lanes)
        pages_t = _tile_pages(pages_g, pages_t)
        if v_lanes:     # the latent kernel contracts a chunk whole
            pages_g = pages_t
        scales = () if k_scale is None else (k_scale, v_scale)
        if (v_cache is None) != (v_lanes is not None) or (
                v_lanes and scales):
            raise ValueError("a latent entry is v_cache=None with v_lanes "
                             "set, and has no int8 form in this kernel")
        return _paged_decode_attention(q, k_cache, v_cache, block_tables,
                                       seq_lens, scales, scale=scale,
                                       interpret=interpret, pages_g=pages_g,
                                       pages_t=pages_t, seqs_pp=seqs_pp,
                                       sliding_window=sliding_window,
                                       logit_softcap=logit_softcap,
                                       v_lanes=v_lanes)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "pages_g", "pages_t", "seqs_pp",
                                             "sliding_window",
                                             "logit_softcap", "v_lanes"))
def _paged_decode_attention(q, k_cache, v_cache, block_tables, seq_lens,
                            scales, *, scale: float, interpret: bool,
                            pages_g: int, pages_t: int, seqs_pp: int,
                            sliding_window: int | None = None,
                            logit_softcap: float | None = None,
                            v_lanes: int | None = None) -> jnp.ndarray:
    B, Hq, D = q.shape
    num_blocks, page_size, Hkv, _ = k_cache.shape
    group = Hq // Hkv
    quantized = bool(scales)
    # A page as it is stored: page x Hkv contiguous rows of D.  The same
    # bytes in the same order, so XLA makes this a bitcast, not a copy
    # (tests/test_chip_compile.py reads the compiled text for it).  A
    # latent entry (v_lanes) has the K pages alone.
    pages = [c.reshape(num_blocks, page_size * Hkv, D)
             for c in ((k_cache,) if v_lanes else (k_cache, v_cache))]
    Dv = v_lanes or D

    # Pad the batch to a whole number of programs; padded rows have
    # seq_len 0 (no DMAs, nothing contracted) and are sliced off below.
    Bp = -(-B // seqs_pp) * seqs_pp
    if Bp != B:
        pad = Bp - B
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, pad), (0, 0)))
        seq_lens = jnp.pad(seq_lens, ((0, pad),))

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, page_size=page_size,
        pages_g=pages_g, pages_t=pages_t, num_kv_heads=Hkv, group=group,
        head_dim=D, seqs_pp=seqs_pp, sliding_window=sliding_window,
        logit_softcap=logit_softcap)
    # operand order must mirror the in_specs/scratch below
    base_kernel = kernel
    if quantized:
        def kernel(bt, sl, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
                   k_scr, v_scr, ks_scr, vs_scr, sems):
            return base_kernel(bt, sl, q_ref, k_hbm, v_hbm, o_ref,
                               k_scr, v_scr, sems, ks_hbm=ks_hbm,
                               vs_hbm=vs_hbm, ks_scr=ks_scr, vs_scr=vs_scr)
    elif v_lanes:
        kernel = functools.partial(
            _latent_decode_kernel, scale=scale, page_size=page_size,
            pages_g=pages_g, seqs_pp=seqs_pp, v_lanes=v_lanes,
            sliding_window=sliding_window, logit_softcap=logit_softcap)

    # the caches stay in HBM
    in_specs = [pl.BlockSpec((seqs_pp, Hq, D), lambda p, bt, sl: (p, 0, 0))
                ] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pages)
    slab_g = pages_g * page_size * Hkv
    if v_lanes:
        scratch = [pltpu.VMEM((LATENT_SLOTS, slab_g, D), k_cache.dtype),
                   pltpu.SemaphoreType.DMA((LATENT_SLOTS,))]
    else:
        scratch = [pltpu.VMEM((2, slab_g, D), c.dtype) for c in pages]
        if quantized:
            in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2  # scale pages
            scratch += [pltpu.VMEM((2, pages_g, page_size, SCALE_LANES),
                                   jnp.float32)] * 2
        scratch.append(pltpu.SemaphoreType.DMA((4 if quantized else 2,
                                                2, pages_g)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bp // seqs_pp,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((seqs_pp, Hq, Dv), lambda p, bt, sl: (p, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, Hq, Dv), q.dtype),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
        name=KERNEL_NAME,
    )(block_tables, seq_lens, q, *pages, *scales)
    return out[:B]
