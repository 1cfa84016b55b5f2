"""Pallas TPU ragged paged attention: mixed prefill + decode in ONE kernel.

The phase-split engine dispatches decode batches and prefill chunks as
separate executables with separate (batch x length) padding grids.  This
kernel serves BOTH from one flat token stream ("Ragged Paged Attention",
PAPERS.md arxiv 2604.15464): the grid partitions the flat (T, Hq, D) query
array into ``blk_q``-row blocks, and scalar-prefetched per-sequence
descriptors — (q_start, q_len, kv_len) plus each sequence's block table —
tell every block what it is serving:

- **decode blocks** (the first ``meta[1]`` programs): ``blk_q`` one-row
  decode sequences, flat row ``r`` == sequence ``r``.  Each program runs
  the cross-sequence double-buffered page-DMA pipeline of the decode
  kernel (pallas_paged_attention.py) — while row ``j``'s last page group
  contracts, row ``j+1``'s first group is already in flight;
- **prefill blocks** (``blk_seq[p] >= 0``): one sequence's ``blk_q``-row
  chunk window, the online-softmax page-group loop of the chunked-prefill
  kernel (pallas_chunked_prefill.py) with causal-within-window masking on
  top of the cached context.

The host layout contract (engine._run_mixed): decode rows first, densely
packed; each prefill chunk starts ``blk_q``-aligned; T is a flat-token
bucket, any multiple of ``blk_q`` (mixed steps take powers of two, packed
batched prefills a finer ladder) — the ONE bucketed dimension that
replaces the old (batch x length) grid.  int8-KV dequant-in-VMEM and sliding-window
page-skip carry over from both parent kernels unchanged.

Semantics match ``tpuserve.ops.attention.ragged_attention``; verified
against it (and against the two phase-split kernels composed) in
interpret mode on CPU (tests/test_kernels.py) so kernel-vs-reference
parity gates without a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuserve.ops import scopes
from tpuserve.ops.attention import SCALE_LANES, dequantize_kv
from tpuserve.ops.pallas_paged_attention import (TARGET_GROUP_ROWS,
                                                 VMEM_LIMIT_BYTES,
                                                 _clamp_to_vmem_budget,
                                                 _scale_rows,
                                                 compiler_params,
                                                 vmem_footprint)

#: what the kernel's custom call is called in a profiler trace (the HLO
#: instruction's name), as the decode and flash kernels have theirs
KERNEL_NAME = "_ragged_paged_attention"

NEG_INF = -1e30

# Flat-row block granularity: the grid's q-block size AND the alignment
# the engine pads prefill-chunk starts to.  128 rows keep the MXU busy on
# TPU; 8 keeps interpret-mode tests and CPU-serving padding waste small.
DEFAULT_BLOCK_Q = 128


def ragged_block(blk_q: int | None = None) -> int:
    """The flat-row block size the mixed engine must lay its stream out
    with (decode region padded to a multiple, prefill chunks aligned to
    it).  One source of truth shared by the kernel and the engine's
    host-side packing — drift would desync ``blk_seq`` from the grid."""
    if blk_q:
        return blk_q
    return DEFAULT_BLOCK_Q if jax.default_backend() == "tpu" else 8


def ragged_block_for(num_q_heads: int, num_kv_heads: int, head_dim: int,
                     page_size: int, kv_itemsize: int, q_itemsize: int,
                     quantized: bool = False) -> int:
    """:func:`ragged_block` for a model's shape: halved until the kernel's
    q and out blocks and its live tiles fit the VMEM budget beside ONE
    page of K and V (the page group may shrink inside the kernel, the
    block may not: it is the layout contract with the host packing).  128
    rows at up to 32 query heads of 128; 64 at 64 heads on 8 KV heads."""
    blk = ragged_block()
    while blk > 8 and vmem_footprint(
            1, blk, blk, page_size, num_kv_heads, head_dim, kv_itemsize,
            num_q_heads, q_itemsize, quantized,
            flat_page=num_kv_heads == 1) > VMEM_LIMIT_BYTES:
        blk //= 2
    return blk


def _ragged_kernel(bt_ref, kv_ref, qs_ref, ql_ref, meta_ref, bseq_ref,
                   q_ref, k_hbm, v_hbm, o_ref, k_scr, v_scr, sems, *,
                   scale, page_size, pages_g, num_kv_heads, group,
                   head_dim, blk_q, ks_hbm=None, vs_hbm=None, ks_scr=None,
                   vs_scr=None, sliding_window=None, logit_softcap=None,
                   decode_rows=True, v_lanes=None):
    """``ks_hbm``/``vs_hbm`` present = int8 cache (pages DMA as int8 with
    per-page scale blocks, dequantized in VMEM).  ``v_lanes`` (static)
    set = a LATENT entry (``v_hbm`` and ``v_scr`` are None): V is the
    first ``v_lanes`` lanes of the landed K page, no V page is read and
    the output is ``v_lanes`` wide.  One KV head's pages are handed over
    and landed as ``(page, D)``, not ``(page, 1, D)`` whose single row
    would pad to a sublane tile.  ``sliding_window``
    (static): out-of-window pages are never DMA'd, in both parts.
    ``decode_rows`` (static) False: the caller dispatches no decode rows
    (``meta`` is zero), and the kernel is built without its decode part."""
    quantized = ks_hbm is not None
    latent = v_lanes is not None
    v_dim = v_lanes or head_dim
    p = pl.program_id(0)
    B = kv_ref.shape[0]
    num_decode = meta_ref[0]
    n_dec_blocks = meta_ref[1]
    rows_g = pages_g * page_size

    def _copies(seq, g, slot, j):
        page = bt_ref[seq, g * pages_g + j]
        copies = [pltpu.make_async_copy(k_hbm.at[page], k_scr.at[slot, j],
                                        sems.at[0, slot, j])]
        if not latent:
            copies.append(
                pltpu.make_async_copy(v_hbm.at[page], v_scr.at[slot, j],
                                      sems.at[1, slot, j]))
        if quantized:
            copies += [
                pltpu.make_async_copy(ks_hbm.at[page], ks_scr.at[slot, j],
                                      sems.at[2, slot, j]),
                pltpu.make_async_copy(vs_hbm.at[page], vs_scr.at[slot, j],
                                      sems.at[3, slot, j]),
            ]
        return copies

    def _move_group(seq, g, slot, needed, start):
        """Start (or wait on) one page group's DMAs.  ``needed(j)`` MUST
        be identical between the start and wait calls or the semaphores
        desync — both parts close over the same predicate."""
        def one(j, _):
            @pl.when(needed(g, j))
            def _():
                for c in _copies(seq, g, slot, j):
                    (c.start if start else c.wait)()
            return 0
        jax.lax.fori_loop(0, pages_g, one, 0)

    def _head_major(scr, slot):
        if num_kv_heads == 1:
            return scr[slot].reshape(1, rows_g, head_dim)
        return jnp.swapaxes(
            scr[slot].reshape(rows_g, num_kv_heads, head_dim), 0, 1)

    def _dequant(slot):
        k = _head_major(k_scr, slot)
        if latent:
            return k, k[..., :v_lanes]
        v = _head_major(v_scr, slot)
        if quantized:
            k = dequantize_kv(k, _scale_rows(ks_scr[slot], num_kv_heads),
                              q_ref.dtype)
            v = dequantize_kv(v, _scale_rows(vs_scr[slot], num_kv_heads),
                              q_ref.dtype)
        return k, v

    # ---- decode part: blk_q one-row sequences, flat row == sequence ----

    def _decode_part():
        base = p * blk_q

        def seq_idx(j):
            # descriptor row, clamped: rows past num_decode are padding
            # (sl() returns 0 for them — no DMAs, no compute)
            return jnp.minimum(base + j, B - 1)

        def sl(j):
            return jnp.where(base + j < num_decode, kv_ref[seq_idx(j)], 0)

        def num_pages(j):
            return pl.cdiv(sl(j), page_size)

        def num_groups(j):
            return jnp.maximum(pl.cdiv(sl(j), rows_g), 1)

        def win_start(j):
            if sliding_window is None:
                return jnp.int32(0)
            return jnp.maximum(sl(j) - sliding_window, 0)

        def first_group(j):
            if sliding_window is None:
                return jnp.int32(0)
            return win_start(j) // rows_g

        def needed_for(j):
            def needed(g, i):
                pi = g * pages_g + i
                ok = pi < num_pages(j)
                if sliding_window is not None:
                    ok &= pi >= win_start(j) // page_size
                return ok
            return needed

        _move_group(seq_idx(0), first_group(0), 0, needed_for(0),
                    start=True)

        def seq_body(j, parity0):
            seq_len = sl(j)
            ng = num_groups(j)
            g0 = first_group(j)
            neff = ng - g0
            ws = win_start(j)
            q_r = q_ref[pl.ds(j, 1)].reshape(num_kv_heads, group, head_dim)

            m0 = jnp.full((num_kv_heads, group, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((num_kv_heads, group, 1), jnp.float32)
            acc0 = jnp.zeros((num_kv_heads, group, v_dim), jnp.float32)

            def body(i, carry):
                g = g0 + i
                m_prev, l_prev, acc_prev = carry
                slot = jax.lax.rem(parity0 + i, 2)

                @pl.when(i + 1 < neff)
                def _prefetch_group():
                    _move_group(seq_idx(j), g + 1, 1 - slot,
                                needed_for(j), start=True)

                @pl.when((i + 1 == neff) & (j + 1 < blk_q))
                def _prefetch_seq():
                    _move_group(seq_idx(j + 1), first_group(j + 1),
                                1 - slot, needed_for(j + 1), start=True)

                _move_group(seq_idx(j), g, slot, needed_for(j),
                            start=False)
                k, v = _dequant(slot)
                row_pos = g * rows_g + jax.lax.broadcasted_iota(
                    jnp.int32, (num_kv_heads, rows_g, 1), 1)
                v_valid = row_pos < seq_len
                if sliding_window is not None:
                    v_valid &= row_pos >= ws
                v = jnp.where(v_valid, v, jnp.zeros_like(v))
                sc = jax.lax.dot_general(
                    q_r, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32) * scale
                if logit_softcap is not None:
                    sc = logit_softcap * jnp.tanh(sc / logit_softcap)
                pos = g * rows_g + jax.lax.broadcasted_iota(
                    jnp.int32, (num_kv_heads, group, rows_g), 2)
                s_valid = pos < seq_len
                if sliding_window is not None:
                    s_valid &= pos >= ws
                sc = jnp.where(s_valid, sc, NEG_INF)
                m_cur = jnp.max(sc, axis=2, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                pr = jnp.exp(sc - m_new)
                correction = jnp.exp(m_prev - m_new)
                l_new = (l_prev * correction
                         + jnp.sum(pr, axis=2, keepdims=True))
                pv = jax.lax.dot_general(
                    pr.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
                acc_new = acc_prev * correction + pv
                return m_new, l_new, acc_new

            m, l, acc = jax.lax.fori_loop(0, neff, body, (m0, l0, acc0))
            safe_l = jnp.where(l == 0.0, 1.0, l)
            out = (acc / safe_l).reshape(1, num_kv_heads * group, v_dim)
            o_ref[pl.ds(j, 1)] = out.astype(o_ref.dtype)
            return parity0 + neff

        jax.lax.fori_loop(0, blk_q, seq_body, 0)

    if decode_rows:
        pl.when(p < n_dec_blocks)(_decode_part)

    # ---- prefill part: one sequence's blk_q-row chunk window ----------

    @pl.when((p >= n_dec_blocks) & (bseq_ref[p] >= 0))
    def _prefill_part():
        s = jnp.minimum(jnp.maximum(bseq_ref[p], 0), B - 1)
        ctx = kv_ref[s] - ql_ref[s]
        qoff = p * blk_q - qs_ref[s]           # within-chunk row offset
        q_start = ctx + qoff                   # global position of row 0
        kv_limit = jnp.minimum(kv_ref[s], q_start + blk_q)
        num_pages = pl.cdiv(kv_limit, page_size)
        num_groups = pl.cdiv(num_pages, pages_g)
        if sliding_window is None:
            blk_ws = jnp.int32(0)
            g0 = jnp.int32(0)
        else:
            blk_ws = jnp.maximum(q_start - sliding_window + 1, 0)
            g0 = blk_ws // rows_g

        def needed(g, i):
            pi = g * pages_g + i
            ok = pi < num_pages
            if sliding_window is not None:
                ok &= pi >= blk_ws // page_size
            return ok

        _move_group(s, g0, 0, needed, start=True)

        rows_q = blk_q * group
        q_r = jnp.swapaxes(
            q_ref[...].reshape(blk_q, num_kv_heads, group, head_dim),
            0, 1).reshape(num_kv_heads, rows_q, head_dim)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (num_kv_heads, rows_q, 1), 1) // group

        m0 = jnp.full((num_kv_heads, rows_q, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((num_kv_heads, rows_q, 1), jnp.float32)
        acc0 = jnp.zeros((num_kv_heads, rows_q, v_dim), jnp.float32)

        def body(i, carry):
            g = g0 + i
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(i, 2)

            @pl.when(g + 1 < num_groups)
            def _prefetch():
                _move_group(s, g + 1, 1 - slot, needed, start=True)

            _move_group(s, g, slot, needed, start=False)
            k, v = _dequant(slot)
            row_pos = g * rows_g + jax.lax.broadcasted_iota(
                jnp.int32, (num_kv_heads, rows_g, 1), 1)
            v_valid = row_pos < kv_limit
            if sliding_window is not None:
                v_valid &= row_pos >= blk_ws
            v = jnp.where(v_valid, v, jnp.zeros_like(v))
            sc = jax.lax.dot_general(
                q_r, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            if logit_softcap is not None:
                sc = logit_softcap * jnp.tanh(sc / logit_softcap)
            kpos = g * rows_g + jax.lax.broadcasted_iota(
                jnp.int32, (num_kv_heads, rows_q, rows_g), 2)
            mask = kpos <= q_pos                  # causal + cached context
            if sliding_window is not None:
                mask &= kpos > q_pos - sliding_window
            sc = jnp.where(mask, sc, NEG_INF)
            m_cur = jnp.max(sc, axis=2, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            pr = jnp.exp(sc - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(pr, axis=2, keepdims=True)
            pv = jax.lax.dot_general(
                pr.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            acc_new = acc_prev * correction + pv
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(0, num_groups - g0, body,
                                      (m0, l0, acc0))
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = (acc / safe_l).reshape(num_kv_heads, blk_q, group, v_dim)
        o_ref[...] = jnp.swapaxes(out, 0, 1).reshape(
            blk_q, num_kv_heads * group, v_dim).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "blk_q",
                                             "pages_per_group",
                                             "sliding_window",
                                             "logit_softcap",
                                             "decode_rows", "v_lanes"))
def ragged_paged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                           kv_lens: jnp.ndarray, q_starts: jnp.ndarray,
                           q_lens: jnp.ndarray, meta: jnp.ndarray,
                           blk_seq: jnp.ndarray, scale: float,
                           interpret: bool | None = None,
                           blk_q: int | None = None,
                           pages_per_group: int | None = None,
                           k_scale: jnp.ndarray | None = None,
                           v_scale: jnp.ndarray | None = None,
                           sliding_window: int | None = None,
                           logit_softcap: float | None = None,
                           decode_rows: bool = True,
                           v_lanes: int | None = None) -> jnp.ndarray:
    """q: (T, Hq, D) flat mixed token stream; k_cache/v_cache: (num_blocks,
    page, Hkv, D) (a LATENT entry, MLA's absorbed form: ``v_cache=None``
    and ``v_lanes``, V the first ``v_lanes`` lanes of the K page as it
    landed, the result (T, Hq, v_lanes)); block_tables: (B, max_pages) per SEQUENCE; kv_lens /
    q_starts / q_lens: (B,) per-sequence descriptors (cached tokens
    INCLUDING this window, flat row of the sequence's first query, rows in
    this window); meta: (2,) int32 [num_decode_rows, num_decode_blocks];
    blk_seq: (T // blk_q,) int32 — the sequence a prefill block serves,
    -1 for decode-region and padding blocks. -> (T, Hq, D).

    Host layout contract (``ragged_block`` is the one source of blk_q):
    rows [0, num_decode) are decode sequences (row r == sequence r), the
    decode region pads to a blk_q multiple, every prefill chunk starts
    blk_q-aligned, and T % blk_q == 0.  Rows past a chunk's ``q_lens``
    and descriptor padding rows are UNSPECIFIED in the output (fully
    masked programs produce zeros; skipped padding blocks write nothing)
    — the engine's last-row gather never reads them.

    ``decode_rows=False`` (static) is the caller's word that ``meta`` is
    zero — a packed batched prefill — and builds the kernel without its
    decode part: half the kernel to trace, lower and compile for each
    rung of that route's ladder.
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        T, Hq, D = q.shape
        num_blocks, page_size, Hkv, _ = k_cache.shape
        max_pages = block_tables.shape[1]
        group = Hq // Hkv
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        blk = ragged_block(blk_q)
        if T % blk:
            raise ValueError(f"flat token count {T} is not a multiple of the "
                             f"ragged block {blk} (engine layout contract)")
        pages_g = pages_per_group or max(1, -(-TARGET_GROUP_ROWS // page_size))
        pages_g = min(pages_g, max_pages)
        # blk is a layout contract with the host packing — only pages_g may
        # shrink to fit VMEM (it only shortens the DMA pipeline).  If the
        # clamp wanted to shrink blk itself (many-query-head models whose
        # q/out blocks alone bust the budget), fail LOUDLY: silently running
        # over budget crashes Mosaic allocation with a much worse message.
        pages_g, blk_clamped = _clamp_to_vmem_budget(
            pages_g, blk, page_size, Hkv, D, k_cache.dtype.itemsize,
            Hq, q.dtype.itemsize, quantized=k_scale is not None,
            rows_per_dot=True, flat_page=Hkv == 1)
        if blk_clamped != blk:
            raise ValueError(
                f"ragged block {blk} needs more VMEM than the budget allows "
                f"for this model shape (Hq={Hq}, D={D}); pack the flat "
                f"stream at ragged_block_for's {blk_clamped} rows, which fit")

        quantized = k_scale is not None
        if (v_cache is None) != (v_lanes is not None) or (
                v_lanes and quantized):
            raise ValueError("a latent entry is v_cache=None with v_lanes "
                             "set, and has no int8 form in this kernel")
        Dv = v_lanes or D
        kernel = functools.partial(
            _ragged_kernel, scale=scale, page_size=page_size, pages_g=pages_g,
            num_kv_heads=Hkv, group=group, head_dim=D, blk_q=blk,
            sliding_window=sliding_window, logit_softcap=logit_softcap,
            decode_rows=decode_rows, v_lanes=v_lanes)
        base_kernel = kernel
        if quantized:
            def kernel(bt, kl, qs, ql, mt, bs_, q_ref, k_hbm, v_hbm, ks_hbm,
                       vs_hbm, o_ref, k_scr, v_scr, ks_scr, vs_scr, sems):
                return base_kernel(bt, kl, qs, ql, mt, bs_, q_ref, k_hbm,
                                   v_hbm, o_ref, k_scr, v_scr, sems,
                                   ks_hbm=ks_hbm, vs_hbm=vs_hbm,
                                   ks_scr=ks_scr, vs_scr=vs_scr)
        elif v_lanes:
            def kernel(bt, kl, qs, ql, mt, bs_, q_ref, k_hbm, o_ref, k_scr,
                       sems):
                return base_kernel(bt, kl, qs, ql, mt, bs_, q_ref, k_hbm,
                                   None, o_ref, k_scr, None, sems)

        # one KV head: a page is (page, D) (a bitcast of the cache)
        page_shape = (page_size, D) if Hkv == 1 else (page_size, Hkv, D)
        pages = [c.reshape(num_blocks, *page_shape)
                 for c in ((k_cache,) if v_lanes else (k_cache, v_cache))]
        # the caches stay in HBM
        in_specs = [pl.BlockSpec((blk, Hq, D),
                                 lambda p, bt, kl, qs, ql, mt, bs_: (p, 0, 0))
                    ] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pages)
        scratch = [pltpu.VMEM((2, pages_g, *page_shape), c.dtype)
                   for c in pages]
        scales = ()
        if quantized:
            in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
            scratch += [pltpu.VMEM((2, pages_g, page_size, SCALE_LANES),
                                   jnp.float32)] * 2
            scales = (k_scale, v_scale)
        scratch.append(pltpu.SemaphoreType.DMA((4 if quantized else 2,
                                                2, pages_g)))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(T // blk,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (blk, Hq, Dv), lambda p, bt, kl, qs, ql, mt, bs_: (p, 0, 0)),
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, Hq, Dv), q.dtype),
            compiler_params=compiler_params("arbitrary"),
            interpret=interpret,
            name=KERNEL_NAME,
        )(block_tables, kv_lens, q_starts, q_lens, meta, blk_seq,
          q, *pages, *scales)
