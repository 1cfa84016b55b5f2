"""Pallas TPU paged window attention: a chunk of queries against the cache.

Serves the two cache-relative window paths that previously only had the
segmented einsum implementation (models/transformer.py `_chunk_trunk`):
chunked prefill of long prompts and the speculative-decode verify pass.
One grid program per (sequence, query block); the sequence's KV pages are
DMA'd from HBM into double-buffered VMEM scratch via the scalar-prefetched
block table — the same page-group pipeline as the paged decode kernel
(pallas_paged_attention.py) — with an online softmax over page groups and a
causal-within-window mask on top of the cached context.

Semantics match ``tpuserve.ops.attention.chunked_prefill_attention``;
verified against it in interpret mode on CPU.  The reference repo delegates
all attention to the CUDA kernels inside the vLLM image it deploys
(reference: kubernetes-single-node.yaml:14; SURVEY.md §2.2).
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
                                                 _clamp_to_vmem_budget,
                                                 _scale_rows,
                                                 compiler_params)

NEG_INF = -1e30


def _window_kernel(bt_ref, ctx_ref, chunk_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_scr, v_scr, sems, *, scale, page_size, pages_g,
                   num_kv_heads, group, head_dim, blk_q,
                   ks_hbm=None, vs_hbm=None, ks_scr=None, vs_scr=None,
                   sliding_window=None, logit_softcap=None):
    """``ks_hbm``/``vs_hbm`` present = int8 cache: pages DMA as int8 with
    per-page scale blocks and dequantize in VMEM (same scheme as the paged
    decode kernel).  ``sliding_window`` (static): each query attends only
    the previous W positions; pages entirely before the q block's
    earliest window are never DMA'd."""
    quantized = ks_hbm is not None
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ctx = ctx_ref[b]
    total = ctx + chunk_ref[b]                 # written keys in the cache
    q_start = ctx + qi * blk_q                 # global position of q row 0
    # Causal limit for this q block: its last row attends to keys
    # <= q_start + blk_q - 1; never beyond the written keys.
    kv_limit = jnp.minimum(total, q_start + blk_q)
    num_pages = pl.cdiv(kv_limit, page_size)
    num_groups = pl.cdiv(num_pages, pages_g)
    # Earliest key ANY row of this q block may attend (row 0's window
    # start); per-row windows are enforced by the score mask.
    if sliding_window is None:
        blk_ws = jnp.int32(0)
        g0 = jnp.int32(0)
    else:
        blk_ws = jnp.maximum(q_start - sliding_window + 1, 0)
        g0 = blk_ws // (pages_g * page_size)

    def _page_needed(g, j):
        """MUST be identical for start and wait or semaphores desync."""
        pi = g * pages_g + j
        needed = pi < num_pages
        if sliding_window is not None:
            needed &= pi >= blk_ws // page_size
        return needed

    def _copies(g, slot, j):
        page = bt_ref[b, g * pages_g + j]
        copies = [
            pltpu.make_async_copy(k_hbm.at[page], k_scr.at[slot, j],
                                  sems.at[0, slot, j]),
            pltpu.make_async_copy(v_hbm.at[page], v_scr.at[slot, j],
                                  sems.at[1, slot, j]),
        ]
        if quantized:
            copies += [
                pltpu.make_async_copy(ks_hbm.at[page], ks_scr.at[slot, j],
                                      sems.at[2, slot, j]),
                pltpu.make_async_copy(vs_hbm.at[page], vs_scr.at[slot, j],
                                      sems.at[3, slot, j]),
            ]
        return copies

    def start_group(g, slot):
        def copy_one(j, _):
            @pl.when(_page_needed(g, j))
            def _():
                for c in _copies(g, slot, j):
                    c.start()
            return 0
        jax.lax.fori_loop(0, pages_g, copy_one, 0)

    def wait_group(g, slot):
        def wait_one(j, _):
            @pl.when(_page_needed(g, j))
            def _():
                for c in _copies(g, slot, j):
                    c.wait()
            return 0
        jax.lax.fori_loop(0, pages_g, wait_one, 0)

    start_group(g0, 0)

    rows_g = pages_g * page_size
    rows_q = blk_q * group
    # (blk_q, Hq, D) -> (Hkv, blk_q*G, D): per-kv-head grouped layout so one
    # (blk_q*G, D) x (D, rows_g) contraction serves each kv head.  Row
    # ordering within a kv head is (chunk index, group member): r // G is
    # the chunk index.
    q_r = jnp.swapaxes(
        q_ref[0].reshape(blk_q, num_kv_heads, group, head_dim),
        0, 1).reshape(num_kv_heads, rows_q, head_dim)

    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (num_kv_heads, rows_q, 1), 1) // group

    m0 = jnp.full((num_kv_heads, rows_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((num_kv_heads, rows_q, 1), jnp.float32)
    acc0 = jnp.zeros((num_kv_heads, rows_q, head_dim), jnp.float32)

    def body(i, carry):
        g = g0 + i
        m_prev, l_prev, acc_prev = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(g + 1 < num_groups)
        def _prefetch():
            start_group(g + 1, 1 - slot)

        wait_group(g, slot)
        k = jnp.swapaxes(k_scr[slot].reshape(rows_g, num_kv_heads, head_dim),
                         0, 1)
        v = jnp.swapaxes(v_scr[slot].reshape(rows_g, num_kv_heads, head_dim),
                         0, 1)
        if quantized:
            k = dequantize_kv(k, _scale_rows(ks_scr[slot], num_kv_heads),
                              q_ref.dtype)
            v = dequantize_kv(v, _scale_rows(vs_scr[slot], num_kv_heads),
                              q_ref.dtype)
        # Zero V rows past THIS PROGRAM'S loaded range: pages beyond
        # kv_limit are never DMA'd (even when within the written keys —
        # early q blocks stop at their causal limit), so their scratch is
        # unspecified (possibly NaN) and 0 * NaN would poison the
        # accumulator even though those probabilities are 0.
        row_pos = g * rows_g + jax.lax.broadcasted_iota(
            jnp.int32, (num_kv_heads, rows_g, 1), 1)
        v_valid = row_pos < kv_limit
        if sliding_window is not None:
            v_valid &= row_pos >= blk_ws           # never-DMA'd pages
        v = jnp.where(v_valid, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(q_r, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        kpos = g * rows_g + jax.lax.broadcasted_iota(
            jnp.int32, (num_kv_heads, rows_q, rows_g), 2)
        mask = kpos <= q_pos                       # causal + context
        if sliding_window is not None:
            mask &= kpos > q_pos - sliding_window  # per-row window
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_new = acc_prev * correction + pv
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_groups - g0, body, (m0, l0, acc0))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = acc / safe_l                            # (Hkv, blk_q*G, D)
    out = out.reshape(num_kv_heads, blk_q, group, head_dim)
    o_ref[0] = jnp.swapaxes(out, 0, 1).reshape(
        blk_q, num_kv_heads * group, head_dim).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "blk_q",
                                             "pages_per_group",
                                             "sliding_window",
                                             "logit_softcap"))
def paged_window_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                           ctx_lens: jnp.ndarray, chunk_lens: jnp.ndarray,
                           scale: float, interpret: bool | None = None,
                           blk_q: int = 128,
                           pages_per_group: int | None = None,
                           k_scale: jnp.ndarray | None = None,
                           v_scale: jnp.ndarray | None = None,
                           sliding_window: int | None = None,
                           logit_softcap: float | None = None) -> jnp.ndarray:
    """q: (B, C, Hq, D) window queries; k_cache/v_cache: (num_blocks, page,
    Hkv, D) with the window's KV already written; block_tables: (B,
    max_pages) int32; ctx_lens/chunk_lens: (B,). -> (B, C, Hq, D).

    Query row i of sequence b sits at global position ``ctx_lens[b] + i``
    and attends causally to every key at or before it.  Rows past
    ``chunk_lens[b]`` are UNSPECIFIED: their q_pos >= kv_limit, so the
    causal mask admits never-DMA'd scratch rows and the result can be
    garbage (only the fully-masked case is guarded to zero).  The engine
    never reads them; a caller that needs deterministic padding rows must
    mask on ``i < chunk_lens[b]`` itself.
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        B, C, Hq, D = q.shape
        num_blocks, page_size, Hkv, _ = k_cache.shape
        max_pages = block_tables.shape[1]
        group = Hq // Hkv
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        blk_q = min(blk_q, C)
        pages_g = pages_per_group or max(1, -(-TARGET_GROUP_ROWS // page_size))
        pages_g = min(pages_g, max_pages)
        # Same VMEM clamp as the decode kernel, with the whole q block in one
        # contraction: the (Hkv, blk_q*G, rows_g) f32 score tiles are the
        # largest term here, so many-q-head models get a shorter page group
        # (then a smaller q block) by rule.
        pages_g, blk_q = _clamp_to_vmem_budget(
            pages_g, blk_q, page_size, Hkv, D, k_cache.dtype.itemsize,
            Hq, q.dtype.itemsize, quantized=k_scale is not None,
            rows_per_dot=True)

        quantized = k_scale is not None
        kernel = functools.partial(
            _window_kernel, scale=scale, page_size=page_size, pages_g=pages_g,
            num_kv_heads=Hkv, group=group, head_dim=D, blk_q=blk_q,
            sliding_window=sliding_window, logit_softcap=logit_softcap)
        if quantized:
            base_kernel = kernel

            def kernel(bt, cx, ck, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
                       k_scr, v_scr, ks_scr, vs_scr, sems):
                return base_kernel(bt, cx, ck, q_ref, k_hbm, v_hbm, o_ref,
                                   k_scr, v_scr, sems, ks_hbm=ks_hbm,
                                   vs_hbm=vs_hbm, ks_scr=ks_scr, vs_scr=vs_scr)

        in_specs = [
            pl.BlockSpec((1, blk_q, Hq, D),
                         lambda b, qi, bt, cx, ck: (b, qi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # k_cache stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # v_cache stays in HBM
        ]
        scratch = [
            pltpu.VMEM((2, pages_g, page_size, Hkv, D), k_cache.dtype),
            pltpu.VMEM((2, pages_g, page_size, Hkv, D), v_cache.dtype),
        ]
        scales = ()
        if quantized:
            in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
            scratch += [pltpu.VMEM((2, pages_g, page_size, SCALE_LANES),
                                   jnp.float32)] * 2
            scales = (k_scale, v_scale)
        scratch.append(pltpu.SemaphoreType.DMA((4 if quantized else 2,
                                                2, pages_g)))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, pl.cdiv(C, blk_q)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, blk_q, Hq, D),
                                   lambda b, qi, bt, cx, ck: (b, qi, 0, 0)),
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=compiler_params("arbitrary", "arbitrary"),
            interpret=interpret,
        )(block_tables, ctx_lens, chunk_lens, q, k_cache, v_cache, *scales)
