"""Pure-JAX reference attention (prefill + paged decode).

These are the semantics the Pallas kernels (tpuserve.ops.pallas_*) must match;
they also serve as the CPU path.  The reference repo delegates all of this to
the vLLM container it deploys (reference: kubernetes-single-node.yaml:14,
llm-d-deploy.yaml:140-193) — here paged attention is an in-repo op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpuserve.ops import scopes

NEG_INF = -1e30


def _softcap(scores, cap):
    """Gemma2 attention-score softcap: cap * tanh(s / cap); None = off.
    Applied after scaling, before masking (matches HF eager)."""
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)

# Sentinel slot id for padding tokens in write_kv_cache: far out of range for
# any realistic cache, so scatter mode="drop" discards the write.
PAD_SLOT = 2**30


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(..., Hkv, D) -> (..., Hkv*n_rep, D) grouped-query expansion."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      prompt_lens: jnp.ndarray, scale: float,
                      sliding_window: int | None = None,
                      logit_softcap: float | None = None) -> jnp.ndarray:
    """Causal self-attention over the prompt being prefetched.

    q: (B, T, Hq, D); k, v: (B, T, Hkv, D); prompt_lens: (B,) valid lengths.
    ``sliding_window``: Mistral-style — row p attends keys in (p - W, p].
    Returns (B, T, Hq, D) in q.dtype.  Softmax in float32.
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        B, T, Hq, D = q.shape
        n_rep = Hq // k.shape[2]
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
        scores = _softcap(scores, logit_softcap)
        pos = jnp.arange(T)
        causal = pos[None, :] <= pos[:, None]                      # (Tq, Tk)
        if sliding_window is not None:
            causal &= pos[None, :] > pos[:, None] - sliding_window
        valid = pos[None, :] < prompt_lens[:, None]                # (B, Tk)
        mask = causal[None, None, :, :] & valid[:, None, None, :]
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        return out.astype(q.dtype)


def _dequant_gathered(k, v, k_scale, v_scale, block_tables, B, S, Hkv,
                      dtype, scale_slices):
    """Dequantize gathered int8 pages (no-op when the cache is raw).

    Plain entries carry one scale per (token, kv head); MLA int8 entries
    carry per-slice scales over the channel axis (``scale_slices``, the
    latent/rope split) that expand back to channel granularity here.  One
    helper for both reference attention ops — the two call sites must
    never drift (round-5 review)."""
    if k_scale is None:
        return k, v
    if scale_slices is not None:
        n = len(scale_slices)
        ksc = expand_slice_scales(
            k_scale[block_tables].reshape(B, S, n), scale_slices,
            k.shape[-1])
        vsc = expand_slice_scales(
            v_scale[block_tables].reshape(B, S, n), scale_slices,
            v.shape[-1])
        return ((k.astype(jnp.float32) * ksc).astype(dtype),
                (v.astype(jnp.float32) * vsc).astype(dtype))

    def scales(cache):
        return unpad_scale_lanes(
            cache[block_tables].reshape(B, S, cache.shape[-1]), Hkv)
    return (dequantize_kv(k, scales(k_scale), dtype),
            dequantize_kv(v, scales(v_scale), dtype))


def paged_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                           seq_lens: jnp.ndarray, scale: float,
                           k_scale: jnp.ndarray | None = None,
                           v_scale: jnp.ndarray | None = None,
                           sliding_window: int | None = None,
                           logit_softcap: float | None = None,
                           scale_slices: tuple[int, ...] | None = None
                           ) -> jnp.ndarray:
    """Single-token decode attention against a paged KV cache.

    q: (B, Hq, D); k_cache/v_cache: (num_blocks, block_size, Hkv, D);
    block_tables: (B, max_blocks) int32 physical block ids;
    seq_lens: (B,) total tokens in cache per sequence (including current).
    ``k_scale``/``v_scale``: (num_blocks, block_size, groups*SCALE_LANES)
    lane-padded dequantization scales (:func:`pad_scale_lanes`) when the
    cache stores int8 — or, with ``scale_slices`` set
    (int8 MLA), (num_blocks, block_size, len(scale_slices)) per-slice
    scales over the channel axis.  ``sliding_window``: attend only
    the last W cached positions.  Returns (B, Hq, D).
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        B, Hq, D = q.shape
        _, block_size, Hkv, _ = k_cache.shape
        max_blocks = block_tables.shape[1]
        S = max_blocks * block_size
        # Gather pages: (B, max_blocks, block_size, Hkv, D) -> (B, S, Hkv, D)
        k = k_cache[block_tables].reshape(B, S, Hkv, D)
        v = v_cache[block_tables].reshape(B, S, Hkv, D)
        k, v = _dequant_gathered(k, v, k_scale, v_scale, block_tables, B, S,
                                 Hkv, q.dtype, scale_slices)
        n_rep = Hq // Hkv
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
        scores = jnp.einsum("bhd,bkhd->bhk", q, k, preferred_element_type=jnp.float32) * scale
        scores = _softcap(scores, logit_softcap)
        valid = jnp.arange(S)[None, :] < seq_lens[:, None]         # (B, S)
        if sliding_window is not None:
            valid &= (jnp.arange(S)[None, :]
                      >= seq_lens[:, None] - sliding_window)
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhk,bkhd->bhd", probs.astype(v.dtype), v)
        return out.astype(q.dtype)


def ragged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, row_block_tables: jnp.ndarray,
                     row_lens: jnp.ndarray, scale: float, *,
                     seg_size: int = 512,
                     k_scale: jnp.ndarray | None = None,
                     v_scale: jnp.ndarray | None = None,
                     sliding_window: int | None = None,
                     logit_softcap: float | None = None,
                     scale_slices: tuple[int, ...] | None = None
                     ) -> jnp.ndarray:
    """Flat-token ragged attention against the paged cache (reference).

    One query row per FLAT token — decode rows and prefill-chunk rows
    alike, no phase split and no (batch, length) padding grid: the mixed
    scheduler packs everything into one (T,) stream ("Ragged Paged
    Attention", PAPERS.md).  Every row's KV (including its own) must
    already be written to the cache; row ``t`` attends keys at sequence
    positions ``< row_lens[t]`` of its OWN sequence.

    q: (T, Hq, D); row_block_tables: (T, max_blocks) — each row carries
    its sequence's block table (callers gather ``block_tables[row_seq]``);
    row_lens: (T,) = the row's global position + 1.  Keys stream in
    ``seg_size`` page-table segments with an online softmax, so the
    transient is (T, Hq, seg) — the dense (T, Hq, S) form would be GBs at
    long context.  For a decode row this degenerates to exactly
    :func:`paged_decode_attention`'s math; for prefill-chunk rows to
    :func:`chunked_prefill_attention`'s.  Returns (T, Hq, D).
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        T, Hq, D = q.shape
        _, bs, Hkv, Dk = k_cache.shape
        mb = row_block_tables.shape[1]
        G = Hq // Hkv
        pg = max(1, seg_size // bs)                # pages per segment
        n_seg = -(-mb // pg)
        pad = n_seg * pg - mb
        bt = row_block_tables
        if pad:
            # padded columns index block 0 but their key positions are
            # >= mb*bs >= any row_lens, so the mask drops them
            bt = jnp.pad(bt, ((0, 0), (0, pad)))
        bt = bt.reshape(T, n_seg, pg).transpose(1, 0, 2)     # (n_seg, T, pg)

        q_r = (q.astype(jnp.float32) * scale).reshape(T, Hkv, G, D)

        def body(carry, bt_seg):
            o, m, l, c0 = carry
            R = pg * bs
            k = k_cache[bt_seg].reshape(T, R, Hkv, Dk)
            v = v_cache[bt_seg].reshape(T, R, Hkv, Dk)
            k, v = _dequant_gathered(k, v, k_scale, v_scale, bt_seg, T, R,
                                     Hkv, q.dtype, scale_slices)
            scores = jnp.einsum("thgd,tkhd->thgk", q_r, k,
                                preferred_element_type=jnp.float32)
            scores = scores.reshape(T, Hq, R)
            scores = _softcap(scores, logit_softcap)
            j = c0 * bs + jnp.arange(R)[None, :]             # key positions
            mask = j < row_lens[:, None]
            if sliding_window is not None:
                mask &= j >= row_lens[:, None] - sliding_window
            scores = jnp.where(mask[:, None, :], scores, NEG_INF)
            m_cur = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.where(mask[:, None, :],
                          jnp.exp(scores - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("thgk,tkhd->thgd",
                            p.reshape(T, Hkv, G, R).astype(v.dtype), v,
                            preferred_element_type=jnp.float32)
            o = o * alpha[..., None] + pv.reshape(T, Hq, Dk)
            return (o, m_new, l, c0 + pg), None

        o0 = jnp.zeros((T, Hq, Dk), jnp.float32)
        m0 = jnp.full((T, Hq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((T, Hq), jnp.float32)
        (o, _, l, _), _ = jax.lax.scan(body, (o0, m0, l0, jnp.int32(0)), bt)
        out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
        return out.astype(q.dtype)


def ragged_blocked_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                             v_cache: jnp.ndarray, blk_bt: jnp.ndarray,
                             row_lens: jnp.ndarray, blk: int, scale: float,
                             *, k_scale: jnp.ndarray | None = None,
                             v_scale: jnp.ndarray | None = None,
                             sliding_window: int | None = None,
                             logit_softcap: float | None = None,
                             scale_slices: tuple[int, ...] | None = None
                             ) -> jnp.ndarray:
    """Block-gather ragged attention: valid ONLY for rows whose ``blk``-row
    block belongs to a single sequence (the mixed layout's prefill-chunk
    blocks — engine._run_mixed aligns chunks to ``blk``).

    Same per-row semantics as :func:`ragged_attention`, but the KV gather
    happens once per BLOCK (``blk_bt``: (T/blk, max_blocks), each block's
    owning-sequence block-table row) instead of once per row — 1/blk the
    gather traffic, which dominates the pure-JAX mixed step.  Decode-region
    and padding blocks may carry a clamped/garbage table row: their output
    is finite but unspecified, and ``forward_ragged`` overlays the per-row
    dense result for decode rows (bit-identical to the decode trunk).
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        T, Hq, D = q.shape
        _, bs, Hkv, Dk = k_cache.shape
        nblk = T // blk
        S = blk_bt.shape[1] * bs
        G = Hq // Hkv
        k = k_cache[blk_bt].reshape(nblk, S, Hkv, Dk)
        v = v_cache[blk_bt].reshape(nblk, S, Hkv, Dk)
        k, v = _dequant_gathered(k, v, k_scale, v_scale, blk_bt, nblk, S,
                                 Hkv, q.dtype, scale_slices)
        q_r = q.reshape(nblk, blk, Hkv, G, D)
        scores = jnp.einsum("nbhgd,nkhd->nhgbk", q_r, k,
                            preferred_element_type=jnp.float32) * scale
        scores = _softcap(scores, logit_softcap)
        j = jnp.arange(S)[None, None, :]                  # key positions
        lens = row_lens.reshape(nblk, blk)[:, :, None]    # (nblk, blk, 1)
        mask = j < lens
        if sliding_window is not None:
            mask &= j >= lens - sliding_window
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("nhgbk,nkhd->nbhgd", probs.astype(v.dtype), v)
        return out.reshape(T, Hq, Dk).astype(q.dtype)


def chunked_prefill_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                              v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                              ctx_lens: jnp.ndarray, chunk_lens: jnp.ndarray,
                              scale: float, *, seg_size: int = 512,
                              k_scale: jnp.ndarray | None = None,
                              v_scale: jnp.ndarray | None = None,
                              sliding_window: int | None = None,
                              logit_softcap: float | None = None,
                              scale_slices: tuple[int, ...] | None = None
                              ) -> jnp.ndarray:
    """Attention for one prefill CHUNK against the paged cache.

    The chunk's K/V must already be written into the cache (so keys live at
    sequence positions ``ctx_lens .. ctx_lens+chunk_lens``).  Each chunk
    query attends to every cached token before it plus causally within the
    chunk.  Keys are processed in ``seg_size`` segments with a flash-style
    online softmax, so the transient score tensor is (B, Hq, C, seg_size)
    instead of (B, Hq, C, S) — at 32k context and a 2k chunk the dense form
    would be gigabytes per layer, defeating the point of chunking.

    q: (B, C, Hq, D) chunk queries; k_cache/v_cache: (num_blocks, block_size,
    Hkv, D); block_tables: (B, max_blocks); ctx_lens: (B,) tokens already in
    cache BEFORE this chunk; chunk_lens: (B,) valid tokens in this chunk.
    Returns (B, C, Hq, D).
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        B, C, Hq, D = q.shape
        _, block_size, Hkv, _ = k_cache.shape
        S = block_tables.shape[1] * block_size
        G = Hq // Hkv
        # K/V stay in cache dtype with Hkv heads until inside the scan body —
        # expanding to Hq heads / fp32 up front would build an n_rep x 2 larger
        # transient than the cache itself at long context.
        k = k_cache[block_tables].reshape(B, S, Hkv, D)
        v = v_cache[block_tables].reshape(B, S, Hkv, D)
        # reference/CPU path: dequantize the gathered window up front (the
        # Pallas kernel dequantizes per-segment in VMEM instead)
        k, v = _dequant_gathered(k, v, k_scale, v_scale, block_tables, B, S,
                                 Hkv, q.dtype, scale_slices)

        seg = min(seg_size, S)
        n_seg = -(-S // seg)
        pad = n_seg * seg - S
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = k.reshape(B, n_seg, seg, Hkv, D)
        v = v.reshape(B, n_seg, seg, Hkv, D)

        # grouped-query layout: (B, C, Hkv, G, D) so the einsum contracts per
        # kv-head without materializing repeated K/V
        q_r = (q.astype(jnp.float32) * scale).reshape(B, C, Hkv, G, D)
        qi = jnp.arange(C)[None, :, None]                    # query chunk index
        q_valid = qi < chunk_lens[:, None, None]             # (B, C, 1)

        def body(carry, seg_kv):
            o, m, l, s0 = carry
            ks, vs = seg_kv                                  # (B, seg, Hkv, D)
            scores = jnp.einsum("bqhgd,bkhd->bhgqk", q_r, ks,
                                preferred_element_type=jnp.float32)
            scores = scores.reshape(B, Hq, C, seg)
            scores = _softcap(scores, logit_softcap)
            j = s0 + jnp.arange(seg)[None, None, :]          # global key position
            mask = (j <= ctx_lens[:, None, None] + qi) & q_valid & (j < S)
            if sliding_window is not None:
                # query at global pos ctx+qi attends keys in (pos - W, pos]
                mask &= j > ctx_lens[:, None, None] + qi - sliding_window
            mask = mask[:, None, :, :]                       # (B, 1, C, seg)
            scores = jnp.where(mask, scores, NEG_INF)
            m_cur = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd",
                            p.reshape(B, Hkv, G, C, seg), vs,
                            preferred_element_type=jnp.float32)
            o = o * alpha[..., None] + pv.reshape(B, Hq, C, D)
            return (o, m_new, l, s0 + seg), None

        o0 = jnp.zeros((B, Hq, C, D), jnp.float32)
        m0 = jnp.full((B, Hq, C), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hq, C), jnp.float32)
        (o, m, l, _), _ = jax.lax.scan(
            body, (o0, m0, l0, jnp.int32(0)),
            (k.transpose(1, 0, 2, 3, 4), v.transpose(1, 0, 2, 3, 4)))
        out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)     # (B, C, Hq, D)


# --------------------------------------------------------------------------
# int8 KV quantization (per-token, per-kv-head scales)
#
# Decode is HBM-bandwidth-bound and at the headline shape KV reads rival
# weight reads (byte count from shapes; not measured on the current code):
# int8 storage halves the value bytes per step.  Scales are one f32 per
# (token, kv head), stored in a parallel paged array so a physical block
# stays a contiguous DMA unit.
#
# Scale-page layout: (num_blocks, block_size, groups * SCALE_LANES).  The
# Pallas kernels DMA one block's scales as a (block_size, lanes) slab, and
# Mosaic only slices a memref whose minor dimension is a whole number of
# 128-lane tiles — a minor dimension of Hkv (8) is refused at compile time.
# So each row carries its kv heads in the first lanes of a 128-lane group
# and zeros after them.  ``groups`` is the number of shards of the kv-head
# axis (tp; 1 unsharded): every shard owns one whole lane group, so the
# array shards over its minor axis exactly as the value pages shard over
# kv heads.  The padding is real HBM (512 B of scales per token per K or V
# next to Hkv*D int8 value bytes); a denser layout needs an in-kernel lane
# relayout Mosaic does not lower today.
# --------------------------------------------------------------------------

KV_QUANT_MAX = 127.0
SCALE_LANES = 128


def pad_scale_lanes(scales: jnp.ndarray, groups: int = 1) -> jnp.ndarray:
    """(..., Hkv) per-head scales -> (..., groups * SCALE_LANES): head h
    of shard g = h // (Hkv / groups) lands in lane g * SCALE_LANES + h %
    (Hkv / groups); the other lanes are zero."""
    hkv = scales.shape[-1]
    per = hkv // groups
    if per * groups != hkv or per > SCALE_LANES:
        raise ValueError(f"{hkv} kv heads do not split into {groups} lane "
                         f"groups of at most {SCALE_LANES}")
    s = scales.reshape(*scales.shape[:-1], groups, per)
    s = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, SCALE_LANES - per)])
    return s.reshape(*scales.shape[:-1], groups * SCALE_LANES)


def unpad_scale_lanes(padded: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """Inverse of :func:`pad_scale_lanes`: (..., groups * SCALE_LANES) ->
    (..., Hkv)."""
    groups = padded.shape[-1] // SCALE_LANES
    s = padded.reshape(*padded.shape[:-1], groups, SCALE_LANES)
    s = s[..., :num_kv_heads // groups]
    return s.reshape(*padded.shape[:-1], num_kv_heads)


def quantize_kv(x: jnp.ndarray):
    """(..., Hkv, D) -> (int8 values, float32 scales (..., Hkv)).

    Symmetric absmax over the head_dim axis: one scale per written vector
    per kv head, so dequantization is a broadcast multiply."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / KV_QUANT_MAX
    s = jnp.maximum(s, 1e-10)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -KV_QUANT_MAX, KV_QUANT_MAX).astype(jnp.int8)
    return q, s


def dequantize_kv(q: jnp.ndarray, scales: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv`; ``scales`` broadcasts over head_dim."""
    return (q.astype(jnp.float32) * scales[..., None].astype(jnp.float32)
            ).astype(dtype)


def write_kv_scales(scale_cache: jnp.ndarray, scales: jnp.ndarray,
                    slots: jnp.ndarray) -> jnp.ndarray:
    """Scatter per-token scale rows into the paged scale array
    (num_blocks, block_size, lanes) — ``scales`` already in the array's
    row layout; same PAD_SLOT drop semantics as :func:`write_kv_cache`."""
    nb, bs, lanes = scale_cache.shape
    flat = scale_cache.reshape(nb * bs, lanes)
    flat = flat.at[slots.reshape(-1)].set(
        scales.reshape(-1, lanes).astype(scale_cache.dtype), mode="drop")
    return flat.reshape(nb, bs, lanes)


def kv_stream_by_page(entry: dict, unit: int, attn_impl: str,
                      mesh=None) -> bool:
    """Whether a prefill stream goes into ``entry`` a page at a time
    (:func:`write_kv_entry` with ``aligned=True``), from what is static at
    trace time: the Pallas kernels are on and unsharded, the entry is
    plain (no ``ks``/``vs`` scale arrays: int8 pages quantize a row at a
    time; K and V pages, or MLA's latent pages alone), and ``unit`` — the
    rows between two places where the stream may start a prompt or end —
    is whole pages.  The trunks ask it for the write, the engine for its
    counter (``tpuserve_prefill_kv_tokens_paged_total``)."""
    return (attn_impl == "pallas" and mesh is None and "ks" not in entry
            and unit % entry["k"].shape[1] == 0)


def write_kv_entry(entry: dict, k: jnp.ndarray, v: jnp.ndarray,
                   slots: jnp.ndarray, aligned: bool = False,
                   head: int = 0) -> dict:
    """Write one layer's new K/V into its cache entry.

    An entry carrying ``ks``/``vs`` scale arrays stores int8: values are
    quantized on write and the scales scattered alongside.  Plain entries
    store in the cache dtype unchanged.  ONE switch point for every model
    trunk (prefill / chunk / verify / decode).

    ``aligned`` (static; :func:`kv_stream_by_page` decides it) is the
    word of a trunk that owns a page-aligned stream — the packed prefill
    and the prefill chunk: every ``block_size`` consecutive rows of
    ``slots`` are one cache page in order, or padding — and sends the rows
    out a page a copy (ops/pallas_kv_write.py) instead of a scatter index
    a row.  Decode's rows go one to a page; they, verify, draft and the
    (B, L) grid keep the scatter.  ``head`` (static): the stream's first
    ``head`` rows are a mixed step's decode region, one row a sequence
    (whole pages of rows, so what follows is page-aligned as a packed
    prefill is): those rows go by the scatter, the prompt chunks behind
    them by the page."""
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        if aligned:
            from tpuserve.ops.pallas_kv_write import paged_kv_write
            ck, cv = entry["k"], entry["v"]
            if head:
                ck = write_kv_cache(ck, k[:head], slots[:head])
                cv = write_kv_cache(cv, v[:head], slots[:head])
            if k.shape[0] > head:
                ck, cv = paged_kv_write(ck, cv, k[head:], v[head:],
                                        slots[head:])
            return {"k": ck, "v": cv}
        if "ks" in entry:
            qk, sk = quantize_kv(k)
            qv, sv = quantize_kv(v)
            groups = entry["ks"].shape[-1] // SCALE_LANES
            return {"k": write_kv_cache(entry["k"], qk, slots),
                    "v": write_kv_cache(entry["v"], qv, slots),
                    "ks": write_kv_scales(entry["ks"],
                                          pad_scale_lanes(sk, groups), slots),
                    "vs": write_kv_scales(entry["vs"],
                                          pad_scale_lanes(sv, groups), slots)}
        return {"k": write_kv_cache(entry["k"], k, slots),
                "v": write_kv_cache(entry["v"], v, slots)}


def pad_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x`` with zeros after its last axis up to ``width`` lanes (a latent
    page is whole lane tiles: ``ModelConfig.cache_head_dim``)."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def write_mla_entry(entry: dict, latent: jnp.ndarray,
                    slots: jnp.ndarray,
                    latent_split: int | None = None,
                    aligned: bool = False) -> dict:
    """Write MLA latent vectors into a k-only cache entry.

    MLA (DeepSeek) caches ONE (latent ⊕ roped-key) vector per token —
    the entry carries no "v" pages at all; the decode path reads the "k"
    pages as both K and V (models/transformer.py absorbed form).
    latent: (..., D) with no head axis; the cache stores it as a single
    kv head.

    int8 entries ("ks") quantize on write — with TWO absmax scales per
    token, one for the rmsnorm'd latent slice (``:latent_split``) and one
    for the roped-key slice (``latent_split:``).  The slices have
    unrelated dynamic ranges (rope channels carry raw key-projection
    magnitudes; the latent is rmsnorm'd), so a single shared scale lets a
    large rope channel crush latent precision (ADVICE r4).  The paired
    scale cache is (num_blocks, block_size, 2); readers expand it back to
    channel granularity via ``scale_slices`` (:func:`expand_slice_scales`).

    ``aligned`` (static; :func:`kv_stream_by_page` decides it): a
    page-aligned stream's rows go out a page a copy, as
    :func:`write_kv_entry`'s do.
    """
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        lat = latent[..., None, :]                     # add the 1-head axis
        lanes = entry["k"].shape[-1]        # the page's, zeros past the latent
        if "ks" in entry:
            if latent_split is None:
                raise ValueError("int8 MLA cache requires latent_split (the "
                                 "kv_lora_rank) for per-slice scales")
            q1, s1 = quantize_kv(lat[..., :latent_split])
            q2, s2 = quantize_kv(lat[..., latent_split:])
            q = pad_lanes(jnp.concatenate([q1, q2], axis=-1), lanes)
            s = jnp.concatenate([s1, s2], axis=-1)     # (..., 2): latent, rope
            return {"k": write_kv_cache(entry["k"], q, slots),
                    "ks": write_kv_scales(entry["ks"], s, slots)}
        if aligned:
            from tpuserve.ops.pallas_kv_write import paged_kv_write
            return {"k": paged_kv_write(entry["k"], None,
                                        pad_lanes(lat, lanes), None, slots)[0]}
        return {"k": write_kv_cache(entry["k"], pad_lanes(lat, lanes), slots)}


def expand_slice_scales(scales: jnp.ndarray, scale_slices: tuple[int, ...],
                        width: int | None = None) -> jnp.ndarray:
    """(..., n_slices) per-slice scales -> (..., 1, D) channel scales,
    D = sum(scale_slices), broadcastable against (..., Hkv=1, D) pages;
    ``width`` > D: the page's zero lanes past the slices, scale 0."""
    per_chan = jnp.concatenate(
        [jnp.broadcast_to(scales[..., i:i + 1],
                          (*scales.shape[:-1], w))
         for i, w in enumerate(scale_slices)], axis=-1)
    if width:
        per_chan = pad_lanes(per_chan, width)
    return per_chan[..., None, :]


def write_kv_cache(cache: jnp.ndarray, new: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """Scatter new K or V vectors into the paged cache.

    cache: (num_blocks, block_size, Hkv, D); new: (N, Hkv, D) or (B, T, Hkv, D);
    slots: flat slot ids (block*block_size + offset), same leading shape as
    ``new`` minus the trailing (Hkv, D).  Padding tokens must use
    ``PAD_SLOT`` (out of range, so the scatter drops them — negative indices
    would wrap in JAX and corrupt the cache).
    """
    num_blocks, block_size, Hkv, D = cache.shape
    flat = cache.reshape(num_blocks * block_size, Hkv, D)
    new = new.reshape(-1, Hkv, D).astype(cache.dtype)
    slots = slots.reshape(-1)
    flat = flat.at[slots].set(new, mode="drop")
    return flat.reshape(num_blocks, block_size, Hkv, D)
