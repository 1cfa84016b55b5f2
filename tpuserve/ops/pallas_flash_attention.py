"""Pallas TPU flash attention for prefill (causal, padded prompts).

Blockwise online-softmax attention: grid (batch, q_heads, q_blocks, k_blocks)
with fp32 running max / sum / accumulator in VMEM scratch persisted across the
k dimension (the innermost, "arbitrary" grid axis).  Inputs are laid out
(B, H, T, D) inside the kernel so each block's trailing two dims are
(block_len, head_dim) — the shape Mosaic can tile onto the 8x128 VPU lanes
and the MXU.  Matches ``tpuserve.ops.attention.prefill_attention`` semantics;
tested against it in interpret mode on CPU and compiled on real TPU (the
reference repo has no kernels to compare — it delegates attention to vLLM's
CUDA kernels, SURVEY.md §2.2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuserve.ops import scopes

#: what the kernel's custom call is called in a profiler trace (the HLO
#: instruction's name); the benchmark's trace readers match it
KERNEL_NAME = "_flash_prefill_attention"


NEG_INF = -1e30


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, blk_q, blk_k,
                  sliding_window=None, logit_softcap=None):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_k
    prompt_len = len_ref[b]

    # Causal block skip: this k block only matters if it starts at or before
    # the last query row of the q block, and inside the valid prompt — and,
    # under a sliding window, not entirely before the EARLIEST row's window.
    relevant = (k_start <= q_start + blk_q - 1) & (k_start < prompt_len)
    if sliding_window is not None:
        relevant &= k_start + blk_k > q_start - sliding_window + 1

    @pl.when(relevant)
    def _compute():
        # Stored-dtype (bf16) MXU inputs with fp32 accumulation: upcasting
        # before the dot would run the MXU at its slow fp32 rate for no
        # accuracy gain over fp32 accumulation.
        q = q_ref[0, 0, :, :]                              # (blk_q, D)
        k = k_ref[0, 0, :, :]                              # (blk_k, D)
        v = v_ref[0, 0, :, :]
        # Zero v rows past the prompt: out-of-bounds block tails are
        # unspecified memory (possibly NaN), and 0 * NaN would poison the
        # accumulator even though their probabilities are exactly 0.
        col_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_k, 1), 0)
        v = jnp.where(col_ids < prompt_len, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = (cols <= rows) & (cols < prompt_len)
        if sliding_window is not None:
            mask &= cols > rows - sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]                                   # (blk_q, 1)
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                              # (blk_q, blk_k)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)
        # p cast to V's stored dtype keeps the PV contraction on the fast
        # MXU path; probabilities are in [0, 1] where bf16 rounding is benign
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == num_k - 1)
    def _finalize():
        # Fully-masked rows (padding) have l == 0; emit zeros there.
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def flash_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            prompt_lens: jnp.ndarray, scale: float,
                            blk_q: int = 128, blk_k: int = 128,
                            interpret: bool | None = None,
                            sliding_window: int | None = None,
                            logit_softcap: float | None = None) -> jnp.ndarray:
    """q: (B, T, Hq, D); k/v: (B, T, Hkv, D); prompt_lens: (B,). -> (B, T, Hq, D).

    T is padded (bucketed) by the engine; query rows past prompt_lens still
    attend to the valid keys (same as the reference impl) — the engine only
    reads the row at prompt_len - 1, so their values are never consumed.

    ``blk_q`` / ``blk_k``: the block split, 128 rows a side in every cell;
    tests pin smaller shapes."""
    with jax.named_scope(scopes.ATTN_KERNEL):
        return _flash_prefill_attention(q, k, v, prompt_lens, scale=scale,
                                        blk_q=blk_q, blk_k=blk_k,
                                        interpret=interpret,
                                        sliding_window=sliding_window,
                                        logit_softcap=logit_softcap)


@functools.partial(jax.jit, static_argnames=("scale", "blk_q", "blk_k",
                                             "interpret", "sliding_window",
                                             "logit_softcap"))
def _flash_prefill_attention(q, k, v, prompt_lens, *, scale: float,
                             blk_q: int, blk_k: int,
                             interpret: bool | None,
                             sliding_window: int | None,
                             logit_softcap: float | None) -> jnp.ndarray:
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    blk_q = min(blk_q, T)
    blk_k = min(blk_k, T)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    grid = (B, Hq, pl.cdiv(T, blk_q), pl.cdiv(T, blk_k))

    # (B, T, H, D) -> (B, H, T, D): trailing block dims become (blk, D),
    # which Mosaic can tile; XLA fuses the transposes into neighbours.
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(_flash_kernel, scale=scale, blk_q=blk_q,
                               blk_k=blk_k, sliding_window=sliding_window,
                               logit_softcap=logit_softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, qi, ki, lens: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, qi, ki, lens: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, qi, ki, lens: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D), lambda b, h, qi, ki, lens: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(prompt_lens, qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
