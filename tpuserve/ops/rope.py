"""Rotary position embeddings (GPT-NeoX split-half convention, as used by
Llama/Qwen/Phi-3 checkpoints)."""

from __future__ import annotations

import jax.numpy as jnp


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN attention-magnitude correction (HF yarn_get_mscale)."""
    if scale <= 1:
        return 1.0
    import math
    return 0.1 * mscale * math.log(scale) + 1.0


def rope_freqs(positions: jnp.ndarray, head_dim: int, theta: float,
               rotary_dim: int | None = None,
               llama3_scaling: tuple | None = None,
               yarn_scaling: tuple | None = None
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for ``positions``.

    positions: int array (...,) — returns cos/sin of shape (..., rotary_dim//2),
    computed in float32.

    ``llama3_scaling``: (factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings) — the Llama-3.1 frequency transform:
    wavelengths longer than original_ctx/low_factor are slowed by
    ``factor``, shorter than original_ctx/high_factor are untouched, and
    the band between interpolates smoothly (matches HF's
    _compute_llama3_parameters).
    """
    rotary_dim = rotary_dim or head_dim
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    attention_factor = 1.0
    if yarn_scaling is not None:
        # YaRN (DeepSeek long context; mirrors HF _compute_yarn_parameters):
        # high-frequency dims extrapolate (unscaled), low-frequency dims
        # interpolate (positions effectively divided by ``factor``), with a
        # linear ramp between the beta_fast/beta_slow correction bounds.
        # cos/sin are scaled by the attention factor
        # mscale(factor, mscale) / mscale(factor, mscale_all_dim) — 1.0 for
        # every DeepSeek config (mscale == mscale_all_dim); the remaining
        # mscale**2 lives in ModelConfig.attn_scale.
        import math
        factor, beta_fast, beta_slow, mscale, mscale_all_dim, orig_max = \
            yarn_scaling

        def corr_dim(n_rot):
            return (rotary_dim
                    * math.log(orig_max / (n_rot * 2 * math.pi))
                    ) / (2 * math.log(theta))
        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), rotary_dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
            / (high - low), 0.0, 1.0)
        extrapolation_factor = 1.0 - ramp
        inv_freq = ((inv_freq / factor) * ramp
                    + inv_freq * extrapolation_factor)
        if mscale and mscale_all_dim:
            attention_factor = (yarn_mscale(factor, mscale)
                                / yarn_mscale(factor, mscale_all_dim))
        else:
            attention_factor = yarn_mscale(factor)
    if llama3_scaling is not None:
        factor, low_f, high_f, orig_ctx = llama3_scaling
        wavelen = 2.0 * jnp.pi / inv_freq
        smooth = (orig_ctx / wavelen - low_f) / (high_f - low_f)
        interp = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        inv_freq = jnp.where(
            wavelen > orig_ctx / low_f, inv_freq / factor,
            jnp.where(wavelen < orig_ctx / high_f, inv_freq, interp))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    if attention_factor != 1.0:
        return (jnp.cos(angles) * attention_factor,
                jnp.sin(angles) * attention_factor)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Apply rotary embedding.

    x: (..., num_heads, head_dim); cos/sin: (..., rotary_dim//2) broadcast over
    the heads axis. The first ``rotary_dim`` features are rotated as two halves
    (NeoX style); any remainder passes through (partial rotary, e.g. Phi).
    """
    rotary_half = cos.shape[-1]
    dtype = x.dtype
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1 = x[..., :rotary_half].astype(jnp.float32)
    x2 = x[..., rotary_half:2 * rotary_half].astype(jnp.float32)
    rot1 = x1 * cos - x2 * sin
    rot2 = x2 * cos + x1 * sin
    # each half rounded where it is made, not after the join: the chip's
    # compiler moves the rounding there anyway, and an operation it makes
    # itself inside a called function (a trunk's layer body,
    # models/transformer.py) is named after the call alone, under no part
    out = jnp.concatenate([rot1.astype(dtype), rot2.astype(dtype)], axis=-1)
    if 2 * rotary_half < x.shape[-1]:
        out = jnp.concatenate([out, x[..., 2 * rotary_half:]], axis=-1)
    return out
