"""Mamba-2 state-space mixing in ``jax.numpy``: the chunked scan that
prefill runs, the short causal convolution in front of it, and the grouped
gated norm behind it.  (The decode-time state update is a Pallas kernel,
ops/pallas_ssm_update.py.)

The recurrence, a head ``h`` with scalar ``A_h < 0`` and a state ``S`` of
shape ``(P, N)``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

is evaluated a chunk of ``Q`` rows at a time (the published "SSD" form,
``mamba_chunk_size``): inside a chunk the outputs are two matrix products
against the chunk's own ``(Q, Q)`` decay-masked ``C B^T``; across chunks
only the ``(P, N)`` state is carried.  Every exponent is a difference of
cumulative sums taken the causal way round, so it is never positive.

Rows lie on ONE flat axis ``T`` (a ``(B, L)`` batch is its reshape):
``chunk_seq[c]`` names the sequence chunk ``c`` belongs to (-1: padding),
a chunk whose predecessor belongs to another sequence restarts from that
sequence's ``s0``, and a padding row carries ``dt = 0`` — it neither
decays nor feeds the state, which is what lets a sequence end anywhere
inside its last chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpuserve.ops import scopes


def ssd_chunk_scan(x, dt, a, bm, cm, s0, chunk_seq, *, chunk: int):
    """x (T, H, P); dt (T, H) f32, zero on padding rows; a (H,) f32,
    negative; bm/cm (T, G, N), head ``h`` reading group ``h // (H / G)``;
    s0 (n_seq, H, P, N) f32, each sequence's state before its first row;
    chunk_seq (T // chunk,) int32.  Returns (y (T, H, P) f32 — without the
    ``D x`` skip —, finals (n_seq, H, P, N) f32: each sequence's state
    after its last row, ``s0`` where it has none)."""
    with jax.named_scope(scopes.SSM_SCAN):
        T, H, P = x.shape
        G, N = bm.shape[1:]
        Q, nc, hg = chunk, T // chunk, H // G
        f32 = jnp.float32

        def chunks(v, *tail):       # widened a chunk at a time, in the body
            return v.reshape(nc, Q, *tail)

        xs = chunks(x, G, hg, P)
        dts = chunks(dt, G, hg)
        bs, cs_ = chunks(bm, G, N), chunks(cm, G, N)
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 chunk_seq[1:] != chunk_seq[:-1]])
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        a_g = a.astype(f32).reshape(G, hg)
        n_seq = s0.shape[0]

        def one(carry, inp):
            state, finals = carry                       # (G, hg, P, N)
            xc, dtc, bc, cc, seq, is_first = inp
            xc, bc, cc = xc.astype(f32), bc.astype(f32), cc.astype(f32)
            prev = jnp.where(is_first,
                             s0[jnp.clip(seq, 0, n_seq - 1)].reshape(G, hg, P, N),
                             state)
            cum = jnp.cumsum(dtc * a_g, axis=0)                     # (Q, G, hg)
            # decay from row j to row i >= j, masked BEFORE the exponent
            seg = cum[:, None] - cum[None, :]                       # (Qi, Qj, ..)
            lmat = jnp.exp(jnp.where(causal[:, :, None, None], seg, -jnp.inf))
            cb = jnp.einsum("ign,jgn->ijg", cc, bc)                 # (Qi, Qj, G)
            xdt = xc * dtc[..., None]                               # (Q, G, hg, P)
            y = jnp.einsum("ijgh,jghp->ighp", lmat * cb[..., None], xdt)
            y = y + jnp.exp(cum)[..., None] * jnp.einsum(
                "ign,ghpn->ighp", cc, prev)
            to_end = jnp.exp(cum[-1][None] - cum)                   # (Q, G, hg)
            new = (jnp.exp(cum[-1])[..., None, None] * prev
                   + jnp.einsum("jgh,jghp,jgn->ghpn", to_end, xdt, bc))
            finals = finals.at[jnp.where(seq >= 0, seq, n_seq)].set(
                new.reshape(H, P, N), mode="drop")
            return (new, finals), y

        init = (jnp.zeros((G, hg, P, N), f32), s0.astype(f32))
        (_, finals), ys = jax.lax.scan(
            one, init, (xs, dts, bs, cs_, chunk_seq, first))
        return ys.reshape(T, H, P), finals


def causal_conv(xbc, tail, kernel, bias, positions=None):
    """Depthwise causal convolution of width ``W`` along axis 1.  xbc
    (B, L, C); tail (B, W - 1, C): the rows that came before row 0 (zeros
    at a sequence's start); kernel (W, C), ``kernel[W - 1]`` weighing the
    row itself; bias (C,) or None.  ``positions`` (B, L): several
    sequences lie end to end on axis 1, each row at this position of its
    own, and a tap that would reach back past position 0 reads zero.
    Returns ((B, L, C) in f32, and the rows ``[tail ++ xbc]`` for the
    caller to cut the next tail from)."""
    with jax.named_scope(scopes.SSM_CONV):
        W = kernel.shape[0]
        rows = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        L = xbc.shape[1]
        k = kernel.astype(jnp.float32)

        def tap(i):
            rows_i = rows[:, i:i + L].astype(jnp.float32)
            if positions is None:
                return rows_i
            return jnp.where((positions >= W - 1 - i)[..., None], rows_i, 0.0)

        out = sum(tap(i) * k[i] for i in range(W))
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out, rows


def next_tail(rows, lens, width: int):
    """The ``width - 1`` rows before position ``lens`` of ``rows`` =
    ``[tail ++ window]`` (B, W - 1 + L, C): the convolution's memory after
    a window of ``lens`` (B,) valid rows."""
    with jax.named_scope(scopes.SSM_CONV):
        idx = lens[:, None] + jnp.arange(width - 1)[None, :]       # (B, W-1)
        return jnp.take_along_axis(rows, idx[:, :, None], axis=1)


def gated_group_norm(y, z, scale, eps: float, groups: int,
                     norm_before_gate: bool):
    """``RMSNorm(y * silu(z))`` normalised inside each of ``groups`` equal
    slices of the last axis (``norm_before_gate``: ``RMSNorm(y) *
    silu(z)``).  f32 in, f32 out."""
    gate = jax.nn.silu(z.astype(jnp.float32))
    if not norm_before_gate:
        y = y * gate
    g = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    y = g.reshape(y.shape) * scale.astype(jnp.float32)
    return y * gate if norm_before_gate else y
