"""Gated delta-rule linear attention in ``jax.numpy``: the chunked scan that
prefill runs, in the mixer's two forms -- ONE decay a head (Olmo-Hybrid:
this docstring, ``gated_delta_step``, ``gated_delta_chunk_scan``) and a
decay for every KEY CHANNEL (Kimi Delta Attention: the second half of the
file, ``kda_step``, ``kda_chunk_scan``).  (The decode-time state update is
a Pallas kernel, ops/pallas_gdn_update.py and ops/pallas_kda_update.py; the
short causal convolution in front of both is ops/ssm.py's.)

The recurrence (arXiv:2412.06464), a head with keys of size ``dk``, values
of size ``dv`` and a state ``S`` of shape ``(dk, dv)``::

    S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T      o_t = S_t^T q_t

with a decay ``a_t = exp(g_t)`` in (0, 1] and a step size ``b_t`` in [0, 2]:
the state is multiplied by ``a (I - b k k^T)``, a reflection along ``k`` at
``b = 2`` (arXiv:2411.12537), so unlike Mamba-2's elementwise decay a row
needs ``S^T k`` BEFORE it can write ``S``.  Prefill evaluates it a chunk of
``Q`` rows at a time, the WY form of the paper's §3.  With ``c_i = exp(sum
of g up to row i)`` inside a chunk that starts from ``S_0``, each row
writes ``k_i u_i^T`` for a pseudo-value

    u_i = b_i (v_i - c_i S_0^T k_i - sum_{j<i} (c_i / c_j) (k_i . k_j) u_j)

that is ``(I + A) U = diag(b) (V - diag(c) K S_0)`` with ``A = tril(diag(b)
(K K^T . c_i / c_j), -1)``.  ``(I + A)^-1`` does not depend on the state:
it is taken for a group of chunks at once by forward substitution (one row
of the inverse a step, ``Q`` steps; the products of powers of ``A`` that a
doubling scheme would form cancel badly at ``b`` near 2), and with it
``U = U0 - W S_0``.  Across chunks a ``lax.scan`` carries ``S`` alone::

    O   = diag(c) Q S_0 + tril(Q K^T . c_i / c_j) U
    S_Q = c_Q S_0 + (K . c_Q / c_j)^T U

Every exponent is a difference of cumulative sums taken the causal way
round, so it is never positive.  Rows lie on ONE flat axis ``T``, as in
ops/ssm.py: ``chunk_seq[c]`` names the sequence chunk ``c`` belongs to
(-1: padding), a chunk whose predecessor belongs to another sequence
restarts from that sequence's ``s0``, and a padding row carries ``g = 0``
and ``b = 0`` -- it neither decays nor writes the state, which is what lets
a sequence end anywhere inside its last chunk.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from tpuserve.ops import scopes

HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(state, q, k, v, g, beta):
    """One row of the recurrence on gathered states: state (B, H, dk, dv)
    f32; q, k (B, H, dk); v (B, H, dv); g, beta (B, H).  Returns (o (B, H,
    dv), the new states).  The decode trunk's ``jax.numpy`` form and what
    the kernel and the chunked scan are tested against."""
    s = state * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                          precision=HIGHEST))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=HIGHEST), s


def _unit_lower_inverse(a, eye):
    """``(I + A)^-1`` for strictly lower ``a`` (G, H, Q, Q), by forward
    substitution: row i from the rows above it, ``e_i - A[i] inv`` (an
    elementwise product and a sum: exact float32 on the chip, where a
    matrix product would round its inputs)."""
    def row(i, inv):
        ai = jax.lax.dynamic_index_in_dim(a, i, axis=2, keepdims=False)
        new = eye[i] - jnp.sum(ai[..., :, None] * inv, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, axis=2)

    return jax.lax.fori_loop(1, a.shape[-1], row,
                             jnp.broadcast_to(eye, a.shape))


#: chunks whose state-free part (the triangular solve, the products
#: against the chunk's own rows) is formed at once: the solve's ``Q``
#: steps then run once a GROUP, not once a chunk, while what is held
#: beside the stream stays a group's size (at 8,192 rows of 30 heads, all
#: 128 chunks at once held 2.8 GB and the cell's prefill did not fit the
#: chip beside its weights, state and pages)
GROUP_CHUNKS = 16


def gated_delta_chunk_scan(x, g, beta, s0, chunk_seq, *, chunk: int, split,
                           out_dtype=jnp.float32):
    """x (T, C): the rows' q, k and v as the caller has them (a linear
    layer's convolved channels), from which ``split`` makes a group of
    rows' q, k (rows, H, dk), q scaled and both normalised, and v (rows,
    H, dv) -- called a group at a time, so that no stream-long copy of
    them is ever held; g (T, H) f32 <= 0, zero on padding rows; beta (T,
    H) f32, zero on padding rows; s0 (n_seq, H, dk, dv) f32, each
    sequence's state before its first row; chunk_seq (T // chunk,) int32.
    Returns (o (T, H, dv) in ``out_dtype`` (computed in f32, as everything
    here), finals (n_seq, H, dk, dv) f32: each sequence's state after its
    last row, ``s0`` where it has none)."""
    with jax.named_scope(scopes.SSM_SCAN):
        T = x.shape[0]
        H, dk, dv = s0.shape[1:]
        Q, nc = chunk, T // chunk
        G = math.gcd(nc, GROUP_CHUNKS)
        f32 = jnp.float32
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        eye = jnp.eye(Q, dtype=f32)
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 chunk_seq[1:] != chunk_seq[:-1]])
        n_seq = s0.shape[0]

        def groups(x, *tail):           # (T, ..) -> (groups, G Q, ..)
            return x.reshape(nc // G, G * Q, *tail)

        def chunks(x, *tail):           # (G Q, H, ..) -> (G, H, Q, ..)
            return jnp.swapaxes(x.astype(f32).reshape(G, Q, H, *tail), 1, 2)

        def one_chunk(carry, inp):
            state, finals = carry                               # (H, dk, dv)
            u0c, wc, qkc, qc, kc, ce, seq, is_first = inp
            prev = jnp.where(is_first, s0[jnp.clip(seq, 0, n_seq - 1)],
                             state)
            u = u0c - jnp.einsum("hik,hkv->hiv", wc, prev, precision=HIGHEST)
            o = (jnp.einsum("hik,hkv->hiv", qc, prev, precision=HIGHEST)
                 + jnp.einsum("hij,hjv->hiv", qkc, u, precision=HIGHEST))
            new = ce[:, None, None] * prev + jnp.einsum(
                "hjk,hjv->hkv", kc, u, precision=HIGHEST)
            finals = finals.at[jnp.where(seq >= 0, seq, n_seq)].set(
                new, mode="drop")
            return (new, finals), o

        def one_group(carry, inp):
            xg, gg, bg, seqs, firsts = inp
            qg, kg, vg = split(xg)
            qs, ks, vs = chunks(qg, dk), chunks(kg, dk), chunks(vg, dv)
            gs, bs = chunks(gg), chunks(bg)                     # (G, H, Q)
            cum = jnp.cumsum(gs, axis=-1)
            # decay from row j to row i >= j, masked BEFORE the exponent
            decay = jnp.exp(jnp.where(causal, cum[..., :, None]
                                      - cum[..., None, :], -jnp.inf))
            kk = jnp.einsum("chik,chjk->chij", ks, ks, precision=HIGHEST)
            a = jnp.where(jnp.tril(causal, -1),
                          bs[..., :, None] * kk * decay, 0.0)

            inv = _unit_lower_inverse(a, eye)
            c = jnp.exp(cum)                                    # (G, H, Q)
            u0 = jnp.einsum("chij,chjv->chiv", inv, bs[..., None] * vs,
                            precision=HIGHEST)
            w = jnp.einsum("chij,chjk->chik", inv, (bs * c)[..., None] * ks,
                           precision=HIGHEST)
            qk = jnp.einsum("chik,chjk->chij", qs, ks,
                            precision=HIGHEST) * decay
            q_in = c[..., None] * qs             # a row's read of S_0
            k_end = jnp.exp(cum[..., -1:] - cum)[..., None] * ks
            carry, os_ = jax.lax.scan(
                one_chunk, carry,
                (u0, w, qk, q_in, k_end, c[..., -1], seqs, firsts))
            return carry, jnp.swapaxes(os_, 1, 2).reshape(
                G * Q, H * dv).astype(out_dtype)

        init = (jnp.zeros((H, dk, dv), f32), s0.astype(f32))
        (_, finals), os_ = jax.lax.scan(
            one_group, init,
            (groups(x, x.shape[-1]), groups(g, H), groups(beta, H),
             chunk_seq.reshape(nc // G, G),
             first.reshape(nc // G, G)))
        return os_.reshape(T, H, dv), finals


# --------------------------------------------------------------------------
# Kimi Delta Attention (arXiv:2510.26692): the same rule with a decay for
# every KEY CHANNEL.  ``a_t = exp(g_t)`` is a vector over the state's ROWS,
#
#     S' = diag(a_t) S_{t-1};  u = b_t (v_t - S'^T k_t);
#     S_t = S' + k_t u_t^T;    o_t = S_t^T q_t
#
# so inside a chunk, with ``G_i`` the running sum of ``g`` (a vector a
# row), the scalar ``c_i / c_j`` above becomes ``exp(G_i - G_j)`` INSIDE
# the contraction over key channels:
#
#     A[i, j] = b_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])       j < i
#
# and likewise for ``q_i . k_j``.  That is a matrix product only once the
# exponent is split about a reference row ``n``, ``(k_i exp(G_i - G_n)) .
# (k_j exp(G_n - G_j))``, and the second factor's exponent is POSITIVE for
# ``j > n``.  The gate's lower bound is what makes that safe: with ``g >=
# -5`` the exponent over 15 rows is at most 75, under ln(float32 max) =
# 88.7; over a chunk of 64 it would reach 315 and overflow.  So the chunk
# keeps its size (the triangular solve, the state's carry and the products
# against ``S_0`` are the scalar form's, with every exponent a causal
# difference and so non-positive) and only the two PAIRWISE matrices are
# formed a SUB-BLOCK of at most ``SUB_BLOCK`` = 16 rows at a time: the rows
# of sub-block ``I`` are referred to its first row ``n_I``, against every
# column up to the sub-block's last -- an earlier column's exponent ``G_n -
# G_j`` is non-positive, a column inside the sub-block is at most 15 rows
# past ``n_I``, and a later column is masked BEFORE the exponent.  One
# product of ``(16, dk) x (dk, Q)`` a sub-block: the contraction's size is
# the whole chunk's, and no pair is formed element by element (which would
# be ``Q x 16 x dk`` exponentials a chunk a head on the vector unit: 0.5 G
# a layer at 8,192 rows, where this is four times the scalar form's).

#: rows of a chunk whose pairwise decays share one reference row: 15 rows
#: at the bound are an exponent of 75 < 88.7 (one of 32 would be 155)
SUB_BLOCK = 16


def kda_step(state, q, k, v, g, beta):
    """One row of the channel-gated recurrence on gathered states: state
    (B, H, dk, dv) f32; q, k (B, H, dk); v (B, H, dv); g (B, H, dk) the
    log of the decay, a value a key channel; beta (B, H).  Returns (o (B,
    H, dv), the new states).  What the kernel (ops/pallas_kda_update.py)
    and :func:`kda_chunk_scan` are tested against; with every channel of
    ``g`` equal it is :func:`gated_delta_step`."""
    s = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                          precision=HIGHEST))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=HIGHEST), s


def kda_chunk_scan(x, g, beta, s0, chunk_seq, *, chunk: int, split,
                   out_dtype=jnp.float32, sub_block: int = SUB_BLOCK):
    """:func:`gated_delta_chunk_scan` with ``g`` (T, H, dk) f32 <= 0, a
    value a key channel (zero on padding rows), bounded below so that
    ``sub_block - 1`` rows of it sum to more than -88; everything else as
    there."""
    with jax.named_scope(scopes.SSM_SCAN):
        T = x.shape[0]
        H, dk, dv = s0.shape[1:]
        Q, nc = chunk, T // chunk
        sb = math.gcd(Q, sub_block)
        nb = Q // sb
        G = math.gcd(nc, GROUP_CHUNKS)
        f32 = jnp.float32
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        eye = jnp.eye(Q, dtype=f32)
        # column j may stand against the rows of sub-block I: j is no
        # later than the sub-block's last row
        upto = (jnp.arange(Q)[None, :]
                < (jnp.arange(nb)[:, None] + 1) * sb)            # (nb, Q)
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 chunk_seq[1:] != chunk_seq[:-1]])
        n_seq = s0.shape[0]

        def groups(x, *tail):           # (T, ..) -> (groups, G Q, ..)
            return x.reshape(nc // G, G * Q, *tail)

        def chunks(x, *tail):           # (G Q, H, ..) -> (G, H, Q, ..)
            return jnp.swapaxes(x.astype(f32).reshape(G, Q, H, *tail), 1, 2)

        def pairs_against(ks, cum):
            """``rows -> sum_d rows_i[d] ks_j[d] exp(cum_i[d] - cum_j[d])``
            for ``j <= i`` (zero elsewhere), (G, H, Q, Q): a sub-block of
            rows a product, the keys' decayed copies formed once."""
            ref = cum[..., ::sb, :]                          # (G, H, nb, dk)
            down = jnp.exp(cum - jnp.repeat(ref, sb, axis=-2))
            right = ks[..., None, :, :] * jnp.exp(jnp.where(
                upto[..., None],
                ref[..., :, None, :] - cum[..., None, :, :], -jnp.inf))

            def pairs(rows):
                p = jnp.einsum(
                    "chbik,chbjk->chbij",
                    (rows * down).reshape(G, H, nb, sb, dk), right,
                    precision=HIGHEST).reshape(G, H, Q, Q)
                return jnp.where(causal, p, 0.0)
            return pairs

        def one_chunk(carry, inp):
            state, finals = carry                               # (H, dk, dv)
            u0c, wc, qkc, qc, kc, ce, seq, is_first = inp
            prev = jnp.where(is_first, s0[jnp.clip(seq, 0, n_seq - 1)],
                             state)
            u = u0c - jnp.einsum("hik,hkv->hiv", wc, prev, precision=HIGHEST)
            o = (jnp.einsum("hik,hkv->hiv", qc, prev, precision=HIGHEST)
                 + jnp.einsum("hij,hjv->hiv", qkc, u, precision=HIGHEST))
            new = ce[..., None] * prev + jnp.einsum(
                "hjk,hjv->hkv", kc, u, precision=HIGHEST)
            finals = finals.at[jnp.where(seq >= 0, seq, n_seq)].set(
                new, mode="drop")
            return (new, finals), o

        def one_group(carry, inp):
            xg, gg, bg, seqs, firsts = inp
            qg, kg, vg = split(xg)
            qs, ks, vs = chunks(qg, dk), chunks(kg, dk), chunks(vg, dv)
            gs, bs = chunks(gg, dk), chunks(bg)     # (G, H, Q, dk), (G, H, Q)
            cum = jnp.cumsum(gs, axis=-2)
            pairs = pairs_against(ks, cum)
            a = jnp.where(jnp.tril(causal, -1),
                          bs[..., :, None] * pairs(ks), 0.0)

            inv = _unit_lower_inverse(a, eye)
            c = jnp.exp(cum)                                 # (G, H, Q, dk)
            u0 = jnp.einsum("chij,chjv->chiv", inv, bs[..., None] * vs,
                            precision=HIGHEST)
            w = jnp.einsum("chij,chjk->chik", inv,
                           bs[..., None] * c * ks, precision=HIGHEST)
            qk = pairs(qs)
            q_in = c * qs                        # a row's read of S_0
            k_end = jnp.exp(cum[..., -1:, :] - cum) * ks
            carry, os_ = jax.lax.scan(
                one_chunk, carry,
                (u0, w, qk, q_in, k_end, c[..., -1, :], seqs, firsts))
            return carry, jnp.swapaxes(os_, 1, 2).reshape(
                G * Q, H * dv).astype(out_dtype)

        init = (jnp.zeros((H, dk, dv), f32), s0.astype(f32))
        (_, finals), os_ = jax.lax.scan(
            one_group, init,
            (groups(x, x.shape[-1]), groups(g, H, dk), groups(beta, H),
             chunk_seq.reshape(nc // G, G),
             first.reshape(nc // G, G)))
        return os_.reshape(T, H, dv), finals
