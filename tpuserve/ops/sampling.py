"""Token sampling — greedy, temperature, top-k, top-p, penalties — as
jit-friendly ops.

Per-request sampling parameters arrive as batched arrays so one compiled
function serves a heterogeneous continuous batch.  Each batch row gets its own
PRNG key (B, 2) uint32, so a request's sampled stream is deterministic given
its seed regardless of which batch it lands in.  The full top-k/top-p path
sorts the vocabulary; the engine picks the cheap path (``mode="greedy"`` /
``mode="temperature"``) when no request in the batch needs truncation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from tpuserve.ops import scopes

NEG_INF = -1e30


def _row_gumbel(keys: jnp.ndarray, shape: tuple[int, int]) -> jnp.ndarray:
    """Per-row Gumbel noise: keys (B, 2) uint32 -> (B, V) float32."""
    u = jax.vmap(lambda k: jax.random.uniform(
        k, shape[1:], jnp.float32, minval=1e-7, maxval=1.0))(keys)
    return -jnp.log(-jnp.log(u))


@partial(jax.jit, static_argnames=("mode",))
def sample_tokens(logits: jnp.ndarray, keys: jnp.ndarray, temperature: jnp.ndarray,
                  top_k: jnp.ndarray, top_p: jnp.ndarray, *,
                  min_p: jnp.ndarray | None = None,
                  mode: str = "full") -> jnp.ndarray:
    """Sample next tokens.

    logits: (B, V); keys: (B, 2) uint32 per-row PRNG keys;
    temperature/top_k/top_p: (B,) per-request params.
    ``temperature <= 0`` means greedy regardless of mode.  ``top_k <= 0``
    disables top-k; ``top_p >= 1`` disables top-p.  ``min_p`` (optional
    (B,), vLLM extension): drop tokens whose probability is below
    ``min_p * max_prob``; ``<= 0`` disables (full mode only).  ``mode``
    is static:
      - "greedy": pure argmax (params/keys ignored).
      - "temperature": no top-k/top-p truncation.
      - "full": sort-based top-k + top-p (+ min-p) truncation.
    Returns (B,) int32.
    """
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if mode == "greedy":
        return greedy_tok

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    gumbel = _row_gumbel(keys, (B, V))

    if mode == "temperature":
        sampled = jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy_tok, sampled)

    # Full path: sort descending once, apply both truncations in sorted
    # order; argmax there and map back through ONE gather (unsorting the
    # whole vocab would cost a second argsort per step on the hot path).
    masked_sorted, sort_idx = truncated_sorted_logits(scaled, top_k, top_p,
                                                      min_p)
    choice = jnp.argmax(masked_sorted + gumbel, axis=-1)     # sorted index
    sampled = jnp.take_along_axis(sort_idx, choice[..., None],
                                  axis=-1)[..., 0].astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


def truncated_sorted_logits(scaled: jnp.ndarray, top_k: jnp.ndarray,
                            top_p: jnp.ndarray,
                            min_p: jnp.ndarray | None = None):
    """Apply top-k/top-p(/min-p) truncation to temperature-scaled logits.
    Returns (masked logits in DESCENDING-sorted order with dropped tokens
    at NEG_INF, sort_idx mapping sorted position -> vocab id).  One home
    for the truncation semantics — the sampler and the speculative
    rejection-acceptance op must agree on the kept set."""
    V = scaled.shape[-1]
    sort_idx = jnp.argsort(-scaled, axis=-1)
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    rank = jnp.arange(V)
    k = jnp.where(top_k <= 0, V, top_k)[..., None]
    keep_k = rank < k
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumsum = jnp.cumsum(probs, axis=-1)
    # Keep tokens whose cumulative prob *before* them is < top_p (always keeps
    # the most-likely token).
    keep_p = (cumsum - probs) < top_p[..., None]
    keep = keep_k & keep_p
    if min_p is not None:
        # sorted descending, so probs[..., :1] is each row's max prob; the
        # clamp makes the most-likely token survive for ANY input (>1 or
        # NaN would mask every token and sample pure Gumbel noise)
        mp = jnp.clip(jnp.nan_to_num(min_p, nan=0.0), 0.0, 1.0)
        keep &= probs >= mp[..., None] * probs[..., :1]
    masked_sorted = jnp.where(keep, sorted_logits, NEG_INF)
    return masked_sorted, sort_idx


def truncated_scaled_logits(scaled: jnp.ndarray, top_k: jnp.ndarray,
                            top_p: jnp.ndarray,
                            min_p: jnp.ndarray | None = None) -> jnp.ndarray:
    """:func:`truncated_sorted_logits` unsorted back to ORIGINAL vocab
    order — for consumers that index by token id (the speculative
    acceptance op); the sampler itself stays in sorted order to avoid
    the extra argsort."""
    masked_sorted, sort_idx = truncated_sorted_logits(scaled, top_k, top_p,
                                                      min_p)
    inv = jnp.argsort(sort_idx, axis=-1)
    return jnp.take_along_axis(masked_sorted, inv, axis=-1)


@jax.jit
def apply_token_mask(logits: jnp.ndarray, packed: jnp.ndarray,
                     enabled: jnp.ndarray) -> jnp.ndarray:
    """Grammar-FSM logit masking: drop every disallowed token to NEG_INF
    BEFORE any top-k/top-p truncation, so sampling renormalises over
    exactly the legal set (distribution-correct guided decoding —
    contrast the engine's legacy top-K candidate substitution, which
    distorts the marginal; tests/test_guided_fsm.py bounds both).

    logits: (B, V); packed: (B, ceil(V/32)) uint32 per-row allow bitmask
    (bit t%32 of word t//32 = token t, runtime/grammar/fsm.py layout);
    enabled: (B,) bool — False rows (unguided requests co-batched with
    guided ones) pass through untouched.
    """
    B, V = logits.shape
    ids = jnp.arange(V, dtype=jnp.int32)
    words = jnp.take_along_axis(
        packed, jnp.broadcast_to(ids // 32, (B, V)), axis=1)
    allow = ((words >> (ids % 32).astype(jnp.uint32)) & 1).astype(bool)
    allow = allow | ~enabled[:, None]
    return jnp.where(allow, logits.astype(jnp.float32), NEG_INF)


@partial(jax.jit, static_argnames=("vocab_size",))
def token_counts(output_tokens: jnp.ndarray, output_mask: jnp.ndarray,
                 vocab_size: int) -> jnp.ndarray:
    """(B, T) token history (+ validity mask) -> (B, V) float32 counts.
    A small T-bucketed executable of its own, so fixed-shape consumers
    (the fused decode window) can take counts without recompiling per
    history-length bucket."""
    B = output_tokens.shape[0]
    ids = jnp.where(output_mask, output_tokens, vocab_size)  # V = dropped
    return jnp.zeros((B, vocab_size), jnp.float32).at[
        jnp.arange(B)[:, None], ids].add(1.0, mode="drop")


def penalize_from_counts(logits: jnp.ndarray, counts: jnp.ndarray,
                         presence_penalty: jnp.ndarray,
                         frequency_penalty: jnp.ndarray,
                         repetition_penalty: jnp.ndarray) -> jnp.ndarray:
    """OpenAI-style presence/frequency and HF-style repetition penalties
    from a (B, V) output-token count matrix.  ONE home for the math —
    the per-step path derives counts from host history each step, the
    fused window carries counts on device across iterations; both must
    penalize identically."""
    logits = logits.astype(jnp.float32)
    seen = counts > 0
    logits = logits - presence_penalty[:, None] * seen
    logits = logits - frequency_penalty[:, None] * counts
    rep = repetition_penalty[:, None]
    rep_logits = jnp.where(logits > 0, logits / rep, logits * rep)
    return jnp.where(seen, rep_logits, logits)


@jax.jit
def apply_logit_penalties(logits: jnp.ndarray, output_tokens: jnp.ndarray,
                          output_mask: jnp.ndarray,
                          presence_penalty: jnp.ndarray,
                          frequency_penalty: jnp.ndarray,
                          repetition_penalty: jnp.ndarray) -> jnp.ndarray:
    """Per-step form: penalties straight from the (B, T) token history.

    logits: (B, V); output_tokens: (B, T) previously generated token ids with
    ``output_mask`` (B, T) marking valid entries; penalties: (B,).
    """
    counts = token_counts(output_tokens, output_mask, logits.shape[1])
    return penalize_from_counts(logits, counts, presence_penalty,
                                frequency_penalty, repetition_penalty)


@jax.jit
def apply_logit_bias(logits: jnp.ndarray, bias_ids: jnp.ndarray,
                     bias_vals: jnp.ndarray) -> jnp.ndarray:
    """OpenAI logit_bias: additive per-token-id bias before sampling.

    logits: (B, V); bias_ids: (B, K) int32 token ids (id >= V for padding,
    scatter mode="drop" ignores it); bias_vals: (B, K) float32.
    """
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    return logits.at[jnp.arange(B)[:, None], bias_ids].add(
        bias_vals, mode="drop")


@partial(jax.jit, static_argnames=("top_n",))
def compute_logprobs(logits: jnp.ndarray, chosen: jnp.ndarray, top_n: int):
    """Log-probabilities for the chosen tokens plus the top-N alternatives.

    logits: (B, V); chosen: (B,) int32.  Returns (chosen_lp (B,),
    top_ids (B, top_n), top_lps (B, top_n)).
    """
    with jax.named_scope(scopes.SAMPLE):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        chosen_lp = jnp.take_along_axis(lp, chosen[:, None].astype(jnp.int32), axis=-1)[:, 0]
        top_lps, top_ids = jax.lax.top_k(lp, top_n)
        return chosen_lp, top_ids.astype(jnp.int32), top_lps


def spec_accept_sampled(logits: jnp.ndarray, draft_next: jnp.ndarray,
                        chunk_lens: jnp.ndarray, keys: jnp.ndarray,
                        temperature: jnp.ndarray, top_k: jnp.ndarray,
                        top_p: jnp.ndarray,
                        min_p: jnp.ndarray | None = None):
    """Rejection-sampling acceptance for speculative decoding under
    temperature/top-k/top-p sampling (the vLLM/spec-sampling scheme,
    specialised to DETERMINISTIC drafts — n-gram lookup and greedy draft
    models propose with an implicit point-mass q, so draft token d is
    accepted w.p. p̃(d) and a rejection resamples from p̃ with d's mass
    removed; the emitted marginal is exactly p̃, the same truncated
    distribution the per-step sampler draws from).

    logits: (B, K, V) verify-pass logits (row j = after consuming row j);
    draft_next: (B, K-1) int32, draft_next[:, j] = the draft token whose
    acceptance row j's distribution decides (= verify input token j+1) —
    positions at or past ``chunk_lens - 1`` are PADDING, not drafts, so
    their token (id 0 from the engine's zero-fill) must NOT lose mass in
    the bonus resample; keys: (B, 2) uint32 per-row PRNG keys (position
    folded in here); temperature/top_k/top_p(/min_p): (B,), the same
    truncation set the per-step sampler uses.  temperature <= 0
    degenerates to exact
    greedy acceptance: p̃ is a point mass at argmax, so accept[j] =
    (draft == argmax) and every resample IS the argmax — byte-identical
    to the greedy accept path.

    Returns (accept (B, K-1) bool, pred (B, K) int32) where pred[:, j] is
    the replacement token when draft j is rejected (j < K-1) and the
    bonus token after a fully-accepted window (j = K-1 — and, for rows
    whose draft list is shorter, at its own chunk end, which the host
    indexes by its known draft length).
    """
    with jax.named_scope(scopes.SAMPLE):
        B, K, V = logits.shape
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (B, K)
        temp = jnp.maximum(temperature, 1e-6)[:, None, None]
        masked = truncated_scaled_logits(
            logits.astype(jnp.float32) / temp,
            jnp.broadcast_to(top_k[:, None], (B, K)),
            jnp.broadcast_to(top_p[:, None], (B, K)),
            None if min_p is None
            else jnp.broadcast_to(min_p[:, None], (B, K)))           # (B, K, V)
        p = jax.nn.softmax(masked, axis=-1)

        # fold the row position into each key (window_sample's convention),
        # then DISTINCT subkeys per (row, position) for the acceptance
        # uniform and the resample gumbel — sharing one key would correlate
        # the accept decision with the replacement draw
        def row_keys(key):
            return jax.vmap(lambda s: jax.random.fold_in(key, s))(jnp.arange(K))
        keys2 = jax.vmap(row_keys)(keys)                             # (B, K, 2)
        u_keys = jax.vmap(jax.vmap(lambda k: jax.random.fold_in(k, 0)))(keys2)
        g_keys = jax.vmap(jax.vmap(lambda k: jax.random.fold_in(k, 1)))(keys2)
        u = jax.vmap(jax.vmap(lambda k: jax.random.uniform(k, ())))(u_keys)
        gumbel = -jnp.log(-jnp.log(jax.vmap(jax.vmap(
            lambda k: jax.random.uniform(k, (V,), jnp.float32,
                                         minval=1e-7, maxval=1.0)))(g_keys)))

        # acceptance: u < p̃(d) at positions 0..K-2
        d = draft_next.astype(jnp.int32)
        p_draft = jnp.take_along_axis(p[:, :-1, :], d[..., None],
                                      axis=-1)[..., 0]               # (B, K-1)
        accept = u[:, :-1] < p_draft

        # resample: p̃ with the draft token's mass removed — but ONLY at real
        # draft positions (j < chunk_len-1).  Padding rows' zero-filled
        # "draft" would otherwise zero token id 0's mass in the bonus
        # distribution at every chunk end (round-5 review).  Gumbel-max over
        # masked logits == categorical over the renormalised distribution.
        is_draft = (jnp.arange(K - 1)[None, :]
                    < (chunk_lens - 1)[:, None])                     # (B, K-1)
        drop = jnp.zeros((B, K, V), bool).at[
            jnp.arange(B)[:, None], jnp.arange(K - 1)[None, :], d].set(
            is_draft)
        resample_logits = jnp.where(drop, NEG_INF, masked)
        sampled = jnp.argmax(resample_logits + gumbel, axis=-1).astype(jnp.int32)
        # degenerate rows: temperature <= 0 → greedy acceptance + greedy pred
        greedy_row = (temperature <= 0.0)[:, None]
        accept = jnp.where(greedy_row, d == greedy[:, :-1], accept)
        pred = jnp.where(greedy_row, greedy, sampled)
        return accept, pred
