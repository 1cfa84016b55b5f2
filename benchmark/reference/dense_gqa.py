"""Plain reference forward pass of the dense-GQA decoder family.

What decides ``correct``.  The published architecture (Mistral-7B, Qwen3:
pre-norm decoder, RMSNorm, rotary positions in the split-half layout,
grouped-query causal attention with an optional per-head q/k RMSNorm and an
optional sliding window, SwiGLU MLP, tied or untied output head) written in
straightforward ``jax.numpy``: float32 throughout, every matrix product at
``precision="highest"``, no cache, no kernels, no batching tricks.  It
shares no code with ``tpuserve``; it takes the engine's parameter tree only
because the weights must be the same.  Weights are cast to float32 one
matrix at a time inside the product that uses them, and the head is applied
in slices of the vocabulary, so nothing is ever copied whole.

Departures from the published models: none in the mathematics.  Sequences
are right-padded to one length (harmless under a causal mask).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_SLICE = 16384


def check_family(cfg) -> None:
    """Refuse an architecture this file does not describe."""
    wrong = []
    if cfg.norm != "rmsnorm" or cfg.norm_weight_offset:
        wrong.append("norm")
    if cfg.mlp_style != "gated" or cfg.act != "silu" or cfg.num_experts:
        wrong.append("mlp")
    if cfg.pos != "rope" or cfg.partial_rotary_factor != 1.0 \
            or cfg.rope_llama3_scaling or cfg.rope_yarn \
            or cfg.rope_scaling_factor != 1.0 or cfg.rope_local_base_freq:
        wrong.append("positions")
    if cfg.mla_kv_lora_rank or cfg.attn_logit_softcapping \
            or cfg.final_logit_softcapping or cfg.sandwich_norms \
            or cfg.query_pre_attn_scalar or cfg.embed_scale_by_sqrt_dim \
            or cfg.window_layers is not None \
            or cfg.window_pattern != "first_full":
        wrong.append("attention")
    if wrong:
        raise ValueError(f"{cfg.name}: not the dense-GQA family "
                         f"({', '.join(wrong)} differ)")


def _f32(x):
    return x.astype(jnp.float32)


def _linear(x, p):
    y = jnp.matmul(x, _f32(p["kernel"]), precision=HIGHEST)
    if "bias" in p:
        y = y + _f32(p["bias"])
    return y


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, positions, theta):
    """x: (B, T, heads, D).  Split-half rotation: feature i pairs with
    feature i + D/2, frequency theta ** (-2i / D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv     # (B, T, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("cfg", "window"))
def _layer(lp, x, positions, cfg, window):
    b, t, _ = x.shape
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    q = _linear(h, lp["q_proj"]).reshape(b, t, hq, d)
    k = _linear(h, lp["k_proj"]).reshape(b, t, hkv, d)
    v = _linear(h, lp["v_proj"]).reshape(b, t, hkv, d)
    if cfg.qk_norm:
        q = _rmsnorm(q, lp["q_norm"]["scale"], cfg.norm_eps)
        k = _rmsnorm(k, lp["k_norm"]["scale"], cfg.norm_eps)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    group = hq // hkv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        * (d ** -0.5)
    qi = positions[:, :, None]
    kj = positions[:, None, :]
    mask = kj <= qi
    if window is not None:
        mask = mask & (kj > qi - window)
    scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    x = x + _linear(att.reshape(b, t, hq * d), lp["o_proj"])
    h = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
    gate = jax.nn.silu(_linear(h, lp["gate_proj"]))
    return x + _linear(gate * _linear(h, lp["up_proj"]), lp["down_proj"])


@partial(jax.jit, static_argnames=("rows_first",))
def _head_slice(h, w_slice, rows_first):
    """h (N, H) against a slice of the head: logits (N, rows).  A tied
    head is the embedding, (rows, H); an untied one is (H, rows)."""
    w = _f32(w_slice)
    return jnp.matmul(h, w.T if rows_first else w, precision=HIGHEST)


def hidden_states(params, cfg, tokens):
    """tokens (B, T) int32 -> final-normed hidden states (B, T, H)."""
    check_family(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x = _f32(params["embed"]["weight"][tokens])
    for li, lp in enumerate(params["layers"]):
        window = cfg.sliding_window
        if window is not None and li < cfg.full_attention_first_layers:
            window = None
        x = _layer(lp, x, positions, cfg, window)
    return _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)


def logprobs_at(params, cfg, tokens, rows):
    """Log-probabilities over the vocabulary after the positions ``rows``
    (a list of (sequence, position) pairs): (len(rows), V) float32."""
    h = hidden_states(params, cfg, tokens)
    seq = jnp.asarray([r[0] for r in rows], jnp.int32)
    pos = jnp.asarray([r[1] for r in rows], jnp.int32)
    h = h[seq, pos]                                          # (N, H)
    spans = [(lo, min(lo + VOCAB_SLICE, cfg.vocab_size))
             for lo in range(0, cfg.vocab_size, VOCAB_SLICE)]
    if cfg.tie_word_embeddings:
        head = params["embed"]["weight"]                     # (V, H)
        parts = [_head_slice(h, head[lo:hi], True) for lo, hi in spans]
    else:
        head = params["lm_head"]["kernel"]                   # (H, V)
        parts = [_head_slice(h, head[:, lo:hi], False) for lo, hi in spans]
    logits = jnp.concatenate(parts, axis=-1)
    return jax.nn.log_softmax(logits, axis=-1)


def score_probes(params, cfg, probes):
    """The harness's call (``harness/plan.py`` has the interface): for
    each probe ``(prompt ids, served token ids, the server's logprobs
    object)`` one row of log-probabilities for every served token, in
    served order.  This family makes its tokens left to right, so served
    token j is scored after position ``len(prompt) + j - 1`` of prompt and
    served tokens run as one sequence; the logprobs object is not read."""
    width = max(len(ids) + len(toks) for ids, toks, _ in probes)
    tokens = np.zeros((len(probes), width), np.int32)
    rows = []
    for i, (ids, toks, _) in enumerate(probes):
        seq = list(ids) + list(toks)
        tokens[i, :len(seq)] = seq
        rows += [(i, len(ids) + j - 1) for j in range(len(toks))]
    return logprobs_at(params, cfg, tokens, rows)
