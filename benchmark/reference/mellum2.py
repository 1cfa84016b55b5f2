"""Plain reference forward pass of the Mellum 2 family: a grouped-query
decoder whose layers are of two kinds and whose MLPs are routed experts.

What decides ``correct`` for a Mellum 2 configuration.  The published
architecture (``config.json`` of ``JetBrains/Mellum2-12B-A2.5B-Instruct``:
``layer_types``, ``rope_parameters`` keyed by layer type, ``num_experts``,
``num_experts_per_tok``, ``norm_topk_prob``) written in straightforward
``jax.numpy``: float32 throughout, every matrix product at
``precision="highest"``, no cache, no kernels, no sorting of rows.  It
shares no code with ``tpuserve``; it takes the engine's parameter tree only
because the weights must be the same.  Weights are cast to float32 one
matrix at a time inside the product that uses them (one EXPERT's three
matrices at a time), and the head is applied in slices of the vocabulary,
so nothing is ever copied whole.

A layer, with ``x`` the residual stream::

    u = RMSNorm(x; w_attn)
    q = W_q u, k = W_k u, v = W_v u          (Hq heads of D, Hkv of D)
    rotary on all D dimensions, split-half layout, by the LAYER's table:
      sliding_attention  angle_i(p) = p theta^(-2i/D)
      full_attention     YaRN: f_i = theta^(-2i/D),
                         r_i = clip((i - low) / (high - low), 0, 1),
                         low  = floor(D ln(L0 / (2 pi beta_fast)) / (2 ln theta)),
                         high = ceil (D ln(L0 / (2 pi beta_slow)) / (2 ln theta)),
                         angle_i(p) = p (f_i / factor r_i + f_i (1 - r_i)),
                         and cos and sin times 0.1 ln(factor) + 1
    causal softmax at scale D^-0.5; on a sliding_attention layer position i
    attends j only if j > i - window
    x = x + W_o attn
    h = RMSNorm(x; w_mlp)
    s = softmax(W_r h) over the E experts; the k largest, renormalised to
    sum 1 (norm_topk_prob)
    x = x + sum over the chosen e of w_e W_down,e (silu(W_gate,e h) * W_up,e h)

with ``logits = W_head RMSNorm(x)``, embedding and head untied.  The expert
sum is a loop over all E experts in which expert e's output is weighted by
``w_e`` where e was chosen and by zero elsewhere: every expert on every
token, which is what the served path's sparse dispatch must equal.

Departures from the published model, both listed in the configuration
file's ``assumed``: no per-head q/k norm (no key of ``config.json`` states
one); the multi-token-prediction head the model card mentions is left out
(no key gives its shape, and serving one token a step does not use it).
Sequences are right-padded to one length (harmless under a causal mask).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_SLICE = 16384
# how far behind this router's own k-th largest logit an expert the server
# named may score and still count as a tie that bf16 decided the other way
# (see route).  On the chip at the published widths every sound pick lay
# within 0.05 (the probes read the same at 0.05, 0.1 and 1.0, and worse at
# 0.02: PERF.md §6, PR 35); the 8th and 9th of 64 random scores lie 0.076
# apart on average.
TIE = 0.1

# config.json key -> ModelConfig field, beyond the harness's own lists:
# what decides each layer's kind and table.  The fields named here return
# what JSON holds (lists, a dict).
FIXED = {
    "layer_types": "layer_types",
    "mlp_layer_types": "mlp_layer_types",
    "rope_parameters": "rope_parameters",
    "max_window_layers": "max_window_layers",
}
# every other key of this family's config.json is on one of the harness's
# lists already
DESCRIPTIVE = ()


def check_family(cfg) -> None:
    """Refuse an architecture this file does not describe."""
    wrong = []
    if cfg.norm != "rmsnorm" or cfg.norm_weight_offset:
        wrong.append("norm")
    if not cfg.num_experts or cfg.moe_scoring != "softmax" \
            or cfg.moe_router_bias or cfg.moe_n_group != 1 \
            or cfg.moe_routed_scaling != 1.0 or cfg.moe_shared_experts \
            or cfg.moe_first_k_dense or cfg.act != "silu" \
            or cfg.mlp_style != "gated":
        wrong.append("experts")
    if cfg.pos != "rope" or cfg.partial_rotary_factor != 1.0 \
            or cfg.rope_llama3_scaling or cfg.rope_yarn \
            or cfg.rope_scaling_factor != 1.0 or cfg.rope_local_base_freq \
            or not getattr(cfg, "rope_full_yarn", None):
        wrong.append("positions")
    if cfg.mla_kv_lora_rank or cfg.attn_logit_softcapping \
            or cfg.final_logit_softcapping or cfg.sandwich_norms \
            or cfg.query_pre_attn_scalar or cfg.embed_scale_by_sqrt_dim \
            or cfg.qk_norm or cfg.window_layers is None \
            or not cfg.sliding_window or cfg.tie_word_embeddings \
            or cfg.attention_in_multiplier != 1.0 \
            or cfg.attention_out_multiplier != 1.0 \
            or cfg.key_multiplier != 1.0:
        wrong.append("attention")
    if getattr(cfg, "mamba_d_ssm", 0):
        wrong.append("state-space heads")
    if cfg.embedding_multiplier != 1.0 or cfg.lm_head_multiplier != 1.0:
        wrong.append("multipliers")
    if wrong:
        raise ValueError(f"{cfg.name}: not the Mellum 2 family "
                         f"({', '.join(wrong)})")


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


def rotary_table(cfg, windowed: bool):
    """``(inverse frequencies (D/2,), factor on cos and sin)`` of one
    layer kind, from the equations in this file's docstring."""
    d, theta = cfg.head_dim, float(cfg.rope_theta)
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if windowed:
        return inv.astype(np.float32), 1.0
    factor, beta_fast, beta_slow, orig = cfg.rope_full_yarn

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)
    return inv.astype(np.float32), 0.1 * math.log(factor) + 1.0


def _rope(x, positions, inv, factor):
    """x: (B, T, heads, D).  Split-half rotation: feature i pairs with
    feature i + D/2."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * inv     # (B, T, D/2)
    cos = (jnp.cos(ang) * factor)[:, :, None, :]
    sin = (jnp.sin(ang) * factor)[:, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("cfg", "windowed"))
def _attention_branch(lp, x, positions, cfg, windowed):
    """``x + W_o attn(RMSNorm(x))`` for one layer of the given kind, one
    KV head's group of query heads at a time (the (T, T) scores of all
    heads at once would not fit beside a served model at long prompts)."""
    b, t, _ = x.shape
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rep = hq // hkv
    u = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    inv, factor = rotary_table(cfg, windowed)
    q = _rope(_linear(u, lp["q_proj"]).reshape(b, t, hq, d), positions,
              inv, factor)
    k = _rope(_linear(u, lp["k_proj"]).reshape(b, t, hkv, d), positions,
              inv, factor)
    v = _linear(u, lp["v_proj"]).reshape(b, t, hkv, d)
    i, j = positions[:, :, None], positions[:, None, :]
    mask = j <= i
    if windowed:
        mask &= j > i - cfg.sliding_window

    def group(args):                      # q (b, t, rep, d); k, v (b, t, d)
        qg, kg, vg = args
        scores = jnp.einsum("bqrd,bkd->brqk", qg, kg, precision=HIGHEST) \
            * (d ** -0.5)
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        return jnp.einsum("brqk,bkd->bqrd", jax.nn.softmax(scores, axis=-1),
                          vg, precision=HIGHEST)

    att = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(b, t, hkv, rep, d), 2, 0),
        jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))  # (hkv, b, t, rep, d)
    att = jnp.moveaxis(att, 0, 2).reshape(b, t, hq * d)
    return x + _linear(att, lp["o_proj"])


def route(lp, h, cfg, served):
    """The router's weight on every expert for every row of ``h``: the
    softmax over all experts, zero outside the ``num_experts_per_tok``
    chosen, those renormalised to sum 1 where the model says so.

    The chosen are the largest, except on a row whose experts the server
    named (``served`` (N, k) int32, -1 on the other rows): there they are
    the server's, as long as each of them scores within ``TIE`` of this
    router's own k-th largest.  Only WHICH experts is taken over, and only
    at a near-tie; their weights are this router's float32 scores.  A
    named expert further behind is a wrong pick, not a tie: the row keeps
    this router's own choice and the comparison shows the difference."""
    logits = _linear(h, lp["router"])
    scores = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    kth = jnp.take_along_axis(logits, idx[:, -1:], axis=1)
    named = jnp.take_along_axis(logits, jnp.maximum(served, 0), axis=1)
    replay = jnp.all((served >= 0) & (named >= kth - TIE), axis=1,
                     keepdims=True)
    idx = jnp.where(replay, served, idx)
    top = jnp.take_along_axis(scores, idx, axis=1)
    if cfg.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top)       # (N, E)


@partial(jax.jit, static_argnames=("cfg",))
def _expert_branch(lp, x, cfg, served):
    """``x + MoE(RMSNorm(x))``: expert by expert, one expert's three
    matrices in float32 at a time, each expert's output weighted by the
    router's weight where it was chosen and by zero where it was not.
    ``served`` (B, T, k): the experts the server named, see :func:`route`."""
    b, t, hidden = x.shape
    h = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg.norm_eps).reshape(-1, hidden)
    weights = route(lp, h, cfg, served.reshape(b * t, -1))
    ek = lp["experts"]

    def one(e, acc):
        def mat(name):
            return _f32(jax.lax.dynamic_index_in_dim(
                ek[name]["kernel"], e, axis=0, keepdims=False))
        gate = jnp.matmul(h, mat("gate_proj"), precision=HIGHEST)
        up = jnp.matmul(h, mat("up_proj"), precision=HIGHEST)
        out = jnp.matmul(jax.nn.silu(gate) * up, mat("down_proj"),
                         precision=HIGHEST)
        w = jax.lax.dynamic_index_in_dim(weights, e, axis=1)   # (N, 1)
        return acc + w * out

    y = jax.lax.fori_loop(0, cfg.num_experts, one, jnp.zeros_like(h))
    return x + y.reshape(b, t, hidden)


@jax.jit
def _head_slice(h, w_slice):
    """h (N, H) against a slice of the untied head (H, rows)."""
    return jnp.matmul(h, _f32(w_slice), precision=HIGHEST)


def hidden_states(params, cfg, tokens, served=None):
    """tokens (B, T) int32 -> final-normed hidden states (B, T, H).
    ``served`` (B, T, layers, k) int32: the experts the server named for a
    position's layers, -1 where it named none (:func:`route`)."""
    check_family(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    if served is None:
        served = np.full((b, t, len(params["layers"]),
                          cfg.num_experts_per_tok), -1, np.int32)
    served = jnp.asarray(served, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x = _f32(params["embed"]["weight"][tokens])
    for li, lp in enumerate(params["layers"]):
        x = _attention_branch(lp, x, positions, cfg,
                              bool(cfg.window_layers[li]))
        x = _expert_branch(lp, x, cfg, served[:, :, li])
    return _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)


def logits_at(params, cfg, tokens, rows, served=None):
    """Logits over the vocabulary after the positions ``rows`` (a list of
    (sequence, position) pairs): (len(rows), V) float32."""
    h = hidden_states(params, cfg, tokens, served)
    seq = jnp.asarray([r[0] for r in rows], jnp.int32)
    pos = jnp.asarray([r[1] for r in rows], jnp.int32)
    h = h[seq, pos]                                          # (N, H)
    head = params["lm_head"]["kernel"]                       # (H, V)
    parts = [_head_slice(h, head[:, lo:min(lo + VOCAB_SLICE, cfg.vocab_size)])
             for lo in range(0, cfg.vocab_size, VOCAB_SLICE)]
    return jnp.concatenate(parts, axis=-1)


def logprobs_at(params, cfg, tokens, rows, served=None):
    return jax.nn.log_softmax(logits_at(params, cfg, tokens, rows, served),
                              axis=-1)


def score_probes(params, cfg, probes):
    """The harness's call (``harness/plan.py`` has the interface): for
    each probe ``(prompt ids, served token ids, the server's logprobs
    object)`` one row of log-probabilities for every served token, in
    served order.  This family makes its tokens left to right, so served
    token j is scored after position ``len(prompt) + j - 1`` of prompt and
    served tokens run as one sequence.  Of the logprobs object two keys
    are read: ``routed_experts``, for each served token the experts each
    layer routed the position that produced it to, and
    ``prompt_routed_experts``, the same for each position of the prompt
    (-1 where the server computed none).  :func:`route` replays them where
    they are near-ties; a position the server names no experts for keeps
    this router's own."""
    width = max(len(ids) + len(toks) for ids, toks, _ in probes)
    tokens = np.zeros((len(probes), width), np.int32)
    served = np.full((len(probes), width, len(params["layers"]),
                      cfg.num_experts_per_tok), -1, np.int32)
    rows = []
    for i, (ids, toks, lp) in enumerate(probes):
        seq = list(ids) + list(toks)
        tokens[i, :len(seq)] = seq
        rows += [(i, len(ids) + j - 1) for j in range(len(toks))]
        for j, layers in enumerate(lp.get("prompt_routed_experts") or ()):
            served[i, j] = layers
        for j, layers in enumerate(lp.get("routed_experts") or ()):
            served[i, len(ids) + j - 1] = layers
    return logprobs_at(params, cfg, tokens, rows, served)
