"""Plain reference forward pass of the openPangu-Ultra-MoE family
(``model_type`` ``pangu_ultra_moe``), as ONE CHIP'S SHARE of a deployment
whose chips share each expert layer.

What decides ``correct`` for an openPangu-Ultra-MoE configuration.  The
published architecture (``config.json`` of
``FreedomIntelligence/openPangu-Ultra-MoE-718B``: ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``sandwich_norm``, ``first_k_dense_replace``,
``n_routed_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``n_shared_experts``) written in straightforward
``jax.numpy``: float32 throughout, every matrix product at
``precision="highest"``, latent attention in its NAIVE form (every head's
keys and values decompressed from the latent, so an error in the served
path's absorption of ``W_kvb`` into the query and the output cannot cancel
here), no cache, no kernels, no sorting of rows, no buffer.  It shares no
code with ``tpuserve``; it takes the engine's parameter tree only because
the weights must be the same, and the ``ModelConfig`` it is handed for the
sizes and for the share.  Weights are cast to float32 one matrix at a time
inside the product that uses them (one EXPERT's three matrices at a time,
a dense layer's three one program each), the heads attend in groups so that
the ``(T, T)`` scores of 128 of them never stand at once, and the head is
applied in slices of the vocabulary, so nothing is ever copied whole.

A layer, with ``x`` the residual stream (one row a position ``t``) and
every norm RMSNorm at ``rms_norm_eps``::

    a   = norm(x; w_in)
    c_q = norm(a W_qa; w_qa)                       q_lora_rank wide
    q   = c_q W_qb            H heads of [q_nope (nope) | q_rope (rope)]
    a W_kva = [c (kv_lora_rank) | k_r (rope)];  c = norm(c; w_kva)
    q_rope, k_r rotated at t over the rope features, split-half layout,
        angle_i(t) = t theta^(-2i/rope): ONE rotated key for all H heads
    [k_nope_h (nope) | v_h (v)] = c W_kvb          for head h
    k_h = [k_nope_h | k_r]
    s_h(t, u) = q_h(t) . k_h(u) / sqrt(nope + rope)   for u <= t
    attn = concat_h(softmax(s_h) v_h) W_o
    x = x + norm(attn; w_post_attn)                sandwich: on the OUTPUT
    b = norm(x; w_pre_mlp)
    the first first_k_dense_replace layers:
        m = W_down (silu(W_gate b) * W_up b)
    every other layer, over ALL E experts:
        p = sigmoid(W_r b)                         float32, E wide
        the k largest of p                  no groups, no selection bias
        w_e = scaling * p_e / (sum of the k chosen p + 1e-20)
        m = sum over the chosen e THAT ARE HELD HERE of w_e E_e(b) + S(b)
    x = x + norm(m; w_post_mlp)                    sandwich again
    with E_e and S gated MLPs as the dense one, S the shared expert

with ``logits = W_head norm(x; w_f)``, embedding and head untied.

**The share** (``cfg.moe_experts_held`` experts from ``cfg.moe_first_expert``
on; ``params`` holds those experts' kernels alone, the router all E
columns): scores, the top-k, the renormalisation over all k chosen and the
scaling are computed over all E BEFORE anything is left out; then the sum
runs over the held experts only, expert e weighted by ``w_e`` where it was
chosen and by zero elsewhere.  What the absent experts would have added is
left out here as it is in the program, and that partial sum goes under the
layer's post-norm and on to the next layer.  With every expert held
(``moe_experts_held`` 0) this is the uncut layer.

Departures from the published model, each listed in the configuration
file's ``assumed``: ``config.json`` names no scoring function, expert
groups or selection bias (sigmoid scores over all experts at once, none:
``norm_topk_prob`` with a factor of 2.5 is DeepSeek-V3's sigmoid recipe);
which rope features pair is a property of the checkpoint's layout (the
engine de-interleaves at load, so split-half here) and has no effect under
random weights; where the four norms of a layer stand is the family's
published block under ``sandwich_norm``; the multi-token-prediction layer
(``num_nextn_predict_layers``) is no part of the next-token forward pass
and is left out.  Sequences are right-padded to one length (harmless under
a causal mask).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_SLICE = 16384
#: query heads whose (T, T) scores stand at once
HEAD_GROUP = 16
# How far behind this router's own k-th largest score an expert the server
# named may lie and still count as a tie that bf16 decided the other way
# (see route).  A random router's 256 logits are near unit normal (unit-RMS
# rows against columns of std fan_in^-0.5): the 8th and 9th largest stand
# near z = 1.83, where the density is 0.074, so they lie about
# 1 / (256 x 0.074) = 0.053 apart, and the sigmoid's slope there
# (0.862 x 0.138 = 0.119) makes that 0.0063 in scores.  Mellum 2's and
# K-EXAONE's 0.1 in logits, where every sound pick lay within 0.05 on the
# chip, is 0.012 in these scores: two mean gaps.  A pick further behind is
# a wrong pick.
TIE = 0.012

# config.json key -> ModelConfig field, beyond the harness's own lists
# (which hold the latent sizes, the widths, rope_theta, norm_topk_prob,
# n_shared_experts and first_k_dense_replace): what else changes the
# mathematics.
FIXED = {
    "sandwich_norm": "sandwich_norms",
    "routed_scaling_factor": "moe_routed_scaling",
}
# the multi-token-prediction layer is not built (``assumed.mtp``): its key
# sizes nothing that runs
DESCRIPTIVE = ("num_nextn_predict_layers",)


def check_family(cfg) -> None:
    """Refuse an architecture this file does not describe."""
    wrong = []
    if cfg.norm != "rmsnorm" or cfg.norm_weight_offset \
            or not cfg.sandwich_norms or cfg.norm_placement != "pre":
        wrong.append("norms")
    if not cfg.num_experts or cfg.moe_scoring != "sigmoid" \
            or cfg.moe_router_bias or cfg.moe_n_group != 1 \
            or cfg.moe_topk_group != 1 or not cfg.norm_topk_prob \
            or cfg.moe_shared_experts != 1 or cfg.act != "silu" \
            or cfg.mlp_style != "gated" or cfg.mlp_bias \
            or cfg.mlp_multipliers != (1.0, 1.0):
        wrong.append("experts")
    if cfg.pos != "rope" or cfg.rope_llama3_scaling or cfg.rope_yarn \
            or cfg.rope_scaling_factor != 1.0 or cfg.rope_local_base_freq \
            or getattr(cfg, "rope_full_yarn", None) \
            or getattr(cfg, "rope_windowed_only", False):
        wrong.append("positions")
    if not cfg.mla_kv_lora_rank or not cfg.mla_q_lora_rank \
            or cfg.attn_logit_softcapping or cfg.final_logit_softcapping \
            or cfg.query_pre_attn_scalar or cfg.embed_scale_by_sqrt_dim \
            or cfg.qk_norm or cfg.window_layers is not None \
            or cfg.sliding_window or cfg.tie_word_embeddings \
            or cfg.attention_bias \
            or cfg.attention_in_multiplier != 1.0 \
            or cfg.attention_out_multiplier != 1.0 \
            or cfg.key_multiplier != 1.0:
        wrong.append("attention")
    if getattr(cfg, "mamba_d_ssm", 0) or getattr(cfg, "linear_layers", None):
        wrong.append("recurrent layers")
    if cfg.embedding_multiplier != 1.0 or cfg.lm_head_multiplier != 1.0:
        wrong.append("multipliers")
    if wrong:
        raise ValueError(f"{cfg.name}: not the openPangu-Ultra-MoE family "
                         f"({', '.join(wrong)})")


def held(cfg) -> tuple:
    """``(first expert id, experts held)`` of the share the ModelConfig
    states; every expert where it states none."""
    n = getattr(cfg, "moe_experts_held", 0)
    return (cfg.moe_first_expert, n) if n else (0, cfg.num_experts)


def _f32(x):
    return x.astype(jnp.float32)


def _linear(x, p):
    return jnp.matmul(x, _f32(p["kernel"]), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, positions, theta):
    """x: (B, T, heads, D).  Split-half rotation: feature i pairs with
    feature i + D/2."""
    d = x.shape[-1]
    inv = jnp.asarray(float(theta) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv     # (B, T, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("cfg",))
def _attention_branch(lp, x, positions, cfg):
    """``x + norm(W_o attn(norm(x)))`` for one layer, latent attention in
    its naive form, ``HEAD_GROUP`` heads at a time."""
    b, t, _ = x.shape
    h = cfg.num_heads
    rope, rank, vd = (cfg.mla_qk_rope_head_dim, cfg.mla_kv_lora_rank,
                      cfg.mla_v_head_dim)
    nope = cfg.head_dim - rope
    a = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    cq = _rmsnorm(_linear(a, lp["q_a_proj"]), lp["q_a_norm"]["scale"],
                  cfg.norm_eps)
    q = _linear(cq, lp["q_b_proj"]).reshape(b, t, h, nope + rope)
    ckv = _linear(a, lp["kv_a_proj"])                  # (b, t, rank + rope)
    c = _rmsnorm(ckv[..., :rank], lp["kv_a_norm"]["scale"], cfg.norm_eps)
    k_r = _rope(ckv[..., None, rank:], positions, cfg.rope_theta)  # 1 head
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], positions, cfg.rope_theta)], -1)
    kv = _linear(c, lp["kv_b_proj"]).reshape(b, t, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rope))], -1)
    v = kv[..., nope:]
    i, j = positions[:, :, None], positions[:, None, :]
    mask = j <= i
    scale = (nope + rope) ** -0.5

    def group(args):                     # (b, t, g, d) each
        qg, kg, vg = args
        scores = jnp.einsum("bqgd,bkgd->bgqk", qg, kg,
                            precision=HIGHEST) * scale
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        return jnp.einsum("bgqk,bkgd->bqgd", jax.nn.softmax(scores, axis=-1),
                          vg, precision=HIGHEST)

    g = min(HEAD_GROUP, h)
    while h % g:
        g -= 1

    def split(y):                        # (b, t, h, d) -> (h/g, b, t, g, d)
        return jnp.moveaxis(y.reshape(b, t, h // g, g, y.shape[-1]), 2, 0)

    att = jax.lax.map(group, (split(q), split(k), split(v)))
    att = jnp.moveaxis(att, 0, 2).reshape(b, t, h * vd)
    return x + _rmsnorm(_linear(att, lp["o_proj"]),
                        lp["post_attn_norm"]["scale"], cfg.norm_eps)


# one matrix of a dense MLP a program: at the published widths a float32
# copy of a dense layer's is 566 MB, and three alive at once beside the
# served model's weights and caches would not fit the chip
_project = jax.jit(_linear)


def _gated_mlp(h, p):
    return _project(jax.nn.silu(_project(h, p["gate_proj"]))
                    * _project(h, p["up_proj"]), p["down_proj"])


def route(lp, h, cfg, served):
    """The router's weight on EVERY expert, held or not, for every row of
    ``h``: the sigmoid of the router's logits, zero outside the
    ``num_experts_per_tok`` chosen, those divided by their sum (plus
    1e-20) and scaled by ``routed_scaling_factor``.

    The chosen are the largest by score, except on a row whose experts the
    server named (``served`` (N, k) int32, -1 on the other rows): there
    they are the server's, as long as each of them lies within ``TIE`` of
    this router's own k-th largest.  Only WHICH experts is taken over, and
    only at a near-tie; their weights are this router's float32 scores.  A
    named expert further behind is a wrong pick, not a tie: the row keeps
    this router's own choice and the comparison shows the difference."""
    scores = jax.nn.sigmoid(_linear(h, lp["router"]))
    _, idx = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    kth = jnp.take_along_axis(scores, idx[:, -1:], axis=1)
    named = jnp.take_along_axis(scores, jnp.maximum(served, 0), axis=1)
    replay = jnp.all((served >= 0) & (named >= kth - TIE), axis=1,
                     keepdims=True)
    idx = jnp.where(replay, served, idx)
    top = jnp.take_along_axis(scores, idx, axis=1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * cfg.moe_routed_scaling
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(top)       # (N, E)


@partial(jax.jit, static_argnames=("cfg",))
def _held_experts(lp, h, cfg, served):
    """The held experts' part of the routed sum for rows ``h`` (N, H):
    expert by expert over the HELD ones, one expert's three matrices in
    float32 at a time, each weighted by the router's weight where it was
    chosen and by zero where it was not.  ``lp["experts"]`` holds the
    held experts' kernels in order; ``served`` (N, k): the experts the
    server named, see :func:`route`."""
    first, n = held(cfg)
    weights = route(lp, h, cfg, served)
    ek = lp["experts"]

    def one(e, acc):
        def mat(name):
            return _f32(jax.lax.dynamic_index_in_dim(
                ek[name]["kernel"], e, axis=0, keepdims=False))
        gate = jnp.matmul(h, mat("gate_proj"), precision=HIGHEST)
        up = jnp.matmul(h, mat("up_proj"), precision=HIGHEST)
        out = jnp.matmul(jax.nn.silu(gate) * up, mat("down_proj"),
                         precision=HIGHEST)
        w = jax.lax.dynamic_index_in_dim(weights, first + e, axis=1)
        return acc + w * out                                   # w (N, 1)

    return jax.lax.fori_loop(0, n, one, jnp.zeros_like(h))


def _mlp_branch(lp, x, cfg, served):
    """``x + norm(MLP(norm(x)))``: the dense gated MLP on a layer that has
    one, else the held experts' part of the routed sum plus the shared
    expert; the sandwich's second norm on the branch's output."""
    b, t, hidden = x.shape
    h = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg.norm_eps).reshape(-1, hidden)
    if "experts" not in lp:
        y = _gated_mlp(h, lp)
    else:
        y = _held_experts(lp, h, cfg, served.reshape(b * t, -1)) \
            + _gated_mlp(h, lp["shared"])
    return x + _rmsnorm(y, lp["post_mlp_norm"]["scale"],
                        cfg.norm_eps).reshape(b, t, hidden)


@jax.jit
def _head_slice(h, w_slice):
    """h (N, H) against a slice of the untied head (H, rows)."""
    return jnp.matmul(h, _f32(w_slice), precision=HIGHEST)


def hidden_states(params, cfg, tokens, served=None):
    """tokens (B, T) int32 -> final-normed hidden states (B, T, H).
    ``served`` (B, T, expert layers, k) int32: the experts the server
    named for a position's expert layers in their order, -1 where it named
    none (:func:`route`)."""
    check_family(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    sparse = [li for li, lp in enumerate(params["layers"]) if "experts" in lp]
    if served is None:
        served = np.full((b, t, len(sparse), cfg.num_experts_per_tok), -1,
                         np.int32)
    served = jnp.asarray(served, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x = _f32(params["embed"]["weight"][tokens])
    for li, lp in enumerate(params["layers"]):
        x = _attention_branch(lp, x, positions, cfg)
        x = _mlp_branch(lp, x, cfg, served[:, :, sparse.index(li)]
                        if li in sparse else None)
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
    EXPERT layer (the dense ones have none) routed the position that
    produced it to, and ``prompt_routed_experts``, the same for each
    position of the prompt (-1 where the server computed none).
    :func:`route` replays them where they are near-ties; a position the
    server names no experts for keeps this router's own."""
    width = max(len(ids) + len(toks) for ids, toks, _ in probes)
    sparse = sum("experts" in lp for lp in params["layers"])
    tokens = np.zeros((len(probes), width), np.int32)
    served = np.full((len(probes), width, sparse, cfg.num_experts_per_tok),
                     -1, np.int32)
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
