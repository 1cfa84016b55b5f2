"""Plain reference forward pass of the Ling-3.0-flash family's language
model (``model_type`` ``bailing_hybrid``), as ONE CHIP'S SHARE of a
deployment whose chips share each expert layer.

What decides ``correct`` for a Ling-3.0-flash configuration.  The published
architecture (``config.json`` of ``inclusionAI/Ling-3.0-flash-VL``, its
language model: ``layer_group_size``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``use_qk_norm``,
``gated_attention_proj_granularity_type``, ``short_conv_kernel_size``,
``kda_safe_gate``, ``kda_lower_bound``, ``first_k_dense_replace``,
``num_experts``, ``n_group``, ``topk_group``, ``num_experts_per_tok``,
``moe_router_enable_expert_bias``, ``routed_scaling_factor``) written in
straightforward ``jax.numpy``: float32 throughout, every matrix product at
``precision="highest"``, the linear layers' recurrence ROW BY ROW (a
``lax.scan`` over tokens: no chunk, no triangular solve, no sub-block, so
nothing of the served path's chunked form can cancel here), latent
attention in its NAIVE form (every head's keys and values decompressed
from the latent), no cache, no kernels, no sorting of rows, no buffer.  It
shares no code with ``tpuserve``; it takes the engine's parameter tree only
because the weights must be the same, and the ``ModelConfig`` it is handed
for the sizes and for the share.  Weights are cast to float32 one matrix at
a time inside the product that uses them (one EXPERT's three matrices at a
time, a dense layer's three one program each), the latent layers' heads
attend in groups, and the head is applied in slices of the vocabulary, so
nothing is ever copied whole and 12 layers at the published widths fit
beside the served model's weights, pool and pages.

A layer, with ``x`` the residual stream (one row a position ``t``), every
norm RMSNorm at ``rms_norm_eps`` and ``a = norm(x; w_in)``.

Layer ``i`` with ``(i + 1) % layer_group_size != 0`` -- Kimi Delta
Attention (arXiv:2510.26692), H heads, keys and values of ``head_dim``::

    [q | k | v] = silu(conv_W([W_q a | W_k a | W_v a]))   causal, depthwise
    q_h = q_h / |q_h| * head_dim^-1/2;  k_h = k_h / |k_h|     (+1e-6 inside)
    b_h = sigmoid(W_b a)_h                                 a step size a head
    g_h = kda_lower_bound * sigmoid(exp(A_h) ((W_f a)_h + dt_bias_h))
                                          head_dim values, in (bound, 0)
    S' = diag(exp(g_h(t))) S_h(t-1)       the state's ROWS decay, each at
    u  = b_h (v_h - S'^T k_h)             its own rate
    S_h(t) = S' + k_h u^T;   o_h = S_h(t)^T q_h
    x = x + W_o concat_h(norm(o_h; w_o) * sigmoid((W_g a)_h))

Layer ``i`` with ``(i + 1) % layer_group_size == 0`` -- latent attention
with no query latent::

    q = a W_q                  H heads of [q_nope (nope) | q_rope (rope)]
    q_h = norm(q_h; w_qn)                      use_qk_norm: a head, 192 wide
    a W_kva = [c (kv_lora_rank) | k_r (rope)];  c = norm(c; w_kva)
    k_r = norm(k_r; w_kn)                      use_qk_norm: the ONE rope key
    q_rope, k_r rotated at t, split-half, angle_i(t) = t theta^(-2i/rope)
    [k_nope_h | v_h] = c W_kvb;   k_h = [k_nope_h | k_r]
    s_h(t, u) = q_h(t) . k_h(u) / sqrt(nope + rope)          for u <= t
    x = x + W_o concat_h(softmax(s_h) v_h * sigmoid((W_gate a)_h))

then, in every layer, ``b = norm(x; w_mlp)`` and::

    the first first_k_dense_replace layers:  m = W_down (silu(W_gate b) * W_up b)
    every other layer, over ALL E experts in n_group groups of E / n_group:
        p = sigmoid(W_r b)                       float32, E wide
        c = p + bias                             selection only
        a group's score: the sum of its two largest c
        the topk_group groups with the largest score survive, c = 0 elsewhere
        the k largest c;  w_e = scaling * p_e / (sum of the k chosen p + 1e-20)
        m = sum over the chosen e THAT ARE HELD HERE of w_e E_e(b) + S(b)
    x = x + m

with ``logits = W_head norm(x; w_f)``, embedding and head untied.

**The share** (``cfg.moe_experts_held`` experts from ``cfg.moe_first_expert``
on; ``params`` holds those experts' kernels alone, the router all E columns
and the bias all E entries): scores, the groups' choice over ALL n_group
groups, the top-k, the renormalisation over all k chosen and the scaling are
computed BEFORE anything is left out; then the sum runs over the held
experts only.  What the absent experts would have added is left out here as
it is in the program, and that partial sum goes on to the next layer.  With
every expert held (``moe_experts_held`` 0) this is the uncut layer.

Departures from the published model, each listed in the configuration
file's ``assumed``: which side of ``layer_group_size`` attends; the q/k
norm of a latent layer (a head's query; of the key, the rope key the
latent's own norm does not cover); the linear layers' normalisation and
output gate; the state's float32; ``rope_interleave`` (split-half here, the
loader's business); the vision tower, the multi-token-prediction layer and
the clamped activation of layers this cut does not reach are left out.
Sequences are right-padded to one length (harmless: every layer is causal
or a recurrence over earlier rows).
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
# How far behind this router's own k-th largest choice an expert the server
# named may lie and still count as a tie that bf16 decided the other way
# (see route).  After the group limit the k = 8 are the largest of the 4
# surviving groups' 4 x 64 = 256 choices, which is openPangu's count: a
# random router's logits are near unit normal, the 8th and 9th of 256 stand
# near z = 1.83 (density 0.074), 1 / (256 x 0.074) = 0.053 apart in logits,
# and the sigmoid's slope there (0.119) makes that 0.0063 in scores.  0.1 in
# logits, where every sound pick of Mellum 2, K-EXAONE and openPangu lay
# within 0.05 on the chip, is 0.012 in these scores: two mean gaps.
TIE = 0.012
# The same for a GROUP's score, the sum of its two largest choices of 64
# (near z = 2.15 and 1.75, slopes 0.094 and 0.127): 0.1 in each logit is
# 0.022 in the sum, against 0.02 between the 4th and 5th of 8 group scores
# (each sum's standard deviation over random columns is about 0.06), so a
# bf16 router decides one token-layer in a few the other way and the
# server's groups have to be replayable too.
GROUP_TIE = 0.022

# config.json key -> ModelConfig field, beyond the harness's own lists
# (which hold the widths, the latent sizes, rope_theta, norm_topk_prob and
# first_k_dense_replace): what else changes the mathematics.
FIXED = {
    "layer_group_size": "layer_group_size",
    "n_group": "moe_n_group",
    "topk_group": "moe_topk_group",
    "routed_scaling_factor": "moe_routed_scaling",
    "moe_router_enable_expert_bias": "moe_router_bias",
    "score_function": "moe_scoring",
    "moe_shared_expert_intermediate_size": "moe_shared_intermediate_size",
    "use_qk_norm": "qk_norm",
    "kda_lower_bound": "lin_gate_lower_bound",
    "kda_safe_gate": "lin_safe_gate",
    "short_conv_kernel_size": "lin_conv_kernel",
    "partial_rotary_factor": "partial_rotary_factor",
    "rotary_dim": "mla_qk_rope_head_dim",
    "gated_attention_proj_granularity_type": "attn_gate_granularity",
    "expert_swiglu_limit_list": "expert_swiglu_limit_list",
    "share_expert_swiglu_limit_list": "share_expert_swiglu_limit_list",
}
# Keys that size nothing that runs: the vision tower's four token ids (the
# tower is not built and the ids lie outside the vocabulary slice); the
# multi-token-prediction layer's switch; and the family's switches for
# variants that no ModelConfig field holds because only ONE value of each
# is built -- the parser (tpuserve/models/config.py _bailing_hybrid_config)
# refuses every other value by name, check_family holds what they imply
# (one key head a query head, a norm a head) and
# tests/benchmark/test_benchmark_ling_metrics.py parses the configuration
# file through that parser.
DESCRIPTIVE = ("image_patch_token", "video_patch_token", "image_start_token",
               "video_start_token", "mtp_use_kda",
               "num_kv_heads_for_linear_attn", "group_norm_size",
               "linear_silu", "use_mla_nope", "use_nGPT",
               "scale_router_input", "value_norm", "up_proj_norm",
               "no_kda_lora", "use_kda_lora")


def check_family(cfg) -> None:
    """Refuse an architecture this file does not describe."""
    wrong = []
    if cfg.norm != "rmsnorm" or cfg.norm_weight_offset or cfg.sandwich_norms \
            or getattr(cfg, "norm_placement", "pre") != "pre":
        wrong.append("norms")
    if not getattr(cfg, "linear_layers", None) \
            or getattr(cfg, "lin_gate", "scalar") != "channel" \
            or not getattr(cfg, "layer_group_size", 0) \
            or not cfg.lin_gate_lower_bound < 0 \
            or cfg.lin_allow_neg_eigval \
            or not (cfg.lin_num_key_heads == cfg.lin_num_value_heads
                    == cfg.num_heads) \
            or not (cfg.lin_key_head_dim == cfg.lin_value_head_dim
                    == cfg.head_dim) \
            or getattr(cfg, "mamba_d_ssm", 0):
        wrong.append("linear layers")
    if not cfg.num_experts or cfg.moe_scoring != "sigmoid" \
            or not cfg.moe_router_bias or cfg.moe_n_group < 2 \
            or not 0 < cfg.moe_topk_group < cfg.moe_n_group \
            or cfg.num_experts % cfg.moe_n_group \
            or cfg.num_experts // cfg.moe_n_group < 2 \
            or not cfg.norm_topk_prob or cfg.moe_shared_experts != 1 \
            or cfg.act != "silu" or cfg.mlp_style != "gated" or cfg.mlp_bias \
            or cfg.mlp_multipliers != (1.0, 1.0) \
            or any(cfg.expert_swiglu_limit_list) \
            or any(cfg.share_expert_swiglu_limit_list):
        wrong.append("experts")
    if cfg.pos != "rope" or cfg.rope_llama3_scaling or cfg.rope_yarn \
            or cfg.rope_scaling_factor != 1.0 or cfg.rope_local_base_freq \
            or getattr(cfg, "rope_full_yarn", None) \
            or getattr(cfg, "rope_windowed_only", False):
        wrong.append("positions")
    if not cfg.mla_kv_lora_rank or cfg.mla_q_lora_rank \
            or cfg.attn_logit_softcapping or cfg.final_logit_softcapping \
            or cfg.query_pre_attn_scalar or cfg.embed_scale_by_sqrt_dim \
            or not cfg.qk_norm or getattr(cfg, "qk_norm_whole", False) \
            or not getattr(cfg, "attn_head_gate", False) \
            or cfg.window_layers is not None or cfg.sliding_window \
            or cfg.tie_word_embeddings or cfg.attention_bias \
            or cfg.attention_in_multiplier != 1.0 \
            or cfg.attention_out_multiplier != 1.0 \
            or cfg.key_multiplier != 1.0:
        wrong.append("attention")
    if cfg.embedding_multiplier != 1.0 or cfg.lm_head_multiplier != 1.0:
        wrong.append("multipliers")
    if wrong:
        raise ValueError(f"{cfg.name}: not the Ling-3.0-flash family "
                         f"({', '.join(wrong)})")


def held(cfg) -> tuple:
    """``(first expert id, experts held)`` of the share the ModelConfig
    states; every expert where it states none."""
    n = getattr(cfg, "moe_experts_held", 0)
    return (cfg.moe_first_expert, n) if n else (0, cfg.num_experts)


def attends(cfg, layer: int) -> bool:
    """Whether ``layer`` is a latent-attention layer."""
    return (layer + 1) % cfg.layer_group_size == 0


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


def _unit(y):
    return y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True) + 1e-6)


@partial(jax.jit, static_argnames=("cfg",))
def _kda_branch(lp, x, cfg):
    """``x + W_o(...)`` for one Kimi-delta layer, the recurrence a row at a
    time."""
    b, t, _ = x.shape
    sp = lp["lin"]
    H, d, W = cfg.num_heads, cfg.head_dim, cfg.lin_conv_kernel
    a = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    qkv = _linear(a, sp["qkv_proj"])            # [Wq a | Wk a | Wv a]
    # depthwise causal convolution: tap W-1 weighs the row itself
    kern = _f32(sp["conv"]["kernel"])                        # (W, C)
    padded = jnp.pad(qkv, ((0, 0), (W - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, i:i + t] * kern[i] for i in range(W)))
    q = _unit(qkv[..., :H * d].reshape(b, t, H, d)) * d ** -0.5
    k = _unit(qkv[..., H * d:2 * H * d].reshape(b, t, H, d))
    v = qkv[..., 2 * H * d:].reshape(b, t, H, d)
    beta = jax.nn.sigmoid(_linear(a, sp["b_proj"]))          # (b, t, H)
    logit = (_linear(a, sp["f_proj"]) + _f32(sp["dt_bias"])).reshape(
        b, t, H, d)
    decay = jnp.exp(cfg.lin_gate_lower_bound * jax.nn.sigmoid(
        jnp.exp(_f32(sp["A_log"]))[:, None] * logit))        # (b, t, H, d)

    def step(state, inp):                                    # (b, H, dk, dv)
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[..., None] * state          # row d of S times a_t[d]
        sk = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HIGHEST)
        state = state + k_t[..., :, None] \
            * (b_t[..., None] * (v_t - sk))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=HIGHEST)

    seq_first = lambda y: jnp.moveaxis(y, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, H, d, d), jnp.float32),
                        tuple(map(seq_first, (q, k, v, decay, beta))))
    o = _rmsnorm(jnp.moveaxis(o, 0, 1), sp["norm"]["scale"], cfg.norm_eps)
    gate = jax.nn.sigmoid(_linear(a, sp["g_proj"]))
    return x + _linear(o.reshape(b, t, H * d) * gate, sp["o_proj"])


@partial(jax.jit, static_argnames=("cfg",))
def _latent_branch(lp, x, positions, cfg):
    """``x + W_o(gated attention)`` for one latent layer, in its naive
    form, ``HEAD_GROUP`` heads at a time."""
    b, t, _ = x.shape
    h = cfg.num_heads
    rope, rank, vd = (cfg.mla_qk_rope_head_dim, cfg.mla_kv_lora_rank,
                      cfg.mla_v_head_dim)
    nope = cfg.mla_qk_nope_head_dim
    a = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    q = _rmsnorm(_linear(a, lp["q_proj"]).reshape(b, t, h, nope + rope),
                 lp["q_norm"]["scale"], cfg.norm_eps)
    ckv = _linear(a, lp["kv_a_proj"])                  # (b, t, rank + rope)
    c = _rmsnorm(ckv[..., :rank], lp["kv_a_norm"]["scale"], cfg.norm_eps)
    k_r = _rope(_rmsnorm(ckv[..., None, rank:], lp["k_norm"]["scale"],
                         cfg.norm_eps), positions, cfg.rope_theta)  # 1 head
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
    att = jnp.moveaxis(att, 0, 2).reshape(b, t, h, vd)
    att = att * jax.nn.sigmoid(
        _linear(a, lp["attn_gate_proj"]))[..., None]
    return x + _linear(att.reshape(b, t, h * vd), lp["o_proj"])


# one matrix of a dense MLP a program: a float32 copy of one is 63 MB at
# the published widths, and the served model's weights, pool and pages
# fill the chip
_project = jax.jit(_linear)


def _gated_mlp(h, p):
    return _project(jax.nn.silu(_project(h, p["gate_proj"]))
                    * _project(h, p["up_proj"]), p["down_proj"])


def route(lp, h, cfg, served):
    """The router's weight on EVERY expert, held or not, for every row of
    ``h``: zero outside the ``num_experts_per_tok`` chosen, those the
    sigmoid scores divided by their sum (plus 1e-20) and scaled by
    ``routed_scaling_factor``.

    The chosen are the k largest CHOICES (score plus selection bias) inside
    the ``topk_group`` groups whose two largest choices sum highest, except
    on a row whose experts the server named (``served`` (N, k) int32, -1 on
    the other rows).  There (1) a group the server's picks lie in survives
    in place of this router's own lowest if its score is within
    ``GROUP_TIE`` of this router's ``topk_group``-th largest, and (2) the
    chosen are the server's, as long as each lies in a surviving group and
    within ``TIE`` of this router's own k-th largest choice among those
    groups.  Only WHICH groups and experts is taken over, and only at a
    near-tie; the weights are this router's float32 scores.  A named
    expert further behind is a wrong pick, not a tie: the row keeps this
    router's own choice and the comparison shows the difference."""
    n, G = h.shape[0], cfg.moe_n_group
    E = cfg.num_experts
    per = E // G
    scores = jax.nn.sigmoid(_linear(h, lp["router"]))
    choice = scores + _f32(lp["router_bias"]["bias"])[None, :]
    group_score = jnp.sum(jax.lax.top_k(choice.reshape(n, G, per), 2)[0], -1)
    kth_group = jax.lax.top_k(group_score, cfg.moe_topk_group)[0][:, -1:]
    rows = jnp.arange(n)[:, None]
    named_groups = jnp.zeros((n, G + 1), bool).at[
        rows, jnp.where(served >= 0, served // per, G)].set(True)[:, :G]
    near = named_groups & (group_score >= kth_group - GROUP_TIE)
    # the server's near-tied groups first, then this router's own order
    _, gidx = jax.lax.top_k(jnp.where(near, group_score + 1e3, group_score),
                            cfg.moe_topk_group)
    alive = jnp.zeros((n, G), bool).at[rows, gidx].set(True)
    alive = jnp.repeat(alive, per, axis=1)                       # (N, E)
    choice = jnp.where(alive, choice, 0.0)
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    kth = jnp.take_along_axis(choice, idx[:, -1:], axis=1)
    at = jnp.maximum(served, 0)
    named = jnp.take_along_axis(choice, at, axis=1)
    replay = jnp.all((served >= 0) & jnp.take_along_axis(alive, at, axis=1)
                     & (named >= kth - TIE), axis=1, keepdims=True)
    idx = jnp.where(replay, served, idx)
    top = jnp.take_along_axis(scores, idx, axis=1)               # unbiased
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * cfg.moe_routed_scaling
    return jnp.zeros_like(scores).at[rows, idx].set(top)         # (N, E)


@partial(jax.jit, static_argnames=("cfg",))
def _held_experts(lp, h, cfg, served):
    """The held experts' part of the routed sum for rows ``h`` (N, H):
    expert by expert over the HELD ones, one expert's three matrices in
    float32 at a time, each weighted by the router's weight where it was
    chosen and by zero where it was not."""
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
    """``x + MLP(norm(x))``: the dense gated MLP on a layer that has one,
    else the held experts' part of the routed sum plus the shared
    expert."""
    b, t, hidden = x.shape
    h = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg.norm_eps).reshape(-1, hidden)
    if "experts" not in lp:
        y = _gated_mlp(h, lp)
    else:
        y = _held_experts(lp, h, cfg, served.reshape(b * t, -1)) \
            + _gated_mlp(h, lp["shared"])
    return x + y.reshape(b, t, hidden)


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
        x = _latent_branch(lp, x, positions, cfg) if attends(cfg, li) \
            else _kda_branch(lp, x, cfg)
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
