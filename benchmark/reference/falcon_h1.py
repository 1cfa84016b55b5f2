"""Plain reference forward pass of the Falcon-H1 family: attention heads and
Mamba-2 state-space heads side by side in every layer.

What decides ``correct`` for a Falcon-H1 configuration.  The published
architecture (HF ``modeling_falcon_h1``; sizes from ``config.json``)
written in straightforward ``jax.numpy``: float32 throughout, every matrix
product at ``precision="highest"``, the state-space recurrence as the
plain token-by-token loop (``lax.scan`` over positions — no chunks, no
cache, no kernel).  It shares no code with ``tpuserve``; it takes the
engine's parameter tree only because the weights must be the same.
Weights are cast to float32 one matrix at a time inside the product that
uses them, and the head is applied in slices of the vocabulary, so nothing
is ever copied whole.

A layer, with ``x`` the residual stream (``H`` state-space heads of size
``P``, ``G`` groups, state size ``N``, ``d = H P``)::

    u = RMSNorm(x; w_in)
    attention   q = Wq(u a_in), k = Wk(u a_in) key_mult, v = Wv(u a_in)
                rotary on the whole head (split-half), causal GQA,
                a = Wo(attn) a_out
    mixer       p = W_in(u s_in) * mup, mup constant on each of the slices
                [z: d | x: d | B: G N | C: G N | dt: H] (ssm_multipliers)
                xBC = silu(conv1d_causal_depthwise([x | B | C]) + bias)
                dt = softplus(dt + dt_bias), A = -exp(A_log)
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     (head h reads
                y_t = S_t C_t + D x_t                  group h // (H / G))
                y = RMSNorm_grouped(y silu(z); w), inside each of G groups
                m = W_out(y) s_out
    x = x + a + m
    x = x + W_down(silu(W_gate(v) g_mult) W_up(v)) d_mult,  v = RMSNorm(x)

with ``h_0 = E[token] embedding_multiplier`` and ``logits =
W_head(RMSNorm(x)) lm_head_multiplier``, the head untied.

Where the published code decides a point the sentences above leave open:
``mamba_norm_before_gate`` false gates BEFORE the grouped norm
(FalconH1RMSNormGated.forward); the key multiplier is applied before the
rotation (FalconH1Attention.forward: ``k_proj(x) * key_multiplier``); the
mixer's output bias switch is ``projectors_bias``, its input's
``mamba_proj_bias`` (FalconH1Mixer.__init__); ``time_step_limit`` is
(0, inf), so the clamp on dt is the identity.  Sequences are right-padded
to one length: harmless under a causal mask and a causal recurrence.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_SLICE = 16384

# config.json key -> ModelConfig field, beyond the harness's own lists:
# every Falcon-H1 key that sizes the mixer or changes the mathematics.
# ``*_multipliers`` are JSON lists; the fields named here compare equal to
# a list.
FIXED = {
    "mamba_d_ssm": "mamba_d_ssm",
    "mamba_n_heads": "mamba_n_heads",
    "mamba_d_head": "mamba_d_head",
    "mamba_d_state": "mamba_d_state",
    "mamba_n_groups": "mamba_n_groups",
    "mamba_d_conv": "mamba_d_conv",
    "mamba_chunk_size": "mamba_chunk_size",
    "mamba_conv_bias": "mamba_conv_bias",
    "mamba_proj_bias": "mamba_proj_bias",
    "projectors_bias": "mamba_out_bias",
    "mamba_rms_norm": "mamba_rms_norm",
    "mamba_norm_before_gate": "mamba_norm_before_gate",
    "mlp_bias": "mlp_bias",
    "embedding_multiplier": "embedding_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
    "key_multiplier": "key_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "mlp_multipliers": "mlp_multiplier_list",
    "ssm_multipliers": "ssm_multiplier_list",
    "rope_scaling": "rope_llama3_scaling",       # null: none is applied
}
# Keys that size nothing once the ones above are given: ``mamba_expand``
# and ``mlp_expansion_factor`` are how d_ssm and intermediate_size were
# derived; ``attn_layer_indices`` null and ``mamba_use_mlp`` true are the
# only values the program accepts (models/config.py rejects the others).
DESCRIPTIVE = ("num_logits_to_keep", "attn_layer_indices", "mamba_expand",
               "mlp_expansion_factor", "mamba_use_mlp")


def check_family(cfg) -> None:
    """Refuse an architecture this file does not describe."""
    wrong = []
    if not getattr(cfg, "mamba_d_ssm", 0):
        wrong.append("no state-space heads")
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
            or cfg.qk_norm or cfg.sliding_window is not None \
            or cfg.tie_word_embeddings:
        wrong.append("attention")
    if wrong:
        raise ValueError(f"{cfg.name}: not the Falcon-H1 family "
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


def _rope(x, positions, theta):
    """x: (B, T, heads, D).  Split-half rotation: feature i pairs with
    feature i + D/2, frequency theta ** (-2i / D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv     # (B, T, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(lp, u, positions, cfg):
    b, t, _ = u.shape
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    u = u * cfg.attention_in_multiplier
    q = _linear(u, lp["q_proj"]).reshape(b, t, hq, d)
    k = _linear(u, lp["k_proj"]).reshape(b, t, hkv, d) * cfg.key_multiplier
    v = _linear(u, lp["v_proj"]).reshape(b, t, hkv, d)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        * (d ** -0.5)
    mask = positions[:, None, :] <= positions[:, :, None]
    scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST)
    return _linear(att.reshape(b, t, hq * d), lp["o_proj"]) \
        * cfg.attention_out_multiplier


def _mixer(sp, u, cfg):
    b, t, _ = u.shape
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    G, d, W = cfg.mamba_n_groups, cfg.mamba_d_ssm, cfg.mamba_d_conv
    gn = G * N
    mup = jnp.concatenate([
        jnp.full((w,), m, jnp.float32) for w, m in
        zip((d, d, gn, gn, H), cfg.ssm_multipliers)])
    p = _linear(u * cfg.ssm_in_multiplier, sp["in_proj"]) * mup
    z, xbc, dt = p[..., :d], p[..., d:2 * d + 2 * gn], p[..., 2 * d + 2 * gn:]
    # depthwise causal convolution: tap W-1 weighs the row itself
    kern = _f32(sp["conv"]["kernel"])                        # (W, C)
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * kern[i] for i in range(W))
    if cfg.mamba_conv_bias:
        conv = conv + _f32(sp["conv"]["bias"])
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d].reshape(b, t, H, P)
    bm = jnp.repeat(xbc[..., d:d + gn].reshape(b, t, G, N), H // G, axis=2)
    cm = jnp.repeat(xbc[..., d + gn:].reshape(b, t, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + _f32(sp["dt_bias"]))           # (b, t, H)
    a = -jnp.exp(_f32(sp["A_log"]))                          # (H,)

    def step(state, inp):                                    # (b, H, P, N)
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    seq_first = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), jnp.float32),
                        (seq_first(x), seq_first(bm), seq_first(cm),
                         seq_first(dt)))
    y = jnp.moveaxis(y, 0, 1) + _f32(sp["D"])[:, None] * x   # (b, t, H, P)
    y = y.reshape(b, t, d)
    gate = jax.nn.silu(z)
    if cfg.mamba_rms_norm:
        if not cfg.mamba_norm_before_gate:
            y = y * gate
        g = y.reshape(b, t, G, d // G)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + cfg.norm_eps)
        y = g.reshape(b, t, d) * _f32(sp["norm"]["scale"])
        if cfg.mamba_norm_before_gate:
            y = y * gate
    else:
        y = y * gate
    return _linear(y, sp["out_proj"]) * cfg.ssm_out_multiplier


@partial(jax.jit, static_argnames=("cfg",))
def _mix(lp, x, positions, cfg):
    """The attention and mixer branches on one normed input; returns the
    residual stream after them and the MLP's normed input."""
    u = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    x = x + _attention(lp, u, positions, cfg) + _mixer(lp["ssm"], u, cfg)
    return x, _rmsnorm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)


# one MLP matrix a program: at the published widths a float32 copy of one
# is 440 MB, and three alive at once beside the served model's weights and
# caches would not fit the chip
_project = jax.jit(_linear)


def _layer(lp, x, positions, cfg):
    x, v = _mix(lp, x, positions, cfg)
    gate = jax.nn.silu(_project(v, lp["gate_proj"]) * cfg.mlp_multipliers[0])
    return x + _project(gate * _project(v, lp["up_proj"]), lp["down_proj"]) \
        * cfg.mlp_multipliers[1]


@jax.jit
def _head_slice(h, w_slice):
    """h (N, H) against a slice of the untied head (H, rows)."""
    return jnp.matmul(h, _f32(w_slice), precision=HIGHEST)


def hidden_states(params, cfg, tokens):
    """tokens (B, T) int32 -> final-normed hidden states (B, T, H)."""
    check_family(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x = _f32(params["embed"]["weight"][tokens]) * cfg.embedding_multiplier
    for lp in params["layers"]:
        x = _layer(lp, x, positions, cfg)
    return _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)


def logits_at(params, cfg, tokens, rows):
    """Logits over the vocabulary after the positions ``rows`` (a list of
    (sequence, position) pairs): (len(rows), V) float32."""
    h = hidden_states(params, cfg, tokens)
    seq = jnp.asarray([r[0] for r in rows], jnp.int32)
    pos = jnp.asarray([r[1] for r in rows], jnp.int32)
    h = h[seq, pos]                                          # (N, H)
    head = params["lm_head"]["kernel"]                       # (H, V)
    parts = [_head_slice(h, head[:, lo:min(lo + VOCAB_SLICE, cfg.vocab_size)])
             for lo in range(0, cfg.vocab_size, VOCAB_SLICE)]
    return jnp.concatenate(parts, axis=-1) * cfg.lm_head_multiplier


def logprobs_at(params, cfg, tokens, rows):
    return jax.nn.log_softmax(logits_at(params, cfg, tokens, rows), axis=-1)


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
