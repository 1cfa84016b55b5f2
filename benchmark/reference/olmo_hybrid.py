"""Plain reference forward pass of the Olmo-Hybrid family (``model_type``
``olmo_hybrid``): gated delta-rule linear-attention layers and full
attention layers in one dense decoder, by ``layer_types``.

What decides ``correct`` for an Olmo-Hybrid configuration.  The published
sizes (``config.json`` of ``allenai/Olmo-Hybrid-7B``: ``layer_types``, the
``linear_*`` keys, 30 attention heads on a hidden size of 3,840) written in
straightforward ``jax.numpy``: float32 throughout, every matrix product at
``precision="highest"``, the linear layers' recurrence as the plain
token-by-token loop (``lax.scan`` over positions -- no chunks, no cache, no
kernel).  It shares no code with ``tpuserve``; it takes the engine's
parameter tree only because the weights must be the same, and the
``ModelConfig`` it is handed for the sizes.  Weights are cast to float32 one
matrix at a time inside the product that uses them, and the head is applied
in slices of the vocabulary, so nothing is ever copied whole.

With ``x`` the residual stream, ``norm`` RMSNorm, ``H`` linear heads with
keys of ``dk`` and values of ``dv``:

    linear_attention layer (arXiv:2412.06464; beta doubled, arXiv:2411.12537)
        [q | k | v] = silu(conv1d_causal_depthwise([Wq x | Wk x | Wv x]))
                      width linear_conv_kernel_dim, the last tap on the row
                      itself, no bias
        per head h:  q_h <- q_h / |q_h| * dk^-1/2,  k_h <- k_h / |k_h|
                     (|y| = sqrt(sum y^2 + 1e-6))
        beta_h  = sigmoid(w_b,h . x), times 2 under linear_allow_neg_eigval
        alpha_h = exp(-exp(A_log_h) * softplus(w_a,h . x + dt_bias_h))
        S_h,t = alpha_h,t S_h,t-1
                + beta_h,t k_h,t (v_h,t - alpha_h,t S_h,t-1^T k_h,t)^T
        o_h,t = S_h,t^T q_h,t                      S in R^(dk x dv), S_0 = 0
        m = Wo(norm_over_each_head's_dv(o; w) * silu(Wg x))
    full_attention layer
        q = norm(Wq x; w_q), k = norm(Wk x; w_k)    over the WHOLE projection
        v = Wv x; heads of hidden / heads; NO rotation; causal softmax at
        head size^-1/2;  m = Wo attn
    x = x + norm(m; w_1)
    x = x + norm(W_down(silu(W_gate x) * W_up x); w_2)

with ``logits = W_head norm(x)``, embedding and head untied.

Departures and assumptions, each listed in the configuration file's
``assumed``: ``config.json`` does not state the head size of the attention
layers (hidden / heads), whether they rotate (``rope_parameters.rope_theta``
is null: they do not), where the norms stand and what the q/k norm spans
(the OLMo 2 / 3 family's: on each branch's output; the whole projection),
nor the linear layer's parameterisation (``A_log`` and ``dt_bias`` a head,
the gate before ``Wo``, as the gated delta-rule's published code has them;
the engine's tree keeps Wq, Wk and Wv side by side as one matrix, the same
numbers).  Each is read HERE from the one ``ModelConfig``
field the program's parser sets for it (``pos``, ``norm_placement``,
``qk_norm_whole``, ``head_dim``, ``lin_allow_neg_eigval``), and
``check_family`` refuses any other value: a reader with the published code
corrects a point in tpuserve/models/config.py ``_olmo_hybrid_config`` and
in the matching line below.  Sequences are right-padded to one length:
harmless under a causal mask and a causal recurrence.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_SLICE = 16384

# config.json key -> ModelConfig field, beyond the harness's own lists:
# every key that sizes a linear layer or says which layers are linear.
# ``layer_types`` is a JSON list and ``rope_parameters`` a JSON object; the
# properties named here return a list and a dict.
FIXED = {
    "layer_types": "layer_types",
    "linear_num_key_heads": "lin_num_key_heads",
    "linear_num_value_heads": "lin_num_value_heads",
    "linear_key_head_dim": "lin_key_head_dim",
    "linear_value_head_dim": "lin_value_head_dim",
    "linear_conv_kernel_dim": "lin_conv_kernel",
    "linear_allow_neg_eigval": "lin_allow_neg_eigval",
    "rope_parameters": "rope_parameters",
}
# every other key of this family's config.json is on one of the harness's
# lists already
DESCRIPTIVE = ()


def check_family(cfg) -> None:
    """Refuse an architecture this file does not describe."""
    wrong = []
    if not getattr(cfg, "linear_layers", None):
        wrong.append("no linear-attention layers")
    if cfg.norm != "rmsnorm" or cfg.norm_weight_offset \
            or getattr(cfg, "norm_placement", "pre") != "post" \
            or cfg.sandwich_norms:
        wrong.append("norm")
    if cfg.mlp_style != "gated" or cfg.act != "silu" or cfg.num_experts \
            or cfg.mlp_bias:
        wrong.append("mlp")
    if cfg.pos != "none":
        wrong.append("positions")
    if cfg.mla_kv_lora_rank or cfg.attn_logit_softcapping \
            or cfg.final_logit_softcapping or cfg.query_pre_attn_scalar \
            or cfg.embed_scale_by_sqrt_dim or cfg.attention_bias \
            or not (cfg.qk_norm and getattr(cfg, "qk_norm_whole", False)) \
            or cfg.sliding_window is not None or cfg.tie_word_embeddings \
            or getattr(cfg, "mamba_d_ssm", 0):
        wrong.append("attention")
    if wrong:
        raise ValueError(f"{cfg.name}: not the Olmo-Hybrid family "
                         f"({', '.join(wrong)})")


def _f32(x):
    return x.astype(jnp.float32)


def _linear(x, p):
    return jnp.matmul(x, _f32(p["kernel"]), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _attention(lp, x, positions, cfg):
    b, t, _ = x.shape
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _rmsnorm(_linear(x, lp["q_proj"]), lp["q_norm"]["scale"],
                 cfg.norm_eps).reshape(b, t, hq, d)
    k = _rmsnorm(_linear(x, lp["k_proj"]), lp["k_norm"]["scale"],
                 cfg.norm_eps).reshape(b, t, hkv, d)
    v = _linear(x, lp["v_proj"]).reshape(b, t, hkv, d)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        * (d ** -0.5)
    mask = positions[:, None, :] <= positions[:, :, None]
    scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST)
    return _linear(att.reshape(b, t, hq * d), lp["o_proj"])


def _unit(y):
    return y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True) + 1e-6)


def _linear_attention(sp, x, cfg):
    b, t, _ = x.shape
    H, dk, dv = (cfg.lin_num_value_heads, cfg.lin_key_head_dim,
                 cfg.lin_value_head_dim)
    W = cfg.lin_conv_kernel
    qkv = _linear(x, sp["qkv_proj"])            # [Wq x | Wk x | Wv x]
    # depthwise causal convolution: tap W-1 weighs the row itself
    kern = _f32(sp["conv"]["kernel"])                        # (W, C)
    padded = jnp.pad(qkv, ((0, 0), (W - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, i:i + t] * kern[i] for i in range(W)))
    q = _unit(qkv[..., :H * dk].reshape(b, t, H, dk)) * dk ** -0.5
    k = _unit(qkv[..., H * dk:2 * H * dk].reshape(b, t, H, dk))
    v = qkv[..., 2 * H * dk:].reshape(b, t, H, dv)
    beta = jax.nn.sigmoid(_linear(x, sp["b_proj"]))          # (b, t, H)
    if cfg.lin_allow_neg_eigval:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(_f32(sp["A_log"])) * jax.nn.softplus(
        _linear(x, sp["a_proj"]) + _f32(sp["dt_bias"])))

    def step(state, inp):                                    # (b, H, dk, dv)
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[..., None, None] * state
        sk = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HIGHEST)
        state = state + k_t[..., :, None] \
            * (b_t[..., None] * (v_t - sk))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=HIGHEST)

    seq_first = lambda y: jnp.moveaxis(y, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, H, dk, dv), jnp.float32),
                        tuple(map(seq_first, (q, k, v, alpha, beta))))
    o = _rmsnorm(jnp.moveaxis(o, 0, 1), sp["norm"]["scale"], cfg.norm_eps)
    gate = jax.nn.silu(_linear(x, sp["g_proj"]))
    return _linear(o.reshape(b, t, H * dv) * gate, sp["o_proj"])


@partial(jax.jit, static_argnames=("cfg", "linear"))
def _mix(lp, x, positions, cfg, linear):
    """The residual stream after one layer's mixer and its norm."""
    m = _linear_attention(lp["lin"], x, cfg) if linear \
        else _attention(lp, x, positions, cfg)
    return x + _rmsnorm(m, lp["post_attn_norm"]["scale"], cfg.norm_eps)


# one MLP matrix a program: at the published widths a float32 copy of one
# is 169 MB, and the served model's weights, state and pages fill the chip
_project = jax.jit(_linear)


def _layer(lp, x, positions, cfg, linear):
    x = _mix(lp, x, positions, cfg, linear)
    m = _project(jax.nn.silu(_project(x, lp["gate_proj"]))
                 * _project(x, lp["up_proj"]), lp["down_proj"])
    return x + _rmsnorm(m, lp["post_mlp_norm"]["scale"], cfg.norm_eps)


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
    x = _f32(params["embed"]["weight"][tokens])
    kinds = cfg.layer_types
    for i, lp in enumerate(params["layers"]):
        x = _layer(lp, x, positions, cfg, kinds[i] == "linear_attention")
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
    return jnp.concatenate(parts, axis=-1)


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
