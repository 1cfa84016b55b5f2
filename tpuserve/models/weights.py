"""Parameter initialisation and HuggingFace checkpoint loading.

The reference downloads weights by delegating ``--download-model
Qwen/Qwen3-0.6B`` to the llm-d installer and stores them on PVCs
(reference: llm-d-deploy.yaml:176-215, kubernetes-single-node.yaml:375-401).
Here loading is in-framework: safetensors -> JAX pytree matching
``tpuserve.models.transformer`` param layout, with the HF->tpuserve name
mapping per model family (including Phi-3's fused qkv/gate_up and OPT's
decoder naming).  ``init_params`` provides random weights for tests/benches
in air-gapped environments.
"""

from __future__ import annotations

import glob
import json
import os
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.models.config import MIXER_LINEAR, ModelConfig

Params = Any


def param_nbytes(params) -> int:
    """Total bytes of a parameter pytree as actually materialized —
    quantized trees count their int8 values + scales, not the fp
    estimate.  The one byte-count used by the KV-cache auto-sizer
    (Engine._auto_num_blocks), the model pool and the runner's gauges."""
    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree_util.tree_leaves(params))


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# Random initialisation (tests, CPU smoke, air-gapped benches)
# --------------------------------------------------------------------------

class _Draw:
    """Leaf factory for :func:`init_params`: every random leaf draws from
    its own fold of the key, in creation order."""

    def __init__(self, cfg: ModelConfig, key):
        self.cfg, self.key, self.n = cfg, key, 0
        self.dtype = param_dtype(cfg)
        # Families with a norm-weight offset (Gemma: effective scale =
        # 1 + w) init the stored weight so the EFFECTIVE gain is 1 — plain
        # ones would compound a 2x gain per norm through every layer on
        # random-init paths.
        self.norm_init = 1.0 - cfg.norm_weight_offset

    def normal(self, shape, std: float):
        self.n += 1
        k = jax.random.fold_in(self.key, self.n)
        return (jax.random.normal(k, shape, jnp.float32) * std
                ).astype(self.dtype)

    def dense(self, n_in: int, n_out: int, bias: bool, mult: float = 1.0):
        """``mult``: a fixed multiplier the forward pass puts on this
        projection (Falcon-H1's muP scalars).  The draw is of the
        EFFECTIVE weight at the usual scale, stored divided by ``mult``:
        left at the plain scale, a head behind ``lm_head_multiplier``
        1/128 would make every logit ~0.01 and the output uniform, and
        no comparison of log-probabilities could tell a right trunk from
        a wrong one."""
        p = {"kernel": self.normal((n_in, n_out), n_in ** -0.5 / mult)}
        if bias:
            p["bias"] = jnp.zeros((n_out,), self.dtype)
        return p

    def norm(self, n: int):
        p = {"scale": jnp.full((n,), self.norm_init, self.dtype)}
        if self.cfg.norm == "layernorm":
            p["bias"] = jnp.zeros((n,), self.dtype)
        return p


def _shard(tree, cfg: ModelConfig, mesh):
    """Constrain a param (sub)tree to its tensor-parallel shardings inside
    the init program, so each device generates only its own shards."""
    if mesh is None:
        return tree
    from tpuserve.parallel.sharding import param_shardings
    return jax.lax.with_sharding_constraint(
        tree, param_shardings(tree, cfg, mesh))


@partial(jax.jit, static_argnames=("cfg", "dense_mlp", "mesh", "linear"))
def _init_layer(key, cfg: ModelConfig, dense_mlp: bool, mesh=None,
                linear: bool = False) -> Params:
    """One transformer layer; ``dense_mlp``: an MoE model's dense layer;
    ``linear``: a linear-attention layer (ModelConfig.layer_mixer)."""
    d = _Draw(cfg, key)
    h = cfg.hidden_size
    if linear:
        lp = {"lin": _init_lin(d, cfg)}
        if cfg.norm_placement != "post":    # pre-norm: on each branch's input
            lp["attn_norm"], lp["mlp_norm"] = d.norm(h), d.norm(h)
    elif cfg.is_mla:
        # DeepSeek MLA: low-rank q (optional), compressed-KV latent +
        # shared roped key, per-head up-projections packed in kv_b_proj
        lp = {
            "attn_norm": d.norm(h),
            "kv_a_proj": d.dense(h, cfg.mla_latent_dim, cfg.attention_bias),
            "kv_a_norm": d.norm(cfg.mla_kv_lora_rank),
            "kv_b_proj": d.dense(
                cfg.mla_kv_lora_rank,
                cfg.num_heads * (cfg.mla_qk_nope_head_dim
                                 + cfg.mla_v_head_dim), False),
            "o_proj": d.dense(cfg.num_heads * cfg.mla_v_head_dim, h,
                              cfg.attention_bias),
            "mlp_norm": d.norm(h),
        }
        if cfg.mla_q_lora_rank:
            lp["q_a_proj"] = d.dense(h, cfg.mla_q_lora_rank,
                                     cfg.attention_bias)
            lp["q_a_norm"] = d.norm(cfg.mla_q_lora_rank)
            lp["q_b_proj"] = d.dense(cfg.mla_q_lora_rank, cfg.q_size, False)
        else:
            lp["q_proj"] = d.dense(h, cfg.q_size, False)
        if cfg.attn_head_gate:              # a sigmoid gate a head
            lp["attn_gate_proj"] = d.dense(h, cfg.num_heads, False)
    else:
        lp = {
            "attn_norm": d.norm(h),
            "q_proj": d.dense(h, cfg.q_size, cfg.attention_bias,
                              cfg.attention_in_multiplier),
            "k_proj": d.dense(h, cfg.kv_size, cfg.attention_bias,
                              cfg.attention_in_multiplier
                              * cfg.key_multiplier),
            "v_proj": d.dense(h, cfg.kv_size, cfg.attention_bias,
                              cfg.attention_in_multiplier),
            "o_proj": d.dense(cfg.q_size, h,
                              cfg.attention_bias and cfg.pos == "learned",
                              cfg.attention_out_multiplier),
            "mlp_norm": d.norm(h),
        }
    if cfg.has_ssm:
        lp["ssm"] = _init_ssm(d, cfg)
    if cfg.qk_norm and not linear:
        # a head's width, or the whole projection's (qk_norm_whole)
        # (a latent layer: each head's query, and the ONE key the heads
        # share that the latent's own norm does not cover, the rope key)
        qn, kn = ((cfg.q_size, cfg.kv_size) if cfg.qk_norm_whole
                  else (cfg.qk_head_dim, cfg.mla_qk_rope_head_dim)
                  if cfg.is_mla else (cfg.head_dim, cfg.head_dim))
        lp["q_norm"] = {"scale": jnp.full((qn,), d.norm_init, d.dtype)}
        lp["k_norm"] = {"scale": jnp.full((kn,), d.norm_init, d.dtype)}
    if cfg.norm_placement == "post":
        # each branch's norm stands on its output alone
        lp.pop("attn_norm", None)
        lp.pop("mlp_norm", None)
    if cfg.sandwich_norms or cfg.norm_placement == "post":
        lp["post_attn_norm"] = d.norm(h)
        lp["post_mlp_norm"] = d.norm(h)
    if cfg.num_experts and not dense_mlp:
        ei = cfg.expert_intermediate_size
        # the router is as wide as the model has experts; the stacks hold
        # the experts this process holds (a share: cfg.moe_experts_held)
        E = cfg.num_experts

        def experts(n_in, n_out):
            return {"kernel": d.normal((cfg.moe_local_experts, n_in, n_out),
                                       n_in ** -0.5)}
        lp["router"] = d.dense(h, E, False)
        if cfg.moe_router_bias:
            # e_score_correction_bias: selection-only, stays f32
            lp["router_bias"] = {"bias": jnp.zeros((E,), jnp.float32)}
        lp["experts"] = {"gate_proj": experts(h, ei),
                         "up_proj": experts(h, ei),
                         "down_proj": experts(ei, h)}
        if cfg.moe_shared_experts:
            si = ei * cfg.moe_shared_experts
            lp["shared"] = {"gate_proj": d.dense(h, si, False),
                            "up_proj": d.dense(h, si, False),
                            "down_proj": d.dense(si, h, False)}
    elif cfg.mlp_style == "gated":
        lp["gate_proj"] = d.dense(h, cfg.intermediate_size, cfg.mlp_bias,
                                  cfg.mlp_multipliers[0])
        lp["up_proj"] = d.dense(h, cfg.intermediate_size, cfg.mlp_bias)
        lp["down_proj"] = d.dense(cfg.intermediate_size, h, cfg.mlp_bias,
                                  cfg.mlp_multipliers[1])
    else:
        lp["fc1"] = d.dense(h, cfg.intermediate_size, cfg.mlp_bias)
        lp["fc2"] = d.dense(cfg.intermediate_size, h, cfg.mlp_bias)
    return _shard(lp, cfg, mesh)


def _init_ssm(d: _Draw, cfg: ModelConfig) -> Params:
    """The Mamba-2 mixer of one layer (Falcon-H1).  ``in_proj``'s columns
    are [z | x | B | C | dt], each slice drawn for its own multiplier
    (:meth:`_Draw.dense`); ``A_log`` and ``dt_bias`` as Mamba-2 draws them
    (A in [1, 16], the step dt log-uniform in [1e-3, 1e-1]); ``D``, the
    convolution and its bias random, so that no term of the layer is a
    no-op under random weights."""
    h, hs = cfg.hidden_size, cfg.mamba_n_heads
    cols = [d.normal((h, w), h ** -0.5 / (cfg.ssm_in_multiplier * m))
            for w, m in zip(cfg.mamba_proj_widths, cfg.ssm_multipliers)]
    in_proj = {"kernel": jnp.concatenate(cols, axis=1)}
    if cfg.mamba_proj_bias:
        in_proj["bias"] = jnp.zeros((cfg.mamba_proj_size,), d.dtype)
    d.n += 1
    ka, kd = jax.random.split(jax.random.fold_in(d.key, d.n))
    a = jax.random.uniform(ka, (hs,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(kd, (hs,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    conv = {"kernel": d.normal((cfg.mamba_d_conv, cfg.mamba_conv_dim),
                               cfg.mamba_d_conv ** -0.5)}
    if cfg.mamba_conv_bias:
        conv["bias"] = d.normal((cfg.mamba_conv_dim,), 0.1)
    return {
        "in_proj": in_proj,
        "conv": conv,
        "A_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),    # softplus^-1(dt)
        "D": 1.0 + d.normal((hs,), 0.25).astype(jnp.float32),
        "norm": {"scale": jnp.full((cfg.mamba_d_ssm,), 1.0, d.dtype)},
        "out_proj": d.dense(cfg.mamba_d_ssm, h, cfg.mamba_out_bias,
                            cfg.ssm_out_multiplier),
    }


def _init_lin(d: _Draw, cfg: ModelConfig) -> Params:
    """The gated delta-rule mixer of one linear-attention layer: the q, k
    and v projections as one matrix, separate gate, decay (``a_proj``; the
    channel gate's ``f_proj``) and step-size (``b_proj``) projections, one depthwise convolution over
    [q | k | v], a per-head
    norm weight shared by the heads.  ``A_log`` and ``dt_bias`` as the
    published gated delta-rule code draws them (A in [0, 16], the step dt
    log-uniform in [1e-3, 1e-1]); the convolution random, so that no term
    of the layer is a no-op under random weights."""
    h, hs = cfg.hidden_size, cfg.lin_num_value_heads
    dk, dv = cfg.lin_key_head_dim, cfg.lin_value_head_dim
    # the two scalars a head read the residual stream AS IT IS where the
    # norms stand on the branches' outputs, and every layer adds two terms
    # of unit size to it: drawn for a stream of the last layer's size, so
    # that the decay's exponent and the step size's logit stay of order
    # one.  At the plain scale a deep layer's decay wipes a head's state on
    # one row in a few (exp(-16 x softplus(5))): its output is then one
    # row's (k . q) v, near zero as often as not, the per-head norm blows
    # the rounding of a bf16 trunk up, and no comparison of
    # log-probabilities can tell a right trunk from a wrong one
    stream = (2 * cfg.num_layers) ** 0.5 \
        if cfg.norm_placement == "post" else 1.0
    d.n += 1
    ka, kd = jax.random.split(jax.random.fold_in(d.key, d.n))
    if cfg.lin_gate == "channel":
        # Kimi-delta: the decay's projection is a value a key channel
        # (``f_proj``, full rank) under a bias a channel and A a head.
        # exp(A_log) in [0.5, 1.5] scales the gate's logit and the bias, in
        # [-6, -2], keeps it NEGATIVE under a unit-normal projection: the
        # gate's sigmoid stands near 0.02 (0.38 to 1e-5 over the draws), a
        # decay of exp(-0.09) a row at the published bound of -5, so a
        # state remembers some ten rows (one to thousands by channel), as a
        # trained layer's does.  Around zero the sigmoid would stand at a
        # half and every channel forget inside one row: a head's output is
        # then one row's (k . q) u, near zero as often as not, the per-head
        # norm blows the rounding of a bf16 trunk up (_init_lin's note on
        # the scalar gate) and the probe's log-probabilities read 0.06-0.08
        # off on a sound trunk (PERF.md section 6, PR 55)
        decay, width = "f_proj", hs * dk
        a_log = jnp.log(jax.random.uniform(ka, (hs,), jnp.float32, 0.5, 1.5))
        dt_bias = jax.random.uniform(kd, (width,), jnp.float32, -6.0, -2.0)
    else:
        decay, width = "a_proj", hs
        a_log = jnp.log(jax.random.uniform(ka, (hs,), jnp.float32, 1e-2,
                                           16.0))
        dt = jnp.exp(jax.random.uniform(kd, (hs,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        dt_bias = dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
    return {
        # Wq, Wk, Wv side by side, [q | k | v] as the convolution takes them
        "qkv_proj": d.dense(h, cfg.lin_conv_dim, False),
        "g_proj": d.dense(h, hs * dv, False),
        decay: d.dense(h, width, False, stream),
        "b_proj": d.dense(h, hs, False, stream),
        "conv": {"kernel": d.normal((cfg.lin_conv_kernel, cfg.lin_conv_dim),
                                    cfg.lin_conv_kernel ** -0.5)},
        "A_log": a_log,
        "dt_bias": dt_bias,
        "norm": {"scale": jnp.full((dv,), d.norm_init, d.dtype)},
        "o_proj": d.dense(hs * dv, h, False),
    }


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def _init_head(key, cfg: ModelConfig, mesh=None) -> Params:
    """Everything outside the layer stack: embeddings, final norm, head."""
    d = _Draw(cfg, key)
    h = cfg.hidden_size
    params = {"embed": {"weight": d.normal(
        (cfg.vocab_size, h), 0.02 / cfg.embedding_multiplier)},
              "final_norm": d.norm(h)}
    if cfg.pos == "learned":
        params["pos_embed"] = {"weight": d.normal(
            (cfg.max_position_embeddings + cfg.learned_pos_offset, h), 0.02)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = d.dense(h, cfg.vocab_size, False,
                                    cfg.lm_head_multiplier)
    return _shard(params, cfg, mesh)


def init_params(cfg: ModelConfig, seed: int = 0, mesh=None) -> Params:
    """Random-normal initialised params in the transformer's pytree layout.

    The leaves are generated ON the device by two jitted programs (one per
    layer structure, one for the head).  With ``mesh`` each leaf is born
    in its tensor-parallel shards (parallel/sharding.py) — never whole on
    one device, so a model that needs tp to fit (Llama-3.1-8B on 16 GB
    chips) can be initialised at all.  The values depend on ``(cfg,
    seed)`` alone, not on the placement."""
    cfg.require_built()
    key = jax.random.key(seed)
    params = _init_head(jax.random.fold_in(key, cfg.num_layers), cfg, mesh)
    # (``linear`` is passed only where it is set, so that a model without
    # such layers calls, and caches, the program it always did)
    params["layers"] = [
        _init_layer(jax.random.fold_in(key, li), cfg,
                    cfg.moe_layer_is_dense(li), mesh,
                    **({"linear": True}
                       if cfg.layer_mixer(li) == MIXER_LINEAR else {}))
        for li in range(cfg.num_layers)]
    return params


# --------------------------------------------------------------------------
# HF checkpoint loading
# --------------------------------------------------------------------------

def _read_safetensors(ckpt_dir: str) -> dict[str, jnp.ndarray]:
    """Load all tensors from single-file or index-sharded safetensors."""
    from safetensors import safe_open
    files = sorted(glob.glob(os.path.join(ckpt_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {ckpt_dir}")
    tensors: dict[str, jnp.ndarray] = {}
    for path in files:
        with safe_open(path, framework="flax") as f:
            for name in f.keys():
                tensors[name] = f.get_tensor(name)
    return tensors


def _t(w: jnp.ndarray, dtype) -> jnp.ndarray:
    """HF stores Linear as (out, in); transformer uses (in, out)."""
    return jnp.asarray(w, dtype=dtype).T


def _mla_deinterleave(p: dict, cfg, heads: int, head_width: int) -> dict:
    """Bake DeepSeek's interleaved-rope channel order out of a projection.

    HF applies rope to DeepSeek checkpoints with GPT-J channel pairing
    (apply_rotary_pos_emb_interleave: view(d/2, 2).transpose) — a pure
    permutation of the rope-dim channels.  Since those channels come
    straight out of this weight, permuting the weight's output channels
    once at load makes the NeoX split-half rope (ops/rope.py) exact, at
    zero runtime cost.  ``heads``/``head_width``: the projection's output
    is [heads x head_width] with the LAST mla_qk_rope_head_dim channels
    of each head being the rope slice (kv_a_proj: one latent+rope row).
    """
    if not cfg.mla_rope_interleave:
        return p
    d = cfg.mla_qk_rope_head_dim
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    idx = np.arange(heads * head_width)
    for hh in range(heads):
        lo = hh * head_width + head_width - d
        idx[lo:lo + d] = lo + perm
    out = {"kernel": p["kernel"][:, idx]}
    if "bias" in p:
        out["bias"] = p["bias"][idx]
    return out


def load_hf_checkpoint(cfg: ModelConfig, ckpt_dir: str) -> Params:
    """Convert an HF checkpoint directory into the transformer param pytree."""
    cfg.require_built()
    raw = _read_safetensors(ckpt_dir)
    dtype = param_dtype(cfg)
    if cfg.pos == "learned":
        return _load_opt(cfg, raw, dtype)
    if cfg.linear_layers is not None and cfg.lin_gate == "channel":
        return _load_llama_family(cfg, raw, dtype)      # bailing_hybrid
    if cfg.linear_layers is not None:
        return _load_olmo_hybrid(cfg, raw, dtype)
    return _load_llama_family(cfg, raw, dtype)


def _load_olmo_hybrid(cfg: ModelConfig, raw: dict, dtype) -> Params:
    """Olmo-Hybrid.  The tensor names are ASSUMED (the hybrid's own code
    is unseen: benchmark/configs/olmo-hybrid-7b-l16.json ``assumed``):
    the attention layers and the MLP as HF ``modeling_olmo2`` names them
    (norms on each branch's output: ``post_attention_layernorm``,
    ``post_feedforward_layernorm``; ``self_attn.q_norm`` / ``k_norm`` over
    the whole projection), the linear layers under ``linear_attn.`` as the
    published gated delta-rule layer names its own (a convolution each for
    q, k and v, ``(channels, 1, width)``, the last tap weighing the row
    itself: joined here into the one the forward pass runs)."""
    def dense(name):
        return {"kernel": _t(raw[name + ".weight"], dtype)}

    def scale(name):
        return {"scale": jnp.asarray(raw[name + ".weight"], dtype=dtype)}

    layers = []
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        lp = {"post_attn_norm": scale(pre + "post_attention_layernorm"),
              "post_mlp_norm": scale(pre + "post_feedforward_layernorm"),
              **{p: dense(pre + "mlp." + p)
                 for p in ("gate_proj", "up_proj", "down_proj")}}
        if cfg.layer_mixer(i) == MIXER_LINEAR:
            la = pre + "linear_attn."
            lp["lin"] = {
                "qkv_proj": {"kernel": jnp.concatenate([
                    dense(la + p)["kernel"]
                    for p in ("q_proj", "k_proj", "v_proj")], axis=1)},
                **{p: dense(la + p)
                   for p in ("g_proj", "a_proj", "b_proj", "o_proj")},
                "conv": {"kernel": jnp.concatenate([
                    jnp.asarray(raw[la + c + "_conv1d.weight"],
                                dtype=dtype)[:, 0, :].T
                    for c in ("q", "k", "v")], axis=1)},
                "A_log": jnp.asarray(raw[la + "A_log"], jnp.float32),
                "dt_bias": jnp.asarray(raw[la + "dt_bias"], jnp.float32),
                "norm": scale(la + "o_norm")}
        else:
            sa = pre + "self_attn."
            lp.update({p: dense(sa + p) for p in ("q_proj", "k_proj",
                                                  "v_proj", "o_proj")})
            lp["q_norm"], lp["k_norm"] = scale(sa + "q_norm"), \
                scale(sa + "k_norm")
        layers.append(lp)
    return {"embed": {"weight": jnp.asarray(raw["model.embed_tokens.weight"],
                                            dtype=dtype)},
            "layers": layers, "final_norm": scale("model.norm"),
            "lm_head": dense("lm_head")}


def _load_kda(raw: dict, pre: str, dtype) -> Params:
    """One Kimi-delta mixer (bailing_hybrid's linear layers).  The tensor
    names are ASSUMED (the family's own code is unseen:
    benchmark/configs/ling-3.0-flash-vl-ep8-l12.json ``assumed``): the
    published Kimi Delta Attention layer's (``q_proj`` .. ``o_proj``, a
    depthwise ``*_conv1d`` each for q, k and v, ``(channels, 1, width)``
    with the last tap on the row itself, ``f_proj`` the decay's full-rank
    projection, ``o_norm`` the per-head norm), joined as
    :func:`_load_olmo_hybrid` joins Olmo-Hybrid's."""
    def dense(name):
        return {"kernel": _t(raw[pre + name + ".weight"], dtype)}

    return {
        "qkv_proj": {"kernel": jnp.concatenate([
            dense(p)["kernel"] for p in ("q_proj", "k_proj", "v_proj")],
            axis=1)},
        **{p: dense(p) for p in ("g_proj", "f_proj", "b_proj", "o_proj")},
        "conv": {"kernel": jnp.concatenate([
            jnp.asarray(raw[pre + c + "_conv1d.weight"],
                        dtype=dtype)[:, 0, :].T
            for c in ("q", "k", "v")], axis=1)},
        "A_log": jnp.asarray(raw[pre + "A_log"], jnp.float32),
        "dt_bias": jnp.asarray(raw[pre + "dt_bias"], jnp.float32),
        "norm": {"scale": jnp.asarray(raw[pre + "o_norm.weight"],
                                      dtype=dtype)}}


def _load_falcon_h1_ssm(raw: dict, pre: str, dtype) -> Params:
    """One layer's Mamba-2 mixer from HF ``modeling_falcon_h1`` names.
    ``conv1d.weight`` is (channels, 1, width), the last tap weighing the
    row itself; the scalars a head stay float32."""
    def f32(name):
        return jnp.asarray(raw[pre + name], jnp.float32)

    def dense(name):
        p = {"kernel": _t(raw[pre + name + ".weight"], dtype)}
        if pre + name + ".bias" in raw:
            p["bias"] = jnp.asarray(raw[pre + name + ".bias"], dtype=dtype)
        return p

    conv = {"kernel": jnp.asarray(raw[pre + "conv1d.weight"],
                                  dtype=dtype)[:, 0, :].T}
    if pre + "conv1d.bias" in raw:
        conv["bias"] = jnp.asarray(raw[pre + "conv1d.bias"], dtype=dtype)
    return {"in_proj": dense("in_proj"), "conv": conv,
            "A_log": f32("A_log"), "dt_bias": f32("dt_bias"), "D": f32("D"),
            "norm": {"scale": jnp.asarray(raw[pre + "norm.weight"],
                                          dtype=dtype)},
            "out_proj": dense("out_proj")}


def _load_llama_family(cfg: ModelConfig, raw: dict, dtype) -> Params:
    def get(name):
        return raw[name]

    def dense(name, bias_name=None):
        p = {"kernel": _t(get(name), dtype)}
        if bias_name and bias_name in raw:
            p["bias"] = jnp.asarray(raw[bias_name], dtype=dtype)
        return p

    def norm_scale(name):
        return {"scale": jnp.asarray(get(name), dtype=dtype)}

    layers = []
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        linear = cfg.layer_mixer(i) == MIXER_LINEAR
        lp = {"attn_norm": norm_scale(pre + "input_layernorm.weight")}
        if linear:
            lp["lin"] = _load_kda(raw, pre + "self_attn.", dtype)
        else:
            lp["o_proj"] = dense(pre + "self_attn.o_proj.weight")
        if cfg.sandwich_norms:
            # Gemma2: post_attention_layernorm wraps the ATTENTION OUTPUT;
            # the MLP pre-norm is pre_feedforward_layernorm
            # (openPangu spells the last two pre_mlp_layernorm and
            # post_mlp_layernorm: whichever pair the checkpoint holds)
            lp["post_attn_norm"] = norm_scale(
                pre + "post_attention_layernorm.weight")
            ff = ("pre_mlp_layernorm", "post_mlp_layernorm") \
                if pre + "pre_mlp_layernorm.weight" in raw else (
                    "pre_feedforward_layernorm", "post_feedforward_layernorm")
            lp["mlp_norm"] = norm_scale(pre + ff[0] + ".weight")
            lp["post_mlp_norm"] = norm_scale(pre + ff[1] + ".weight")
        elif cfg.has_ssm:                                       # Falcon-H1
            lp["mlp_norm"] = norm_scale(pre + "pre_ff_layernorm.weight")
            lp["ssm"] = _load_falcon_h1_ssm(raw, pre + "mamba.", dtype)
        else:
            lp["mlp_norm"] = norm_scale(
                pre + "post_attention_layernorm.weight")
        if linear:
            pass                            # no attention projections
        elif cfg.is_mla:                                        # DeepSeek MLA
            rope_d = cfg.mla_qk_rope_head_dim
            lp["kv_a_proj"] = _mla_deinterleave(
                dense(pre + "self_attn.kv_a_proj_with_mqa.weight",
                      pre + "self_attn.kv_a_proj_with_mqa.bias"),
                cfg, heads=1, head_width=cfg.mla_latent_dim)
            lp["kv_a_norm"] = norm_scale(
                pre + "self_attn.kv_a_layernorm.weight")
            lp["kv_b_proj"] = dense(pre + "self_attn.kv_b_proj.weight")
            if cfg.mla_q_lora_rank:
                lp["q_a_proj"] = dense(pre + "self_attn.q_a_proj.weight",
                                       pre + "self_attn.q_a_proj.bias")
                lp["q_a_norm"] = norm_scale(
                    pre + "self_attn.q_a_layernorm.weight")
                lp["q_b_proj"] = _mla_deinterleave(
                    dense(pre + "self_attn.q_b_proj.weight"), cfg,
                    heads=cfg.num_heads, head_width=cfg.qk_head_dim)
            else:
                lp["q_proj"] = _mla_deinterleave(
                    dense(pre + "self_attn.q_proj.weight"), cfg,
                    heads=cfg.num_heads, head_width=cfg.qk_head_dim)
        elif pre + "self_attn.qkv_proj.weight" in raw:          # Phi-3 fused qkv
            qkv = jnp.asarray(raw[pre + "self_attn.qkv_proj.weight"], dtype=dtype)
            q, k, v = jnp.split(qkv, [cfg.q_size, cfg.q_size + cfg.kv_size], axis=0)
            lp["q_proj"], lp["k_proj"], lp["v_proj"] = ({"kernel": q.T}, {"kernel": k.T}, {"kernel": v.T})
        else:
            for proj in ("q", "k", "v"):
                lp[f"{proj}_proj"] = dense(pre + f"self_attn.{proj}_proj.weight",
                                           pre + f"self_attn.{proj}_proj.bias")
        if cfg.attn_head_gate and not linear:
            lp["attn_gate_proj"] = dense(pre + "self_attn.g_proj.weight")
        if cfg.qk_norm and not linear:
            lp["q_norm"] = {"scale": jnp.asarray(get(pre + "self_attn.q_norm.weight"), dtype=dtype)}
            lp["k_norm"] = {"scale": jnp.asarray(get(pre + "self_attn.k_norm.weight"), dtype=dtype)}
            if cfg.is_mla and cfg.mla_rope_interleave:
                # a latent layer's q/k norm weighs the rope features the
                # projections above were de-interleaved for: the same
                # permutation, of the query's rope slice and of the rope key
                perm = np.concatenate([np.arange(0, rope_d, 2),
                                       np.arange(1, rope_d, 2)])
                q_idx = np.arange(cfg.qk_head_dim)
                q_idx[-rope_d:] = cfg.qk_head_dim - rope_d + perm
                lp["q_norm"] = {"scale": lp["q_norm"]["scale"][q_idx]}
                lp["k_norm"] = {"scale": lp["k_norm"]["scale"][perm]}
        moe_layer = cfg.num_experts and not cfg.moe_layer_is_dense(i)
        if moe_layer:                                           # Qwen3/DS MoE
            lp["router"] = {"kernel": _t(get(pre + "mlp.gate.weight"), dtype)}
            if cfg.moe_router_bias:
                lp["router_bias"] = {"bias": jnp.asarray(
                    get(pre + "mlp.gate.e_score_correction_bias"),
                    jnp.float32)}
            lp["experts"] = {
                proj: {"kernel": jnp.stack([
                    _t(get(pre + f"mlp.experts.{e}.{proj}.weight"), dtype)
                    for e in range(cfg.moe_first_expert,
                                   cfg.moe_first_expert
                                   + cfg.moe_local_experts)])}
                for proj in ("gate_proj", "up_proj", "down_proj")}
            if cfg.moe_shared_experts:
                lp["shared"] = {
                    proj: dense(pre + f"mlp.shared_experts.{proj}.weight")
                    for proj in ("gate_proj", "up_proj", "down_proj")}
        elif pre + "mlp.gate_up_proj.weight" in raw:            # Phi-3 fused mlp
            gu = jnp.asarray(raw[pre + "mlp.gate_up_proj.weight"], dtype=dtype)
            g, u = jnp.split(gu, 2, axis=0)
            lp["gate_proj"], lp["up_proj"] = {"kernel": g.T}, {"kernel": u.T}
        else:
            # Falcon-H1 calls its MLP feed_forward
            mlp = "feed_forward." if cfg.has_ssm else "mlp."
            lp["gate_proj"] = dense(pre + mlp + "gate_proj.weight")
            lp["up_proj"] = dense(pre + mlp + "up_proj.weight")
        if not moe_layer:
            mlp = "feed_forward." if cfg.has_ssm else "mlp."
            lp["down_proj"] = dense(pre + mlp + "down_proj.weight")
        layers.append(lp)

    if cfg.moe_experts_held and \
            raw["model.embed_tokens.weight"].shape[0] != cfg.vocab_size:
        # a share takes its experts above; a slice of the vocabulary is
        # stated as a smaller vocabulary (benchmark/harness/plan.py
        # SHARE), which does not say WHICH rows
        raise ValueError(
            f"{cfg.name}: the checkpoint has "
            f"{raw['model.embed_tokens.weight'].shape[0]} vocabulary rows, "
            f"the configuration {cfg.vocab_size}; a sliced vocabulary runs "
            "on random weights only")
    params = {
        "embed": {"weight": jnp.asarray(get("model.embed_tokens.weight"), dtype=dtype)},
        "layers": layers,
        "final_norm": {"scale": jnp.asarray(
            get("model.final_layernorm.weight" if cfg.has_ssm
                else "model.norm.weight"), dtype=dtype)},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": _t(get("lm_head.weight"), dtype)}
    return params


def _load_opt(cfg: ModelConfig, raw: dict, dtype) -> Params:
    # OPT checkpoints may or may not carry the "model." prefix.
    def get(name):
        for cand in (name, "model." + name):
            if cand in raw:
                return raw[cand]
        raise KeyError(name)

    def dense(name):
        p = {"kernel": _t(get(name + ".weight"), dtype)}
        try:
            p["bias"] = jnp.asarray(get(name + ".bias"), dtype=dtype)
        except KeyError:
            pass
        return p

    def norm(name):
        return {"scale": jnp.asarray(get(name + ".weight"), dtype=dtype),
                "bias": jnp.asarray(get(name + ".bias"), dtype=dtype)}

    layers = []
    for i in range(cfg.num_layers):
        pre = f"decoder.layers.{i}."
        layers.append({
            "attn_norm": norm(pre + "self_attn_layer_norm"),
            "q_proj": dense(pre + "self_attn.q_proj"),
            "k_proj": dense(pre + "self_attn.k_proj"),
            "v_proj": dense(pre + "self_attn.v_proj"),
            "o_proj": dense(pre + "self_attn.out_proj"),
            "mlp_norm": norm(pre + "final_layer_norm"),
            "fc1": dense(pre + "fc1"),
            "fc2": dense(pre + "fc2"),
        })
    return {
        "embed": {"weight": jnp.asarray(get("decoder.embed_tokens.weight"), dtype=dtype)},
        "pos_embed": {"weight": jnp.asarray(get("decoder.embed_positions.weight"), dtype=dtype)},
        "layers": layers,
        "final_norm": norm("decoder.final_layer_norm"),
    }


def load_or_init(cfg: ModelConfig, ckpt_dir: str | None, seed: int = 0,
                 mesh=None) -> Params:
    """Load from a checkpoint dir when given/present, else random-init
    (sharded over ``mesh`` from birth, see :func:`init_params`)."""
    if ckpt_dir and glob.glob(os.path.join(ckpt_dir, "*.safetensors")):
        return load_hf_checkpoint(cfg, ckpt_dir)
    return init_params(cfg, seed, mesh)


# --------------------------------------------------------------------------
# Streaming leaf-wise persistence (the weight-tier demotion path)
# --------------------------------------------------------------------------
#
# ``save_orbax`` (and a naive np.savez of the whole tree) materialises a
# second full host copy of the model while writing — during a model-pool
# demotion that transiently DOUBLES host RSS exactly when the host tier is
# under byte pressure.  These helpers stream one tensor at a time: each
# leaf is pulled to host, written, and released before the next is
# touched, so peak extra RSS is one leaf, not one model
# (tpuserve/modelpool/tiers.py is the consumer; tests/test_modelpool.py
# pins the peak-RSS bound).

_STREAM_MANIFEST = "manifest.json"


def _leaf_np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name incl. ml_dtypes extension types (bfloat16
    leaves round-trip the spill dir as raw bytes + this tag)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def iter_param_leaves(params, prefix: str = ""):
    """Yield ``(dotted_path, leaf)`` pairs of a params pytree in
    deterministic depth-first order.  Param trees are pure nests of
    dict/list/tuple over arrays — integer path components are list
    indices (``layers.0.q_proj.kernel``)."""
    if isinstance(params, dict):
        for k in params:
            yield from iter_param_leaves(params[k], f"{prefix}{k}.")
    elif isinstance(params, (list, tuple)):
        for i, v in enumerate(params):
            yield from iter_param_leaves(v, f"{prefix}{i}.")
    elif params is not None:
        yield prefix[:-1], params


def stream_params_to_dir(params, out_dir: str) -> int:
    """Write a params pytree leaf-by-leaf into ``out_dir``.

    One ``.npy`` file per leaf plus a ``manifest.json`` (written LAST —
    its presence marks the directory complete; readers treat a
    manifest-less dir as garbage).  Extension dtypes (bfloat16, int8
    scales ride as-is) are stored as raw bytes with the dtype tagged in
    the manifest.  Never holds more than one leaf's host copy beyond the
    caller's own tree.  Returns the total leaf bytes written."""
    os.makedirs(out_dir, exist_ok=True)
    leaves = []
    total = 0
    for idx, (path, leaf) in enumerate(iter_param_leaves(params)):
        a = np.asarray(leaf)            # ONE leaf on host at a time
        tag = "" if a.dtype.isbuiltin == 1 else str(a.dtype)
        fname = f"{idx:05d}.npy"
        ent = {"path": path, "file": fname, "shape": list(a.shape)}
        if tag:
            ent["dtype"] = tag
            a = np.ascontiguousarray(a).view(np.uint8)
        fpath = os.path.join(out_dir, fname)
        tmp = f"{fpath}.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            np.save(f, a)
        os.replace(tmp, fpath)          # atomic per leaf
        total += int(a.nbytes)
        leaves.append(ent)
        del a                           # release before the next leaf
    manifest = {"version": 1, "total_bytes": total, "leaves": leaves}
    mpath = os.path.join(out_dir, _STREAM_MANIFEST)
    tmp = f"{mpath}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, mpath)
    return total


def stream_dir_nbytes(in_dir: str) -> int | None:
    """Leaf bytes recorded in a streamed dir's manifest; None when the
    dir has no (complete) manifest."""
    try:
        with open(os.path.join(in_dir, _STREAM_MANIFEST)) as f:
            return int(json.load(f)["total_bytes"])
    except (OSError, ValueError, KeyError):
        return None


def load_params_from_dir(in_dir: str) -> Params:
    """Rebuild the pytree written by :func:`stream_params_to_dir`.

    Leaves come back as numpy arrays (the caller decides when each goes
    to device — ``jax.tree.map(jnp.asarray, ...)`` for a full promote).
    Raises ``FileNotFoundError`` on a manifest-less dir (incomplete
    write)."""
    with open(os.path.join(in_dir, _STREAM_MANIFEST)) as f:
        manifest = json.load(f)
    root: dict = {}
    for ent in manifest["leaves"]:
        a = np.load(os.path.join(in_dir, ent["file"]))
        tag = ent.get("dtype")
        if tag:
            a = a.view(_leaf_np_dtype(tag)).reshape(ent["shape"])
        parts = ent["path"].split(".")
        cur = root
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = a

    def _listify(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [_listify(node[str(i)]) for i in range(len(node))]
        return {k: _listify(v) for k, v in node.items()}

    return _listify(root)


# --------------------------------------------------------------------------
# Weight-only int8 quantization
# --------------------------------------------------------------------------
#
# Decode throughput on TPU is bounded by reading every weight from HBM once
# per step; symmetric per-output-channel int8 halves those bytes (vs bf16).
# XLA fuses the int8->bf16 convert into the matmul loop, so HBM sees int8
# reads while the MXU runs at its bf16 rate.  The deployed vLLM image the
# reference relies on exposes the same class of option (quantized serving);
# here it is a one-flag engine feature (EngineConfig.quantization="int8").

def _quantize_channelwise(w: jnp.ndarray, axis: int | tuple[int, ...]):
    """w -> (int8 weights, float32 scale along the kept ``axis`` axes).

    Symmetric: w ≈ w_q * scale, scale = max|w| / 127 per output channel.
    ``axis`` may be a tuple (e.g. (0, 2) for stacked MoE expert kernels
    (E, in, out): per-expert-per-output-channel scales shaped (E, out)).
    """
    keep = (axis,) if isinstance(axis, int) else tuple(axis)
    w32 = np.asarray(w, np.float32)
    reduce_axes = tuple(i for i in range(w32.ndim) if i not in keep)
    amax = np.max(np.abs(w32), axis=reduce_axes, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0)
    q = np.clip(np.rint(w32 / scale), -127, 127).astype(np.int8)
    kept_shape = tuple(w32.shape[i] for i in sorted(keep))
    return jnp.asarray(q), jnp.asarray(scale.reshape(kept_shape), jnp.float32)


def quantize_params_int8(params: Params) -> Params:
    """Quantize every linear kernel and the token embedding to int8.

    - linear dicts ({"kernel", ["bias"]}): kernel (in, out) -> int8 +
      ``scale`` (out,) float32; bias untouched.
    - embed ({"weight"}): (V, H) -> int8 + ``scale`` (V,) per-vocab-row
      (serves both the gather and, when tied, the transposed lm_head).
    - pos_embed, norms, qk-norm scales stay full precision (tiny).
    """
    def quant_linear(p: dict) -> dict:
        q, scale = _quantize_channelwise(p["kernel"], axis=1)
        out = {"kernel": q, "scale": scale}
        if "bias" in p:
            out["bias"] = p["bias"]
        return out

    def quant_experts(ep: dict) -> dict:
        # Stacked expert kernels (E, in, out): per-expert-per-output-channel
        # scales (E, out).  For MoE models the experts are the vast majority
        # of weights, so skipping them would void the HBM saving int8 exists
        # for (the r2 advisor caught exactly that).
        out = {}
        for proj, p in ep.items():
            q, scale = _quantize_channelwise(p["kernel"], axis=(0, 2))
            out[proj] = {"kernel": q, "scale": scale}
        return out

    def quant_layer(lp: dict) -> dict:
        out = {}
        for name, p in lp.items():
            if name == "experts":
                out[name] = quant_experts(p)
            elif name == "shared":
                # DeepSeek shared experts: a nested dict of plain linears
                out[name] = {k: quant_linear(v) for k, v in p.items()}
            else:
                out[name] = quant_linear(p) if "kernel" in p else p
        return out

    new = {"layers": [quant_layer(lp) for lp in params["layers"]]}
    eq, escale = _quantize_channelwise(params["embed"]["weight"], axis=0)
    new["embed"] = {"weight": eq, "scale": escale}
    if "lm_head" in params:
        new["lm_head"] = quant_linear(params["lm_head"])
    for k in ("pos_embed", "final_norm"):
        if k in params:
            new[k] = params[k]
    return new


# --------------------------------------------------------------------------
# LoRA adapters: merge-at-load
# --------------------------------------------------------------------------

# HF/PEFT module name -> our layer param key(s).  A string maps 1:1; a
# callable receives the ModelConfig and returns [(key, out_width), ...]
# column splits for fused projections (Phi-3 qkv/gate_up — the base
# loader splits the same way at load, see _load_llama_family).
_LORA_MODULES = {
    "self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
    "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj",
    "self_attn.out_proj": "o_proj",                       # OPT
    "mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
    "mlp.down_proj": "down_proj",
    "fc1": "fc1", "fc2": "fc2",                           # OPT
    "self_attn.qkv_proj": lambda cfg: [                   # Phi-3 fused
        ("q_proj", cfg.q_size), ("k_proj", cfg.kv_size),
        ("v_proj", cfg.kv_size)],
    "mlp.gate_up_proj": lambda cfg: [                     # Phi-3 fused
        ("gate_proj", cfg.intermediate_size),
        ("up_proj", cfg.intermediate_size)],
}


def _read_lora_adapter(adapter_dir: str) -> tuple[dict, float]:
    """(tensors, scaling) from a PEFT adapter directory.  Supports
    adapter_model.safetensors (preferred) and adapter_model.bin."""
    import json as _json
    cfg_path = os.path.join(adapter_dir, "adapter_config.json")
    with open(cfg_path) as f:
        acfg = _json.load(f)
    r = int(acfg.get("r", 8))
    alpha = float(acfg.get("lora_alpha", r))
    if acfg.get("use_rslora"):
        scaling = alpha / max(r, 1) ** 0.5    # rsLoRA: alpha/sqrt(r)
    else:
        scaling = alpha / max(r, 1)
    st = os.path.join(adapter_dir, "adapter_model.safetensors")
    if os.path.isfile(st):
        from safetensors import safe_open
        raw = {}
        with safe_open(st, framework="numpy") as f:
            for k in f.keys():
                raw[k] = f.get_tensor(k)
        return raw, scaling
    bin_path = os.path.join(adapter_dir, "adapter_model.bin")
    if os.path.isfile(bin_path):
        import torch
        sd = torch.load(bin_path, map_location="cpu", weights_only=True)
        return {k: v.float().numpy() for k, v in sd.items()}, scaling
    raise FileNotFoundError(
        f"no adapter_model.safetensors/.bin in {adapter_dir}")


def _parse_lora_factors(params: Params, cfg: ModelConfig, adapter_dir: str,
                        label: str = "") -> list:
    """Parse + validate one PEFT adapter against the model, returning
    low-rank factors [(li, param_key, A (in, r), B (r, w))] with the PEFT
    scaling folded into B and fused HF projections (Phi-3 qkv/gate_up)
    already split into this model's per-projection columns.  ONE parser
    for both consumers — :func:`apply_lora` (merge) and
    :func:`load_lora_stack` (runtime stack) — so they can never accept
    different adapter sets.  Validates everything before returning:
    callers may mutate params knowing nothing else will raise."""
    import re

    import numpy as np
    tag = f" in {label!r}" if label else ""
    raw, scaling = _read_lora_adapter(adapter_dir)
    pairs: dict[tuple[int, str], dict] = {}
    for key, tensor in raw.items():
        m = re.search(r"layers\.(\d+)\.([a-z_.0-9]+)\.lora_(A|B)\.weight$",
                      key)
        if m is None:
            raise ValueError(f"unsupported LoRA adapter key {key!r}{tag}")
        li, module, ab = int(m.group(1)), m.group(2), m.group(3)
        if module not in _LORA_MODULES:
            raise ValueError(f"LoRA target module {module!r} not supported "
                             f"(key {key!r}){tag}")
        if li >= cfg.num_layers:
            raise ValueError(f"LoRA key {key!r} targets layer {li} but the "
                             f"model has {cfg.num_layers}{tag}")
        pairs.setdefault((li, module), {})[ab] = np.asarray(
            tensor, dtype=np.float32)
    if not pairs:
        raise ValueError(f"adapter at {adapter_dir} contained no LoRA pairs")
    factors = []
    for (li, module), ab in sorted(pairs.items()):
        if "A" not in ab or "B" not in ab:
            raise ValueError(f"LoRA pair for layer {li} {module} is missing "
                             f"lora_{'A' if 'A' not in ab else 'B'}{tag}")
        target = _LORA_MODULES[module]
        splits = target(cfg) if callable(target) else [(target, None)]
        # HF shapes: A (r, in), B (out, r) -> ours (in, r) / (r, out)
        A = ab["A"].T
        B = ab["B"].T * scaling
        lp = params["layers"][li]
        col = 0
        for pk, width in splits:
            if pk not in lp or "kernel" not in lp[pk]:
                raise ValueError(f"model has no dense {pk} in layer {li} "
                                 "(MoE expert linears are not LoRA targets)")
            kernel = lp[pk]["kernel"]
            w = kernel.shape[1] if width is None else width
            if kernel.shape[0] != A.shape[0]:
                raise ValueError(
                    f"LoRA delta shape {(A.shape[0], B.shape[1])} does not "
                    f"match weight shape {tuple(kernel.shape)} for layer "
                    f"{li} {pk}{tag}")
            factors.append((li, pk, A, B[:, col:col + w]))
            col += w
        if col != B.shape[1]:
            raise ValueError(
                f"LoRA delta shape {(A.shape[0], B.shape[1])} does not "
                f"match fused projection width {col} for layer {li} "
                f"{module}{tag}")
    return factors


def apply_lora(params: Params, cfg: ModelConfig, adapter_dir: str) -> Params:
    """Merge a PEFT LoRA adapter into the dense weights: W += s·B@A.

    Merge-at-load serves a finetuned adapter at full base-model speed
    (zero runtime cost, works under TP sharding and int8 quantization
    since both happen downstream).  For per-request adapter multiplexing
    see :func:`load_lora_stack`.

    Raises on adapter keys that target modules this loader can't map —
    silently dropping part of an adapter would serve wrong weights.
    """
    factors = _parse_lora_factors(params, cfg, adapter_dir)
    # validate the merge targets BEFORE touching a weight: a failure
    # mid-merge would leave the caller's pytree half-merged
    for li, pk, _, _ in factors:
        if "scale" in params["layers"][li][pk]:
            raise ValueError(
                "cannot merge LoRA into already-quantized weights; "
                "load the bf16 checkpoint and quantize after")
    for li, pk, A, B in factors:
        lp = params["layers"][li]
        kernel = lp[pk]["kernel"]
        # A @ B[:, lo:hi] == (A @ B)[:, lo:hi] bitwise — columns of a
        # matmul are independent — so the factor form merges identically
        lp[pk]["kernel"] = (kernel.astype(jnp.float32)
                            + jnp.asarray(A @ B)).astype(kernel.dtype)
    return params


def load_lora_stack(params: Params, cfg: ModelConfig,
                    adapters: "dict[str, str]") -> list:
    """Load MULTIPLE PEFT adapters for per-request multiplexing.

    vLLM's multi-LoRA serving (punica SGMV kernels batching rows of
    different adapters) is the delegated analog; the TPU-native form is
    pure einsum: each targeted linear gains a ``lora`` sub-dict of
    STACKED low-rank factors — A (n, in, r_max), B (n, r_max, out) with
    the PEFT scaling folded into B and short-rank adapters zero-padded —
    and the per-row one-hot adapter weights contract against the stack at
    runtime (models/transformer._lora_delta).  A base-model row is an
    all-zero one-hot: it reads the stack but adds exactly nothing, so
    mixed batches need no gather/scatter, branches, or ragged shapes —
    the XLA-friendly dense-dispatch idiom also used for MoE experts.

    Unlike :func:`apply_lora` (merge-at-load, one adapter, zero runtime
    cost) this composes with int8 base weights: the delta applies after
    the dequantizing matmul.  Returns the adapter names in index order;
    mutates ``params`` in place.
    """
    import numpy as np
    names = list(adapters)
    if not names:
        raise ValueError("load_lora_stack needs at least one adapter")
    # (li, pk) -> per-adapter {idx: (A (in, r), B (r, w))}
    factors: dict[tuple[int, str], dict[int, tuple]] = {}
    for idx, (name, adapter_dir) in enumerate(adapters.items()):
        for li, pk, A, B in _parse_lora_factors(params, cfg, adapter_dir,
                                                label=name):
            factors.setdefault((li, pk), {})[idx] = (A, B)

    dtype = jnp.dtype(cfg.dtype)
    n = len(names)
    for (li, pk), per in factors.items():
        lp = params["layers"][li]
        in_f = lp[pk]["kernel"].shape[0]
        w = per[next(iter(per))][1].shape[1]
        r_max = max(a.shape[1] for a, _ in per.values())
        A_st = np.zeros((n, in_f, r_max), np.float32)
        B_st = np.zeros((n, r_max, w), np.float32)
        for idx, (A, B) in per.items():
            A_st[idx, :, :A.shape[1]] = A
            B_st[idx, :B.shape[0], :] = B
        lp[pk]["lora"] = {"A": jnp.asarray(A_st, dtype),
                          "B": jnp.asarray(B_st, dtype)}
    return names


# --------------------------------------------------------------------------
# Orbax save/restore (weight persistence analog of the reference's PVC cache)
# --------------------------------------------------------------------------

def save_orbax(params: Params, path: str) -> None:
    import orbax.checkpoint as ocp
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(path), params, force=True)
    ckptr.wait_until_finished()


def restore_orbax(cfg: ModelConfig, path: str,
                  target_params: Params | None = None) -> Params:
    """Restore a params pytree.  ``target_params`` supplies the target
    structure when it differs from a fresh ``init_params`` tree (e.g. an
    int8-quantized checkpoint, whose linears carry kernel+scale)."""
    import orbax.checkpoint as ocp
    ckptr = ocp.StandardCheckpointer()
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        target_params if target_params is not None else init_params(cfg),
    )
    return ckptr.restore(os.path.abspath(path), target)
