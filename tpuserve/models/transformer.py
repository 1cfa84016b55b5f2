"""Functional decoder-only transformer covering the Qwen3/Qwen2/Llama/Phi-3/OPT
family, built for XLA: static shapes, paged KV cache, bf16 matmuls with fp32
softmax/norm accumulation.

Params are a plain pytree (dict of layer lists), so the same code path works
under ``jit``, ``pjit`` with NamedShardings, and ``jax.grad`` (fine-tuning).
The reference delegates the model entirely to the vLLM container image
(reference: llm-d-deploy.yaml:176-193); here it is framework code.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from tpuserve.models.config import (MIXER_ATTENTION, MIXER_LINEAR,
                                    ModelConfig)
from tpuserve.ops import attention as attn_ops
from tpuserve.ops import gated_delta as gdn_ops
from tpuserve.ops import rope as rope_ops
from tpuserve.ops import scopes
from tpuserve.ops import ssm as ssm_ops
from tpuserve.utils import next_power_of_2, round_up

Params = Any  # nested dict/list pytree of jnp arrays


# --------------------------------------------------------------------------
# Normalisation
# --------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float,
            offset: float = 0.0) -> jnp.ndarray:
    """``offset``: Gemma stores RMSNorm weights as residuals around zero
    and computes (1 + w) * normed — pass 1.0 for that family."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (scale.astype(jnp.float32) + offset)).astype(dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def _post_norm(m: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    """A branch's output under its norm where the norm stands on the
    OUTPUT (``norm_placement`` "post"), in float32: added to the residual
    stream it makes the stream float32 from the first layer on.  No norm
    stands between such a stream and the next add, so a stream kept in
    bfloat16 is rounded at every one of its adds, two a layer, and each
    branch carries what the adds before it lost on to the next (the
    linear-attention mixer twice over: it sums where attention averages);
    the branches round their own input once (``h.astype(cfg.dtype)``)."""
    return _norm(m.astype(jnp.float32), p, cfg)


def _norm(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"], cfg.norm_eps, cfg.norm_weight_offset)
    return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)


def _linear(x: jnp.ndarray, p: dict, ad: jnp.ndarray | None = None,
            out=None) -> jnp.ndarray:
    """``out``: the dtype the product leaves in (None: its inputs'); the
    MXU accumulates in float32 either way, so float32 here keeps what a
    bfloat16 result would round away, at the price of the result's
    bytes."""
    w = p["kernel"]
    if out is not None and "scale" not in p:
        y = jnp.dot(x, w, preferred_element_type=out)
    elif "scale" in p:
        # int8 weight-only quantization (models/weights.py
        # quantize_params_int8): XLA fuses the convert into the matmul
        # loop, so HBM reads int8 while the MXU runs at its bf16 rate; the
        # per-output-channel scale applies after the contraction.
        y = (x @ w.astype(x.dtype)) * p["scale"].astype(x.dtype)
    else:
        y = x @ w
    if ad is not None and "lora" in p:
        y = y + _lora_delta(x, p["lora"], ad)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


def _lora_delta(x: jnp.ndarray, la: dict, ad: jnp.ndarray) -> jnp.ndarray:
    """Per-row multi-LoRA contribution (weights.load_lora_stack layout).

    ``ad`` (B, n) one-hot adapter weights per batch row (all-zero = base
    model).  The contraction folds the stacked factors into per-row
    (H, r)/(r, W) matrices first — n and r are small, so this is noise
    next to the dense matmul — then applies the rank-r bottleneck.  Dense
    over the adapter dim like the MoE expert dispatch: no gathers, no
    ragged shapes, mixed-adapter batches in one executable."""
    A = la["A"].astype(x.dtype)                    # (n, H, r)
    Bm = la["B"].astype(x.dtype)                   # (n, r, W)
    adx = ad.astype(x.dtype)
    Ar = jnp.einsum("bn,nhr->bhr", adx, A)
    Br = jnp.einsum("bn,nrw->brw", adx, Bm)
    if x.ndim == 2:                                # decode: (B, H)
        return jnp.einsum("bh,bhr,brw->bw", x, Ar, Br)
    return jnp.einsum("bth,bhr,brw->btw", x, Ar, Br)   # prefill: (B, T, H)


def _scaled(x: jnp.ndarray, mult: float) -> jnp.ndarray:
    """``x`` times a fixed multiplier of the model (Falcon-H1's muP
    scalars), in ``x``'s dtype; not applied at all where it is 1."""
    return x if mult == 1.0 else x * jnp.asarray(mult, x.dtype)


def _act(x: jnp.ndarray, name: str) -> jnp.ndarray:
    if name == "silu":
        return jax.nn.silu(x)
    if name in ("gelu", "gelu_pytorch_tanh"):
        return jax.nn.gelu(x)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {name}")


def _attn_residual(h: jnp.ndarray, out: jnp.ndarray, lp: dict,
                   cfg: ModelConfig, ad: jnp.ndarray | None = None,
                   m: jnp.ndarray | None = None) -> jnp.ndarray:
    """The residual stream ``h`` plus the attention heads' output ``out``
    (..., heads, width) through the output projection; Gemma2 sandwich
    norms apply a post-attention layernorm to the projected output before
    the add.  ``m``: a state-space branch's term of the same layer, added
    to the attention's before both join the stream."""
    with jax.named_scope(scopes.ATTN_OUT):
        if out.shape[-2] != cfg.num_heads:      # cfg.cache_q_heads' zeros
            out = out[..., :cfg.num_heads, :]
        if cfg.attn_head_gate:
            # a sigmoid gate a HEAD (``attn_gate_proj``) from the layer's
            # normed input, on the heads' output before o_proj.  The input
            # norm is taken again here, where h and the weights are at
            # hand: the compiler folds it into _mla_latents' own
            with jax.named_scope(scopes.ATTN_GATE):
                gate = jax.nn.sigmoid(_linear(
                    _norm(h, lp["attn_norm"], cfg), lp["attn_gate_proj"], ad))
                out = out * gate[..., None].astype(out.dtype)
        if cfg.norm_placement == "post":
            return h + _post_norm(
                _linear(out.reshape(*out.shape[:-2], -1), lp["o_proj"], ad,
                        jnp.float32), lp["post_attn_norm"], cfg)
        att = _scaled(_linear(out.reshape(*out.shape[:-2], -1),
                              lp["o_proj"], ad),
                      cfg.attention_out_multiplier)
        if cfg.sandwich_norms:
            att = _norm(att, lp["post_attn_norm"], cfg)
        return h + (att if m is None else att + m)


def _mlp_residual(h: jnp.ndarray, lp: dict, cfg: ModelConfig,
                  ad: jnp.ndarray | None = None, tally: list | None = None,
                  moe_dense: bool = False) -> jnp.ndarray:
    """The residual stream plus its pre-norm MLP branch; under sandwich
    norms the pre-norm weights are the checkpoint's
    pre_feedforward_layernorm (mapped onto ``mlp_norm``) and a
    post-feedforward layernorm wraps the output before the add.
    ``tally`` and ``moe_dense`` are an expert layer's (:func:`_moe_mlp`)."""
    with jax.named_scope(scopes.MLP):
        if cfg.norm_placement == "post":        # the norm on the OUTPUT
            def branch(rows):
                return _post_norm(
                    _mlp(rows.astype(cfg.dtype), lp, cfg, ad, tally,
                         moe_dense), lp["post_mlp_norm"], cfg)

            T = h.shape[0]
            if h.ndim == 2 and T > POST_MLP_ROWS and not T % POST_MLP_ROWS \
                    and tally is None:
                # a packed prefill: the MLP is a function of a row, so its
                # float32 intermediates need hold a block of rows, not
                # 8,192 of them (0.7 GB at this family's widths, beside
                # weights, state and pages that fill the chip)
                return h + jax.lax.map(branch, h.reshape(
                    -1, POST_MLP_ROWS, h.shape[-1])).reshape(h.shape)
            return h + branch(h)
        m = _mlp(_norm(h, lp["mlp_norm"], cfg), lp, cfg, ad, tally,
                 moe_dense)
        if cfg.sandwich_norms:
            m = _norm(m, lp["post_mlp_norm"], cfg)
        return h + m


#: rows of a packed prefill a post-norm MLP takes at once
POST_MLP_ROWS = 1024


def _mlp(x: jnp.ndarray, p: dict, cfg: ModelConfig,
         ad: jnp.ndarray | None = None, tally: list | None = None,
         moe_dense: bool = False) -> jnp.ndarray:
    # branch on the PARAMS, not cfg.num_experts: DeepSeek keeps the first
    # first_k_dense_replace layers dense inside an MoE model, so those
    # layers carry plain gated-MLP params (weights.init_params)
    if "experts" in p:
        return _moe_mlp(x, p, cfg, tally, moe_dense)
    with jax.named_scope(scopes.MLP):
        if cfg.mlp_style == "gated" and cfg.norm_placement == "post":
            # every product leaves in float32 and is rounded once, where
            # it enters the next product (_post_norm has the reason)
            f32 = jnp.float32
            gate = _act(_linear(x, p["gate_proj"], ad, f32), cfg.act)
            return _linear((gate * _linear(x, p["up_proj"], ad, f32)
                            ).astype(x.dtype), p["down_proj"], ad, f32)
        if cfg.mlp_style == "gated":
            gate_m, down_m = cfg.mlp_multipliers
            gate = _act(_scaled(_linear(x, p["gate_proj"], ad), gate_m),
                        cfg.act)
            return _scaled(_linear(gate * _linear(x, p["up_proj"], ad),
                                   p["down_proj"], ad), down_m)
        return _linear(_act(_linear(x, p["fc1"], ad), cfg.act), p["fc2"], ad)


def _moe_mlp(x: jnp.ndarray, p: dict, cfg: ModelConfig,
             tally: list | None = None, dense: bool = False) -> jnp.ndarray:
    """Mixture-of-experts MLP: the router scores every expert and picks
    ``num_experts_per_tok`` a token; the picked experts' gated-MLP outputs
    are combined with the (optionally renormalised) router weights.

    Dispatch is SPARSE: the ``T k`` (token, expert) pairs are ordered by
    expert (a stable sort), their rows gathered, and gate/up and down run
    as GROUPED products over the contiguous row groups with the per-expert
    group sizes as data (``ops/pallas_moe_gmm.py``, the custom call
    ``_moe_grouped_matmul``): always ``T k`` rows, an expert may get none,
    no capacity, no dropped token, no padding to a per-expert maximum.
    Each row is then weighted and a token's ``k`` rows summed.  That is
    ``k / E`` of the dense form's operations, and in decode each TOUCHED
    expert's kernels are read once.  One implementation for every preset
    on one device (shared experts, the int8 ``scale`` path and DeepSeek's
    routing included).

    ``dense`` (static; what the engine observes under a mesh, where the
    stacked kernels are sharded over the 'ep' axis and GSPMD cannot
    partition a kernel): every expert runs on every token and the
    unpicked ones weigh zero, so each shard computes its own experts and
    one psum combines (parallel/sharding.py).  No expert-parallel
    exchange exists yet (ROADMAP C9/M1).

    A SHARE (``cfg.moe_experts_held``; what a configuration observes of
    its deployment, not an option of the call): this process holds that
    many of the ``E`` experts.  Router, top-k, renormalisation over all
    ``k`` picks and scaling are as ever, over all ``E``; then
    :func:`_moe_held_experts` computes the held experts' part for the
    picks that fall on them and the absent experts' part is left out, the
    shared expert added whole.  That partial sum goes on to the next layer.

    ``tally``: a list the trunk collects routing in; this layer appends
    ``(E,)`` int32 rows routed to each expert (padding rows of the
    dispatch included: they are computed like any other), its ``(T, k)``
    picks and, under a share, what landed here (None without one).
    """
    shape = x.shape
    k = cfg.num_experts_per_tok
    with jax.named_scope(scopes.MOE_ROUTE):
        xt = x.reshape(-1, shape[-1])                          # (T, H)
        T = xt.shape[0]
        if "scale" in p["router"]:
            router = _linear(xt, p["router"]).astype(jnp.float32)  # (T, E)
        else:
            # scores in float32 from the product's own accumulator: a bf16
            # output would round near-tied scores before the top-k reads
            # them, and a flipped pick is another expert's output
            router = jnp.matmul(xt, p["router"]["kernel"],
                                preferred_element_type=jnp.float32)
        # DeepSeek-V3 scores experts with a sigmoid; selection adds the
        # auxiliary-loss-free correction bias and (optionally) restricts
        # the top-k to the best topk_group of n_group expert groups — but
        # the COMBINE weights always come from the unbiased scores (HF
        # DeepseekV3TopkRouter.get_topk_indices/forward).
        if cfg.moe_scoring == "sigmoid":
            scores = jax.nn.sigmoid(router)
        else:
            scores = jax.nn.softmax(router, axis=-1)
        choice = scores
        if "router_bias" in p:
            choice = choice + p["router_bias"]["bias"][None, :]
        E = scores.shape[-1]
        group_rows = None
        if cfg.moe_n_group > 1:
            topi, _, group_rows = _group_limited_select(choice, cfg)
        else:
            _, topi = jax.lax.top_k(choice, k)                 # (T, k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)      # unbiased
        if cfg.norm_topk_prob:
            # HF adds 1e-20 on the sigmoid path (sums are not 1 there)
            eps = 1e-20 if cfg.moe_scoring == "sigmoid" else 0.0
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + eps)
        if cfg.moe_routed_scaling != 1.0:
            topv = topv * cfg.moe_routed_scaling
        picks = topi.reshape(-1)                               # (T k,)
        sizes = jnp.sum(picks[:, None] == jnp.arange(E)[None, :], axis=0,
                        dtype=jnp.int32)                       # (E,)
        if not dense and not cfg.moe_experts_held:
            order = jnp.argsort(picks, stable=True)     # rows by expert
    ek = p["experts"]
    landed = None
    if cfg.moe_experts_held:
        if dense:
            raise ValueError(
                f"{cfg.name}: the dense form of the expert layer runs ALL "
                "experts under a mesh; a share of them has no such form")
        y, landed = _moe_held_experts(xt, ek, topi, topv, cfg)
        if group_rows is not None:          # a fifth count under groups
            with jax.named_scope(scopes.MOE_ROUTE):
                landed = jnp.concatenate([landed, group_rows[None]])
    elif dense:
        y = _moe_dense_experts(xt, ek, topi, topv, cfg)
    else:
        from tpuserve.ops.pallas_moe_gmm import grouped_matmul
        rows = _gather_rows(xt, order // k)                    # (T k, H)

        def expert_proj(inp: jnp.ndarray, ep: dict) -> jnp.ndarray:
            # int8 stacked expert kernels carry a per-expert-per-output-
            # channel scale (E, out), applied after the product as in
            # _linear; the kernel converts the int8 blocks itself, so HBM
            # reads int8 (weights.quantize_params_int8)
            y = grouped_matmul(inp, ep["kernel"], sizes)
            if "scale" in ep:
                y = y * ep["scale"][picks[order]].astype(y.dtype)
            return y

        with jax.named_scope(scopes.MOE_EXPERTS):
            h = _act(expert_proj(rows, ek["gate_proj"]), cfg.act) \
                * expert_proj(rows, ek["up_proj"])
            o = expert_proj(h, ek["down_proj"])
        with jax.named_scope(scopes.MOE_COMBINE):
            # each row back beside its token's other picks
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype))
            o = _gather_rows(o, back).reshape(T, k, -1)
            # the router's weights stay float32 into the sum over a
            # token's picks
            y = jnp.einsum("tkh,tk->th", o, topv).astype(x.dtype)
    if tally is not None:
        tally.append((sizes, topi, landed))
    if "shared" in p:
        with jax.named_scope(scopes.MOE_SHARED):
            # DeepSeek shared experts: an always-on gated MLP beside the
            # routed ones (HF DeepseekV3MoE.shared_experts), written out
            # here so that its time reads under its own part and not the
            # dense MLP's
            sp = p["shared"]
            y = y + _linear(_act(_linear(xt, sp["gate_proj"]), cfg.act)
                            * _linear(xt, sp["up_proj"]), sp["down_proj"])
    with jax.named_scope(scopes.MOE_COMBINE):
        # (the rows back in the caller's shape, named as it always was: an
        # operation's name is part of its program's key in the compile
        # cache)
        return y.reshape(shape)


def _surviving_groups(choice: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The groups a group-limited router keeps for each row: ``choice``
    (T, E) float32 selection scores -> (T, G) bool, ``moe_topk_group`` true
    a row.  What ``lax.top_k`` over the group scores picks, ties included
    (the lower index first), and no sort: 64 values are not ordered to
    learn their two largest, nor 8 to learn which four are largest."""
    T, E = choice.shape
    G = cfg.moe_n_group
    grouped = choice.reshape(T, G, E // G)
    # group score: V3 (sigmoid) sums the group's top-2 member scores; V2's
    # group_limited_greedy (softmax) takes the single max (HF
    # modeling_deepseek_v2 vs _v3 — using the wrong one silently routes
    # full V2/V2.5 checkpoints to different expert groups)
    group_scores = jnp.max(grouped, axis=-1)                   # (T, G)
    if cfg.moe_scoring == "sigmoid":
        # the second largest: the maximum again where it stands twice,
        # else the largest of what lies below it
        top = grouped == group_scores[..., None]
        below = jnp.max(jnp.where(top, -jnp.inf, grouped), axis=-1)
        twice = jnp.sum(top, axis=-1, dtype=jnp.int32) > 1
        group_scores = group_scores + jnp.where(twice, group_scores, below)
    # a group's rank: the groups that score higher, or as high at a lower
    # index
    mine, other = group_scores[:, :, None], group_scores[:, None, :]
    g = jnp.arange(G)
    ahead = (other > mine) | ((other == mine) & (g[None, :] < g[:, None]))
    return jnp.sum(ahead, axis=-1, dtype=jnp.int32) < cfg.moe_topk_group


def _group_limited_select(choice: jnp.ndarray, cfg: ModelConfig):
    """A group-limited router's picks (``cfg.moe_n_group > 1``; DeepSeek-V2
    / -V3 style): ``choice`` (T, E) float32 selection scores -> ``(topi,
    gmask, group_rows)``: the (T, k) picks among the surviving groups'
    experts in ``lax.top_k``'s order, the (T, G) bool survivors, and under
    a share (``cfg.moe_experts_held``) the int32 count of rows one of
    whose surviving groups lies (partly) here, the rows a chip that holds
    this share is sent at all; None without one."""
    T, E = choice.shape
    G = cfg.moe_n_group
    gmask = _surviving_groups(choice, cfg)
    # HF masks non-selected groups to 0.0, not -inf
    choice = jnp.where(gmask[..., None], choice.reshape(T, G, E // G),
                       0.0).reshape(T, E)
    _, topi = jax.lax.top_k(choice, cfg.num_experts_per_tok)   # (T, k)
    group_rows = None
    if cfg.moe_experts_held:
        per = E // G
        lo = cfg.moe_first_expert // per
        hi = -(-(cfg.moe_first_expert + cfg.moe_experts_held) // per)
        group_rows = jnp.sum(jnp.any(gmask[:, lo:hi], axis=-1),
                             dtype=jnp.int32)
    return topi, gmask, group_rows


def held_piece_rows(pairs: int, held: int, experts: int) -> int:
    """Rows of one piece of :func:`_moe_held_experts`'s buffer, for a
    dispatch of ``pairs`` (token, expert) picks over ``experts`` experts
    of which ``held`` are here: what lands here when the router spreads
    its picks evenly, ``pairs held / experts``, plus three standard
    deviations of that count, rounded up to the row tile the grouped
    product takes at that size (``ops/pallas_moe_gmm.tiling``: a piece is
    never padded there).  A static function of shapes; never more than
    every pick."""
    mean = pairs * held / experts
    want = math.ceil(mean + 3.0 * math.sqrt(mean * (1.0 - held / experts)))
    tile = 16 if want <= 128 else 128 if want <= 1024 else 256
    return min(round_up(want, tile), round_up(pairs, 16))


def _moe_held_experts(xt, ek, topi, topv, cfg: ModelConfig):
    """The held experts' part of an expert layer under a share (see
    :func:`_moe_mlp`): ``xt`` (T, H) rows, ``ek`` the HELD experts'
    stacked kernels ``(held, ., .)``, ``topi`` / ``topv`` (T, k) the
    router's picks over all experts and their final weights.  Returns the
    weighted sum over each token's picks that fall on held experts
    (T, H) in ``xt``'s dtype, zero for a token none of whose picks do,
    and ``(4,)`` int32: the picks that landed here, the held experts that
    got at least one, the rows of buffer moved for them and the pieces
    they were moved in.

    **The buffer follows what lands here, not ``T k``.**  The picks are
    ordered held-first by expert (one stable sort of ``T k`` small
    integers; no row moves yet), and the rows of the held ones are
    gathered, multiplied (the grouped products of the layer that holds
    every expert, over ``held`` groups) and added back to their tokens a
    PIECE at a time: :func:`held_piece_rows` rows, as many pieces as the
    count needs (a ``fori_loop`` whose trip count is data).  One piece in
    the usual case; at the skew that sends EVERY pick here, ``T k /
    piece`` of them and still no pick dropped.  Rows of the last piece
    past the count belong to no group: the kernel visits no tile for
    them, and what it leaves there is masked out before the add."""
    from tpuserve.ops.pallas_moe_gmm import grouped_matmul
    T, k = topi.shape
    held, first = cfg.moe_experts_held, cfg.moe_first_expert
    piece = held_piece_rows(T * k, held, cfg.num_experts)
    with jax.named_scope(scopes.MOE_ROUTE):
        local = topi.reshape(-1) - first                       # (T k,)
        local = jnp.where((local >= 0) & (local < held), local, held)
        sizes = jnp.sum(local[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)                       # (held,)
        ends = jnp.cumsum(sizes)
        starts, landed = ends - sizes, ends[-1]
        pieces = (landed + piece - 1) // piece
        # held picks first, by expert; padded so that every piece is whole
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        order = jnp.pad(order, (0, -(T * k) % piece))
        weights = topv.reshape(-1)

    def one_piece(i, y):
        lo = i * piece
        with jax.named_scope(scopes.MOE_ROUTE):
            idx = jax.lax.dynamic_slice(order, (lo,), (piece,))
            live = lo + jnp.arange(piece, dtype=jnp.int32) < landed
            tok = idx // k
            group = jnp.clip(ends - lo, 0, piece) \
                - jnp.clip(starts - lo, 0, piece)
        rows = _gather_rows(xt, tok)                           # (piece, H)

        def expert_proj(inp: jnp.ndarray, ep: dict) -> jnp.ndarray:
            out = grouped_matmul(inp, ep["kernel"], group)
            if "scale" in ep:           # int8 kernels, as in _moe_mlp
                out = out * ep["scale"][
                    jnp.minimum(local[idx], held - 1)].astype(out.dtype)
            return out

        with jax.named_scope(scopes.MOE_EXPERTS):
            h = _act(expert_proj(rows, ek["gate_proj"]), cfg.act) \
                * expert_proj(rows, ek["up_proj"])
            o = expert_proj(h, ek["down_proj"])
        with jax.named_scope(scopes.MOE_COMBINE):
            # the router's weights stay float32 into the sum over a
            # token's picks, as where every expert is held
            o = jnp.where(live[:, None],
                          o.astype(jnp.float32) * weights[idx][:, None], 0.0)
            return y.at[tok].add(o)

    y = jax.lax.fori_loop(0, pieces, one_piece,
                          jnp.zeros(xt.shape, jnp.float32))
    with jax.named_scope(scopes.MOE_ROUTE):
        stats = jnp.stack([landed, jnp.sum(sizes > 0, dtype=jnp.int32),
                           pieces * piece, pieces])
    return y.astype(xt.dtype), stats


# rows from which a gather that REPEATS rows goes by the plain form: where
# it pulls ahead of the sliced one (2,304 bf16 a row, ns a row sliced /
# plain: 14.9 / 17.1 at 8 k rows, 14.0 / 7.8 at 16 k, 31 / 8.7 at 32 k;
# PERF.md §6, PR 42), and past the one rung the compiler refuses it at
_REPEATS_PLAIN_ROWS = 16384


def _gathers_plain(rows: int, source_rows: int, width: int) -> bool:
    """Whether :func:`_gather_rows` takes ``rows`` rows of ``width`` values
    out of ``source_rows`` by the plain form: a static function of shapes.
    A width that is no whole number of 128-lane tiles has no other form; a
    decode window's rows (``pallas_moe_gmm.DECODE_ROWS`` and fewer) keep
    the sliced one, whose programs are what they were (the two read within
    a tenth there); above that a PERMUTATION (as many rows out as in: the
    add-back's, whose result XLA's own sum reads) goes plain, and a gather
    that repeats rows (into expert order, for the grouped product's custom
    call) from ``_REPEATS_PLAIN_ROWS`` rows."""
    from tpuserve.ops.pallas_moe_gmm import DECODE_ROWS
    return bool(width % 128) or (rows > DECODE_ROWS and (
        rows == source_rows or rows >= _REPEATS_PLAIN_ROWS))


def moe_plain_moves(cfg: ModelConfig, tokens: int) -> tuple[bool, bool]:
    """Of an expert layer's two row moves in a dispatch of ``tokens`` rows
    (into expert order; back beside each token's other picks), which go
    by the plain gather: what the engine counts beside the routed rows
    (``EngineStats.moe_row_moves_plain``), from shapes alone.  Under a
    share one piece's rows are gathered and nothing is gathered back."""
    pairs, width = tokens * cfg.num_experts_per_tok, cfg.hidden_size
    if cfg.moe_experts_held:
        piece = held_piece_rows(pairs, cfg.moe_experts_held, cfg.num_experts)
        return _gathers_plain(piece, tokens, width), False
    return (_gathers_plain(pairs, tokens, width),
            _gathers_plain(pairs, pairs, width))


def _gather_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[idx]``, in the form that is faster on the chip AT THESE SHAPES
    (:func:`_gathers_plain`; the forms agree bit for bit: rows are moved,
    not computed).  Measured with ``tools/moe_row_move_probe.py`` (PERF.md
    §6, PR 42):

    * the plain row gather reads a source that fits VMEM from there and
      writes rows in the layout their consumer reads: 8-17 ns a row of
      4,608 bytes (54 once a source of 32 k rows no longer fits);
    * the same gather of ``(tiles, 128)`` slices pays two relayout passes
      over the result (and two over a source as large): 14-61 ns a row.
      It exists because the compiler REFUSES the plain form at one rung of
      the packed-prefill ladder: 1,536 tokens into 12,288 rows for the
      grouped product's custom call, where memory-space assignment puts
      operand and result in VMEM and the gather then lacks 0.4 MB of
      scoped VMEM (tests/test_chip_compile_experts.py holds both facts,
      and that what is chosen here compiles at every rung)."""
    rows, width = idx.shape[0], x.shape[-1]
    with jax.named_scope(scopes.MOE_GATHER):
        if _gathers_plain(rows, x.shape[0], width):
            return x[idx]
        return x.reshape(x.shape[0], width // 128, 128)[idx].reshape(
            rows, width)


def _moe_dense_experts(xt, ek, topi, topv, cfg: ModelConfig):
    """The dense form of the routed experts, for a mesh (see _moe_mlp):
    static shapes and no gather, so expert parallelism is pure GSPMD."""
    T = xt.shape[0]
    E = ek["gate_proj"]["kernel"].shape[0]

    def expert_proj(spec: str, inp: jnp.ndarray, ep: dict) -> jnp.ndarray:
        y = jnp.einsum(spec, inp, ep["kernel"].astype(inp.dtype))
        if "scale" in ep:
            y = y * ep["scale"][None].astype(y.dtype)
        return y

    with jax.named_scope(scopes.MOE_EXPERTS):
        g = expert_proj("th,ehi->tei", xt, ek["gate_proj"])
        u = expert_proj("th,ehi->tei", xt, ek["up_proj"])
        o = expert_proj("tei,eih->teh", _act(g, cfg.act) * u,
                        ek["down_proj"])
    with jax.named_scope(scopes.MOE_COMBINE):
        combine = jnp.zeros((T, E), topv.dtype).at[
            jnp.arange(T)[:, None], topi].set(topv)            # (T, E)
        return jnp.einsum("teh,te->th", o, combine.astype(o.dtype))


# --------------------------------------------------------------------------
# Attention projections (shared by prefill and decode)
# --------------------------------------------------------------------------

def _qkv(h: jnp.ndarray, lp: dict, cfg: ModelConfig, positions: jnp.ndarray,
         layer_idx: int, ad: jnp.ndarray | None = None):
    """The residual stream h: (..., H) through the layer's input norm ->
    q (..., Hq, D), k/v (..., Hkv, D), with qk-norm and RoPE, and the
    normed input itself (a state-space branch reads the same one).
    ``layer_idx`` selects per-layer rope (Gemma3: windowed layers
    rotate at the local base frequency unscaled; full layers at
    rope_theta with the linear position scaling.  Mellum 2: full layers
    rotate by a YaRN table whose cos and sin carry the attention factor,
    windowed layers by the plain one.  K-EXAONE: full layers do not
    rotate at all)."""
    with jax.named_scope(scopes.ATTN_QKV):
        hn = h.astype(cfg.dtype) if cfg.norm_placement == "post" \
            else _norm(h, lp["attn_norm"], cfg)
        h = _scaled(hn, cfg.attention_in_multiplier)
        q = _linear(h, lp["q_proj"], ad)
        if cfg.qk_norm_whole:               # over all heads at once
            q = rmsnorm(q, lp["q_norm"]["scale"], cfg.norm_eps)
        q = q.reshape(*h.shape[:-1], cfg.num_heads, cfg.head_dim)
        k = _linear(h, lp["k_proj"], ad)
        if cfg.qk_norm_whole:
            k = rmsnorm(k, lp["k_norm"]["scale"], cfg.norm_eps)
        k = k.reshape(*h.shape[:-1], cfg.num_kv_heads, cfg.head_dim)
        v = _linear(h, lp["v_proj"], ad).reshape(
            *h.shape[:-1], cfg.num_kv_heads, cfg.head_dim)
        k = _scaled(k, cfg.key_multiplier)
        if cfg.qk_norm and not cfg.qk_norm_whole:
            q = rmsnorm(q, lp["q_norm"]["scale"], cfg.norm_eps,
                        cfg.norm_weight_offset)
            k = rmsnorm(k, lp["k_norm"]["scale"], cfg.norm_eps,
                        cfg.norm_weight_offset)
        if cfg.pos == "rope" and cfg.layer_rotates(layer_idx):
            rotary_dim = int(cfg.head_dim * cfg.partial_rotary_factor)
            theta, scaling = cfg.layer_rope(layer_idx)
            pos = positions
            if scaling != 1.0:
                pos = positions.astype(jnp.float32) / scaling
            cos, sin = rope_ops.rope_freqs(
                pos, cfg.head_dim, theta, rotary_dim,
                llama3_scaling=cfg.rope_llama3_scaling,
                yarn_scaling=cfg.layer_yarn(layer_idx))
            q = rope_ops.apply_rope(q, cos, sin)
            k = rope_ops.apply_rope(k, cos, sin)
        if cfg.cache_kv_heads != cfg.num_kv_heads:
            # heads as the cache stores them: zeros past the model's own
            pad = [(0, 0)] * (k.ndim - 2)
            extra = cfg.cache_kv_heads - cfg.num_kv_heads
            q = jnp.pad(q, pad + [(0, cfg.cache_q_heads - cfg.num_heads),
                                  (0, 0)])
            k = jnp.pad(k, pad + [(0, extra), (0, 0)])
            v = jnp.pad(v, pad + [(0, extra), (0, 0)])
        return q, k, v, hn


# --------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek MLA)
# --------------------------------------------------------------------------
#
# The cache stores ONE vector per token: the rmsnorm'd kv_lora_rank latent
# concatenated with a single shared roped key (cfg.mla_latent_dim wide,
# cache_kv_heads == 1) — ~10x less KV HBM traffic and capacity than
# materialised per-head K/V, which is the whole point on TPU where decode
# is KV-bandwidth-bound.  Prefill decompresses K/V for the prompt (naive
# form: compute-bound anyway); chunked prefill and decode run the ABSORBED
# form — W_UK folds into the query and W_UV into the output, so attention
# happens entirely in latent space and the paged-attention op reads the
# latent pages as both K and V (scores need q_lat . c plus the rope dot;
# the value contraction needs only the first kv_lora_rank columns of the
# output).  References: DeepSeek-V2 paper §2.1; HF modeling_deepseek_v3
# (the naive form this must match numerically).

def _mla_rope(x: jnp.ndarray, cfg: ModelConfig,
              positions: jnp.ndarray) -> jnp.ndarray:
    """x (..., heads, rope) rotated at ``positions`` over the rope
    features: the queries' rope part, and the one key all heads share."""
    cos, sin = rope_ops.rope_freqs(positions, cfg.mla_qk_rope_head_dim,
                                   cfg.rope_theta, yarn_scaling=cfg.rope_yarn)
    return rope_ops.apply_rope(x, cos, sin)


def _mla_latents(h: jnp.ndarray, lp: dict, cfg: ModelConfig,
                 positions: jnp.ndarray, ad: jnp.ndarray | None = None):
    """The residual stream through the layer's input norm -> what the
    queries are made from (the normed query latent; the normed input
    itself where the model has no query latent) and the cache-ready
    latent (..., latent_dim) = rmsnorm(c_kv) ⊕ roped key: everything of
    the layer's input side that is narrow."""
    with jax.named_scope(scopes.ATTN_QKV):
        hn = _norm(h, lp["attn_norm"], cfg)
        cq = hn
        if "q_a_proj" in lp:
            cq = rmsnorm(_linear(hn, lp["q_a_proj"], ad),
                         lp["q_a_norm"]["scale"], cfg.norm_eps,
                         cfg.norm_weight_offset)
        ckv = _linear(hn, lp["kv_a_proj"], ad)
        c = rmsnorm(ckv[..., :cfg.mla_kv_lora_rank],
                    lp["kv_a_norm"]["scale"], cfg.norm_eps,
                    cfg.norm_weight_offset)
        k_rope = ckv[..., None, cfg.mla_kv_lora_rank:]
        if cfg.qk_norm:
            # the q/k norm's key side, on what of the ONE key all heads
            # share the latent's own norm does not cover: the rope key,
            # before its rotation.  (A norm over each head's DECOMPRESSED
            # 192-wide key is a scalar a token a head that a 576-wide
            # latent page does not hold: no absorbed form serves it.)
            k_rope = rmsnorm(k_rope, lp["k_norm"]["scale"], cfg.norm_eps,
                             cfg.norm_weight_offset)
        k_rope = _mla_rope(k_rope, cfg, positions)[..., 0, :]
        return cq, jnp.concatenate([c, k_rope], axis=-1)


def _mla_queries(cq: jnp.ndarray, lp: dict, cfg: ModelConfig,
                 positions: jnp.ndarray, ad: jnp.ndarray | None = None):
    """:func:`_mla_latents`' first result -> q_nope (..., H, nope) and
    roped q_rope (..., H, rope): the wide side (128 heads of 192 a row at
    the published sizes)."""
    with jax.named_scope(scopes.ATTN_QKV):
        q = _linear(cq, lp["q_b_proj" if "q_a_proj" in lp else "q_proj"], ad)
        q = q.reshape(*cq.shape[:-1], cfg.num_heads, cfg.qk_head_dim)
        if cfg.qk_norm:                     # a head at a time, unrotated
            q = rmsnorm(q, lp["q_norm"]["scale"], cfg.norm_eps,
                        cfg.norm_weight_offset)
        nope = cfg.mla_qk_nope_head_dim
        return q[..., :nope], _mla_rope(q[..., nope:], cfg, positions)


def _mla_proj(h: jnp.ndarray, lp: dict, cfg: ModelConfig,
              positions: jnp.ndarray, ad: jnp.ndarray | None = None):
    """The residual stream through the layer's input norm -> q_nope
    (..., H, nope), roped q_rope (..., H, rope), and the cache-ready
    latent (..., latent_dim) = rmsnorm(c_kv) ⊕ roped key."""
    cq, latent = _mla_latents(h, lp, cfg, positions, ad)
    return (*_mla_queries(cq, lp, cfg, positions, ad), latent)


def _mla_kv_b(lp: dict, cfg: ModelConfig, dtype) -> tuple:
    """kv_b_proj split into W_UK (kv_lora, H, nope) / W_UV (kv_lora, H, v)
    — dequantized when the kernel is int8 (weights.quantize_params_int8)."""
    p = lp["kv_b_proj"]
    w = p["kernel"].astype(dtype)
    if "scale" in p:
        w = w * p["scale"][None].astype(dtype)
    w = w.reshape(cfg.mla_kv_lora_rank, cfg.num_heads,
                  cfg.mla_qk_nope_head_dim + cfg.mla_v_head_dim)
    return w[..., :cfg.mla_qk_nope_head_dim], \
        w[..., cfg.mla_qk_nope_head_dim:]


def _mla_decompress(latent, lp, cfg: ModelConfig, dtype):
    """Materialise per-head K (..., H, head_dim) and V (..., H, v_dim)
    from latents — the naive form for compute-bound full-sequence paths."""
    with jax.named_scope(scopes.ATTN_QKV):
        w_uk, w_uv = _mla_kv_b(lp, cfg, dtype)
        c = latent[..., :cfg.mla_kv_lora_rank]
        k_nope = jnp.einsum("...tc,chn->...thn", c, w_uk)
        v = jnp.einsum("...tc,chv->...thv", c, w_uv)
        k_rope = jnp.broadcast_to(
            latent[..., None, cfg.mla_kv_lora_rank:],
            (*k_nope.shape[:-1], cfg.mla_qk_rope_head_dim))
        return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _mla_naive_qkv(h, lp, cfg: ModelConfig, positions,
                   ad: jnp.ndarray | None = None):
    """Drop-in _qkv analog for cache-free MLA paths: full q and
    decompressed per-head k/v (v is mla_v_head_dim wide — the shared
    attention ops contract the value dim independently); None where _qkv
    hands back the normed input (no such model has a second branch)."""
    q_nope, q_rope, latent = _mla_proj(h, lp, cfg, positions, ad)
    k, v = _mla_decompress(latent, lp, cfg, q_nope.dtype)
    with jax.named_scope(scopes.ATTN_QKV):
        return jnp.concatenate([q_nope, q_rope], axis=-1), k, v, None


def _mla_prefill_out(q_nope, q_rope, latent, lp, cfg: ModelConfig,
                     prompt_lens, scale: float) -> jnp.ndarray:
    """Naive (decompressed) attention over fresh prompt K/V: prefill is
    compute-bound, so materialising per-head K/V for the prompt costs
    little and reuses the masked prefill attention op unchanged."""
    k, v = _mla_decompress(latent, lp, cfg, q_nope.dtype)
    with jax.named_scope(scopes.ATTN_QKV):
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return attn_ops.prefill_attention(q, k, v, prompt_lens, scale)


def _mla_absorb_q(q_nope, q_rope, lp, cfg: ModelConfig) -> jnp.ndarray:
    """Fold W_UK into the query: scores against raw latents become exact
    (q_lat . c == q_nope . k_nope); the roped dims ride alongside, then
    zeros over the lanes a page holds past the latent
    (``ModelConfig.cache_head_dim``)."""
    with jax.named_scope(scopes.ATTN_QKV):
        w_uk, _ = _mla_kv_b(lp, cfg, q_nope.dtype)
        q_lat = jnp.einsum("...hn,chn->...hc", q_nope, w_uk)
        return attn_ops.pad_lanes(
            jnp.concatenate([q_lat, q_rope], axis=-1), cfg.cache_head_dim)


#: rows of a packed prefill whose queries stand at once: 128 heads of 192
#: are 49 KB a row and 164 KB absorbed into the 640-lane latent, 0.4 and
#: 1.25 GB at the ladder's top rung of 8,192 rows (and as much again folded
#: out of the latent), beside weights and pages that fill the chip
MLA_PACKED_ROWS = 1024


def _mla_packed_attention(h, cq, positions, lp, cfg: ModelConfig, ad,
                          latent_pages, block_tables, kv_lens, q_starts,
                          q_lens, meta, blk_seq, scale: float, blk: int,
                          decode_rows: bool):
    """A flat stream's attention in the ragged kernel's latent entry, from
    the narrow side (:func:`_mla_latents`' ``cq``, the latent pages the
    layer just wrote) to the residual stream: the queries, ``W_uk`` folded
    into them, the kernel, ``W_uv`` folded out, the output projection and
    the add.  A packed prefill (no decode rows, no adapter rows) longer
    than ``MLA_PACKED_ROWS`` goes ``MLA_PACKED_ROWS`` rows at a time: all
    of that is a function of a row and its sequence's pages, so a piece
    is the same program on a slice of the stream with the sequences'
    first rows counted from the slice's own (``q_starts`` less its
    offset), and the wide queries of 8,192 rows never stand at once."""
    from tpuserve.ops.pallas_ragged_attention import ragged_paged_attention

    def attend(h, cq, positions, q_starts, blk_seq):
        q_nope, q_rope = _mla_queries(cq, lp, cfg, positions, ad)
        out = ragged_paged_attention(
            _mla_absorb_q(q_nope, q_rope, lp, cfg), latent_pages, None,
            block_tables, kv_lens, q_starts, q_lens, meta, blk_seq, scale,
            blk_q=blk, decode_rows=decode_rows,
            v_lanes=cfg.mla_kv_lora_rank)
        return _attn_residual(h, _mla_unabsorb(out, lp, cfg), lp, cfg, ad)

    T, C = h.shape[0], MLA_PACKED_ROWS
    if decode_rows or ad is not None or T <= C:
        return attend(h, cq, positions, q_starts, blk_seq)
    n = -(-T // C)

    def pieces(x, per, fill=0):
        pad = ((0, n * per - x.shape[0]),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, pad, constant_values=fill).reshape(
            n, per, *x.shape[1:])

    out = jax.lax.map(
        lambda a: attend(a[0], a[1], a[2], q_starts - a[3], a[4]),
        (pieces(h, C), pieces(cq, C), pieces(positions, C),
         jnp.arange(n, dtype=jnp.int32) * C, pieces(blk_seq, C // blk, -1)))
    return out.reshape(n * C, h.shape[-1])[:T]


def _mla_window_attention(q, latent_pages, block_tables, ctx_lens,
                          chunk_lens, scale: float, v_lanes: int):
    """A (B, C) window of absorbed queries against the latent pages in the
    ragged kernel: each sequence's window is one prefill chunk of the flat
    stream that kernel serves, so the chunk, verify and draft trunks need
    no latent kernel of their own.  Rows past ``chunk_lens`` come back
    zero (their blocks are skipped)."""
    from tpuserve.ops.pallas_ragged_attention import (ragged_block_for,
                                                      ragged_paged_attention)
    B, C, Hq, D = q.shape
    blk = min(ragged_block_for(
        Hq, 1, D, latent_pages.shape[1], latent_pages.dtype.itemsize,
        q.dtype.itemsize), next_power_of_2(C))
    Cp = -(-C // blk) * blk
    q = jnp.pad(q, ((0, 0), (0, Cp - C), (0, 0), (0, 0)))
    row = jnp.arange(Cp, dtype=jnp.int32)
    live = row[None, :] < chunk_lens[:, None]                     # (B, Cp)
    seq = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, Cp))
    out = ragged_paged_attention(
        q.reshape(B * Cp, Hq, D), latent_pages, None, block_tables,
        (ctx_lens + chunk_lens).astype(jnp.int32),
        jnp.arange(B, dtype=jnp.int32) * Cp, chunk_lens.astype(jnp.int32),
        jnp.zeros((2,), jnp.int32),
        jnp.where(live, seq, -1)[:, ::blk].reshape(-1), scale, blk_q=blk,
        decode_rows=False, v_lanes=v_lanes)
    out = out.reshape(B, Cp, Hq, v_lanes)
    return jnp.where(live[:, :, None, None], out, 0)[:, :C]


def _mla_unabsorb(out_lat, lp, cfg: ModelConfig) -> jnp.ndarray:
    """Latent-space attention output -> per-head values via W_UV.  The
    paged op returned p @ [c ⊕ k_rope]; only the first kv_lora_rank
    columns are the value contraction, the rope tail is discarded."""
    with jax.named_scope(scopes.ATTN_OUT):
        _, w_uv = _mla_kv_b(lp, cfg, out_lat.dtype)
        return jnp.einsum("...hc,chv->...hv",
                          out_lat[..., :cfg.mla_kv_lora_rank], w_uv)


# --------------------------------------------------------------------------
# Mamba-2 mixer beside the attention heads (Falcon-H1)
# --------------------------------------------------------------------------
#
# Every layer of a hybrid model runs this branch on the SAME normed input
# as its attention heads and adds both to the residual.  It keeps, per
# sequence and not per token, a float32 state (heads, head size, state
# size) and the last ``mamba_d_conv - 1`` inputs of a short convolution.
# The engine holds them in a pool indexed by SEAT — one slot a running
# sequence, one more that padding rows share (runtime/kv_cache.py
# create_ssm_state) — which the trunks below take as ``ssm`` (per layer
# {"state", "conv"}) with ``seats`` (one a sequence) and return updated,
# beside the KV cache.  A window that starts at position 0 starts from
# zeros, whatever its seat held: that is how a seat is cleared for its
# next sequence.  Rows with PAD_SLOT as their cache slot are padding and
# change nothing.  A decode step touches each memory once, in place, by a
# kernel of its own: the state (ops/pallas_ssm_update.py) and the
# convolution's inputs (ops/pallas_conv_tail.py, which also says how the
# pool lays them out: a row of channels as (channels / 128, 128); a
# prefill reshapes from and to it, _read_tails / _keep_tails).
# Equations: HF modeling_falcon_h1 (FalconH1Mixer).

def _ssm_project(hn: jnp.ndarray, sp: dict, cfg: ModelConfig):
    """hn (..., hidden) -> gate z (..., d_ssm), convolution input xBC
    (..., conv_dim), raw step dt (..., heads): the input projection under
    its input multiplier and the five per-slice multipliers."""
    with jax.named_scope(scopes.SSM_IN_PROJ):
        p = _linear(_scaled(hn, cfg.ssm_in_multiplier), sp["in_proj"])
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            p = p * jnp.concatenate([
                jnp.full((w,), m, p.dtype) for w, m
                in zip(cfg.mamba_proj_widths, cfg.ssm_multipliers)])
        d = cfg.mamba_d_ssm
        return p[..., :d], p[..., d:d + cfg.mamba_conv_dim], \
            p[..., d + cfg.mamba_conv_dim:]


def _ssm_inputs(conv_out: jnp.ndarray, dt_raw: jnp.ndarray, sp: dict,
                cfg: ModelConfig, valid: jnp.ndarray):
    """Convolved xBC (f32) and raw dt -> x (..., H, P), B, C (..., G, N),
    dt (..., H) f32, A (H,).  Rows that are not ``valid`` come out as
    zeros: a padding row's input may be anything (the paged kernels leave
    such rows unspecified), and the scan SUMS over a chunk's rows, where
    a NaN times a zero step is still a NaN."""
    with jax.named_scope(scopes.SSM_CONV):
        xbc = jnp.where(valid[..., None], jax.nn.silu(conv_out), 0.0)
        # activations in the model's dtype, as everywhere else in the
        # trunk; the scan and the state update widen what they accumulate
        xbc = xbc.astype(dt_raw.dtype)
        d, gn = cfg.mamba_d_ssm, cfg.mamba_n_groups * cfg.mamba_d_state
        lead = xbc.shape[:-1]
        x = xbc[..., :d].reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head)
        bm = xbc[..., d:d + gn].reshape(*lead, cfg.mamba_n_groups,
                                        cfg.mamba_d_state)
        cm = xbc[..., d + gn:].reshape(*lead, cfg.mamba_n_groups,
                                       cfg.mamba_d_state)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + sp["dt_bias"])
        dt = jnp.where(valid[..., None], dt, 0.0)
        return x, bm, cm, dt, -jnp.exp(sp["A_log"].astype(jnp.float32))


def _ssm_output(y: jnp.ndarray, x: jnp.ndarray, z: jnp.ndarray, sp: dict,
                cfg: ModelConfig) -> jnp.ndarray:
    """Scan output y and its input x (..., H, P), gate z (..., d_ssm) ->
    the branch's contribution to the residual (..., hidden)."""
    with jax.named_scope(scopes.SSM_OUT):
        y = y + sp["D"].astype(jnp.float32)[:, None] * x
        y = y.reshape(*y.shape[:-2], cfg.mamba_d_ssm)
        if cfg.mamba_rms_norm:
            y = ssm_ops.gated_group_norm(
                y, z, sp["norm"]["scale"], cfg.norm_eps, cfg.mamba_n_groups,
                cfg.mamba_norm_before_gate)
        else:
            y = y * jax.nn.silu(z.astype(jnp.float32))
        return _scaled(_linear(y.astype(z.dtype), sp["out_proj"]),
                       cfg.ssm_out_multiplier)


# The pool keeps a seat's convolution memory in the layout of the decode
# step's kernel (ops/pallas_conv_tail.py ``tail_slab``); a prefill reads
# and writes it as (n, W - 1, channels), through these two alone.

def _read_tails(conv: jnp.ndarray, seats: jnp.ndarray,
                shape: tuple) -> jnp.ndarray:
    """The seats' tails as ``shape`` (n, W - 1, channels), in the pool's
    dtype."""
    return conv[seats].reshape(shape)


def _keep_tails(conv: jnp.ndarray, seats: jnp.ndarray,
                tails: jnp.ndarray) -> jnp.ndarray:
    """The pool's convolution memory with the seats' tails (n, W - 1,
    channels) after a prefill."""
    with jax.named_scope(scopes.SSM_CONV):
        return conv.at[seats].set(
            tails.astype(conv.dtype).reshape(-1, *conv.shape[1:]))


def _ssm_window(hn: jnp.ndarray, sp: dict, cfg: ModelConfig,
                lens: jnp.ndarray, entry: dict | None = None,
                seats: jnp.ndarray | None = None,
                fresh: jnp.ndarray | None = None):
    """The mixer over a window of rows a sequence: hn (B, L, hidden), the
    first ``lens`` (B,) rows of each valid.  ``fresh`` (B,) bool, for a
    window against the cache: True where the window starts its sequence,
    elsewhere state and convolution memory come from ``entry`` at
    ``seats``; None: every window starts from zeros.  Returns (m (B, L,
    hidden), entry with the seats' state and memory after the window —
    None in, None out: the cache-less trunks)."""
    B, L, _ = hn.shape
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    W = cfg.mamba_d_conv
    z, xbc, dt_raw = _ssm_project(hn, sp, cfg)
    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    tail = jnp.zeros((B, W - 1, cfg.mamba_conv_dim), xbc.dtype)
    if fresh is not None:
        keep = ~fresh
        with jax.named_scope(scopes.SSM_SCAN):
            s0 = jnp.where(keep[:, None, None, None], entry["state"][seats],
                           s0)
        with jax.named_scope(scopes.SSM_CONV):
            tail = jnp.where(keep[:, None, None], _read_tails(
                entry["conv"], seats, tail.shape), tail)
    conv_out, rows = ssm_ops.causal_conv(xbc, tail, sp["conv"]["kernel"],
                                         sp["conv"].get("bias"))
    valid = jnp.arange(L)[None, :] < lens[:, None]
    x, bm, cm, dt, a = _ssm_inputs(conv_out, dt_raw, sp, cfg, valid)
    # the scan's chunk: the published size where the window holds whole
    # chunks, else the largest part of it that divides the window
    Q = math.gcd(cfg.mamba_chunk_size, L)
    y, finals = ssm_ops.ssd_chunk_scan(
        x.reshape(B * L, H, P), dt.reshape(B * L, H), a,
        bm.reshape(B * L, *bm.shape[2:]), cm.reshape(B * L, *cm.shape[2:]),
        s0, jnp.repeat(jnp.arange(B, dtype=jnp.int32), L // Q), chunk=Q)
    m = _ssm_output(y.reshape(B, L, H, P), x, z, sp, cfg)
    if entry is None:
        return m, None
    with jax.named_scope(scopes.SSM_SCAN):
        state = entry["state"].at[seats].set(finals)
    return m, {"state": state,
               "conv": _keep_tails(entry["conv"], seats,
                                   ssm_ops.next_tail(rows, lens, W))}


def _ssm_nocache(hn: jnp.ndarray, lp: dict, cfg: ModelConfig,
                 lens: jnp.ndarray):
    """The mixer's term of the cache-less trunks (embeddings, prompt
    scoring, the draft proposer, the plain forward): every sequence from
    zeros, nothing kept.  Zero for a model without the branch."""
    if not cfg.has_ssm:
        return 0
    return _ssm_window(hn, lp["ssm"], cfg, lens)[0]


def _ssm_packed(hn: jnp.ndarray, sp: dict, cfg: ModelConfig,
                positions: jnp.ndarray, valid: jnp.ndarray,
                blk_seq: jnp.ndarray, q_starts: jnp.ndarray,
                q_lens: jnp.ndarray, blk: int, entry: dict,
                seats: jnp.ndarray):
    """The mixer over a PACKED prefill: hn (T, hidden), each prompt
    starting at position 0 on a ``blk``-row boundary of the flat axis
    (``blk_seq`` names each block's prompt, -1 padding; Engine._pack_ragged)
    and running ``q_lens`` rows from ``q_starts``.  The scan's chunk
    divides the block, so no chunk straddles two prompts; every prompt
    starts from zeros.  Returns (m (T, hidden), entry)."""
    T = hn.shape[0]
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    W = cfg.mamba_d_conv
    z, xbc, dt_raw = _ssm_project(hn, sp, cfg)
    # the flat axis as one row of the convolution, a tap that would reach
    # back past its prompt's first row reading zero
    conv_out, _ = ssm_ops.causal_conv(
        xbc[None], jnp.zeros((1, W - 1, xbc.shape[-1]), xbc.dtype),
        sp["conv"]["kernel"], sp["conv"].get("bias"),
        positions=positions[None])
    x, bm, cm, dt, a = _ssm_inputs(conv_out[0], dt_raw, sp, cfg, valid)
    Q = math.gcd(cfg.mamba_chunk_size, blk)
    n_seq = q_lens.shape[0]
    y, finals = ssm_ops.ssd_chunk_scan(
        x, dt, a, bm, cm,
        jnp.zeros((n_seq, H, P, cfg.mamba_d_state), jnp.float32),
        jnp.repeat(blk_seq, blk // Q), chunk=Q)
    m = _ssm_output(y, x, z, sp, cfg)
    with jax.named_scope(scopes.SSM_SCAN):
        state = entry["state"].at[seats].set(finals)
    with jax.named_scope(scopes.SSM_CONV):
        # each prompt's last W - 1 inputs (zeros before its first row)
        back = jnp.arange(W - 1)[None, :] - (W - 1)             # -3 .. -1
        idx = (q_starts + q_lens)[:, None] + back
        tails = jnp.where((q_lens[:, None] + back >= 0)[..., None],
                          xbc[jnp.clip(idx, 0, T - 1)], 0)
    return m, {"state": state,
               "conv": _keep_tails(entry["conv"], seats, tails)}


def _ssm_decode(hn: jnp.ndarray, sp: dict, cfg: ModelConfig,
                valid: jnp.ndarray, entry: dict, seats: jnp.ndarray,
                attn_impl: str):
    """One token a row: hn (B, hidden).  The convolution's memory taps
    the new row and shifts by one, the state is updated, both in place on
    the pool — each by its Pallas kernel under ``attn_impl="pallas"``
    (ops/pallas_conv_tail.py, ops/pallas_ssm_update.py), by the same
    formulas in ``jax.numpy`` otherwise.  Padding rows (not ``valid``)
    carry the trash seat.  Returns (m (B, hidden), entry)."""
    z, xbc, dt_raw = _ssm_project(hn, sp, cfg)
    from tpuserve.ops import pallas_conv_tail as tap
    from tpuserve.ops import pallas_ssm_update as upd
    pallas = attn_impl == "pallas"
    with jax.named_scope(scopes.SSM_CONV):
        conv_out, conv = (tap.conv_tail_step if pallas
                          else tap.conv_tail_step_reference)(
            entry["conv"], seats, xbc, sp["conv"]["kernel"],
            sp["conv"].get("bias"))
    x, bm, cm, dt, a = _ssm_inputs(conv_out, dt_raw, sp, cfg, valid)
    with jax.named_scope(scopes.SSM_SCAN):
        x = x.astype(jnp.float32)
        y, state = (upd.ssm_state_update if pallas
                    else upd.ssm_state_update_reference)(
            entry["state"], seats, jnp.exp(dt * a), dt[..., None] * x, bm, cm)
    m = _ssm_output(y, x, z, sp, cfg)
    return m, {"state": state, "conv": conv}


# --------------------------------------------------------------------------
# Gated delta-rule linear attention IN PLACE OF attention (Olmo-Hybrid)
# --------------------------------------------------------------------------
#
# A layer whose mixer is ``MIXER_LINEAR`` (ModelConfig.layer_mixer) runs no
# attention and writes no K/V page: it keeps, per sequence, a float32
# matrix state a head (key size x value size) and the last
# ``lin_conv_kernel - 1`` inputs of a short convolution, in the same seat
# pool as Falcon-H1's mixer (``ssm``: one entry a layer that holds a
# state, at the layer's place among them; the state in slabs of heads,
# ops/pallas_gdn_update.py; the convolution's inputs as Falcon-H1's,
# stepped by the same kernel) under the same rules: a window that starts at
# position 0 starts from zeros, padding rows change nothing.  The helpers
# open the scope of their ROLE in a recurrent mixer (``ssm.*``): the
# readers by scope read both mixers alike, the kernels differ by name.
# Equations: arXiv:2412.06464 (ops/gated_delta.py), the step size doubled
# under ``lin_allow_neg_eigval``; benchmark/reference/olmo_hybrid.py
# writes the layer out.  The mixer's FORM is ``cfg.lin_gate``: "scalar" is
# the above; "channel" is Kimi Delta Attention (arXiv:2510.26692;
# Ling-3.0-flash's linear layers, which stand five to one with LATENT
# attention layers: pages of one kind and a pool in one model), the same
# routes with a decay for every key channel (``f_proj``, the bounded gate),
# a sigmoid output gate, ``kda_chunk_scan`` and the kernel
# ``_kda_state_update``; benchmark/reference/ling_hybrid.py writes it out.

def _lin_project(h: jnp.ndarray, lp: dict, cfg: ModelConfig):
    """The residual stream h (..., hidden) -> the convolution's input
    [q | k | v] (..., conv_dim), the output gate (..., H dv), the raw
    decay (..., H; the channel gate's: (..., H dk)) and the raw step size
    (..., H)."""
    sp = lp["lin"]
    with jax.named_scope(scopes.SSM_IN_PROJ):
        u = h.astype(cfg.dtype) if cfg.norm_placement == "post" \
            else _norm(h, lp["attn_norm"], cfg)
        # every product leaves in float32 (_post_norm has the reason; the
        # two scalars a head most of all: a rounding of the decay's
        # exponent is a rounding of every later row's read of the state)
        def proj(name):
            return _linear(u, sp[name], out=jnp.float32)

        if cfg.lin_gate != "channel":
            return tuple(map(proj, ("qkv_proj", "g_proj", "a_proj",
                                    "b_proj")))
        # Kimi-delta: the decay's projection is as wide as the keys
        qkv, gate = proj("qkv_proj"), proj("g_proj")
        with jax.named_scope(scopes.SSM_GATE):
            f_raw = proj("f_proj")
        return qkv, gate, f_raw, proj("b_proj")


def _lin_qkv(x: jnp.ndarray, cfg: ModelConfig):
    """Rows of convolved, activated [q | k | v] channels (..., conv_dim)
    -> q, k (..., H, dk) with each head's vector normalised (q also
    scaled dk^-1/2) and v (..., H, dv), f32."""
    H, dk, dv = (cfg.lin_num_value_heads, cfg.lin_key_head_dim,
                 cfg.lin_value_head_dim)
    x = x.astype(jnp.float32)
    lead = x.shape[:-1]

    def unit(y):
        return y * jax.lax.rsqrt(
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)

    return (unit(x[..., :H * dk].reshape(*lead, H, dk)) * dk ** -0.5,
            unit(x[..., H * dk:2 * H * dk].reshape(*lead, H, dk)),
            x[..., 2 * H * dk:].reshape(*lead, H, dv))


def _lin_inputs(conv_out: jnp.ndarray, a_raw: jnp.ndarray,
                b_raw: jnp.ndarray, sp: dict, cfg: ModelConfig,
                valid: jnp.ndarray):
    """Convolved [q | k | v] (f32), raw decay and step size -> the
    activated channels x (..., conv_dim) that :func:`_lin_qkv` splits,
    the log of the decay g <= 0 (..., H; the channel gate's (..., H, dk))
    and the step size beta in [0, 2) (..., H) f32.  Rows that are not
    ``valid`` come out as zeros (``_ssm_inputs`` has the reason), which
    neither decay nor write the state."""
    with jax.named_scope(scopes.SSM_CONV):
        x = jnp.where(valid[..., None], jax.nn.silu(conv_out), 0.0)
        # activations in the model's dtype, as everywhere else in the
        # trunk; the scan and the state update widen what they accumulate
        x = x.astype(cfg.dtype)
        beta = jax.nn.sigmoid(b_raw.astype(jnp.float32))
        if cfg.lin_allow_neg_eigval:
            beta = 2.0 * beta
        if cfg.lin_gate == "channel":
            # Kimi-delta's safe gate: a value a key channel in (bound, 0)
            with jax.named_scope(scopes.SSM_GATE):
                H, dk = cfg.lin_num_value_heads, cfg.lin_key_head_dim
                logit = (a_raw.astype(jnp.float32) + sp["dt_bias"]).reshape(
                    *a_raw.shape[:-1], H, dk)
                g = cfg.lin_gate_lower_bound * jax.nn.sigmoid(
                    jnp.exp(sp["A_log"].astype(jnp.float32))[:, None] * logit)
                g = jnp.where(valid[..., None, None], g, 0.0)
            return x, g, jnp.where(valid[..., None], beta, 0.0)
        g = -jnp.exp(sp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a_raw.astype(jnp.float32) + sp["dt_bias"])
        return x, jnp.where(valid[..., None], g, 0.0), \
            jnp.where(valid[..., None], beta, 0.0)


def _lin_output(o: jnp.ndarray, gate: jnp.ndarray, h: jnp.ndarray, lp: dict,
                cfg: ModelConfig) -> jnp.ndarray:
    """The heads' output o (..., H, dv) under the per-head norm and the
    gate (..., H dv), through the output projection, added to the
    residual stream h."""
    sp = lp["lin"]
    with jax.named_scope(scopes.SSM_OUT):
        o = o.astype(jnp.float32)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg.norm_eps)
        o = o * sp["norm"]["scale"].astype(jnp.float32)
        act = jax.nn.sigmoid if cfg.lin_gate == "channel" else jax.nn.silu
        y = o.reshape(gate.shape) * act(gate.astype(jnp.float32))
        m = _linear(y.astype(cfg.dtype), sp["o_proj"], out=jnp.float32)
        if cfg.norm_placement == "post":
            return h + _post_norm(m, lp["post_attn_norm"], cfg)
        return h + m.astype(h.dtype)


def _lin_scan(cfg: ModelConfig):
    """The chunked scan of the linear mixer's form (``cfg.lin_gate``)."""
    return gdn_ops.kda_chunk_scan if cfg.lin_gate == "channel" \
        else gdn_ops.gated_delta_chunk_scan


def _lin_slabs(entry: dict, cfg: ModelConfig) -> int:
    """Heads side by side in a slab of the pool's state."""
    return cfg.lin_num_value_heads // entry["state"].shape[1]


def _lin_keep(entry: dict, seats: jnp.ndarray, finals: jnp.ndarray,
              tails: jnp.ndarray, cfg: ModelConfig) -> dict:
    """The pool's entry with the seats' states (n, H, dk, dv) and
    convolution memories (n, W - 1, conv_dim) after a prefill."""
    from tpuserve.ops.pallas_gdn_update import to_slabs
    with jax.named_scope(scopes.SSM_SCAN):
        state = entry["state"].at[seats].set(
            to_slabs(finals, _lin_slabs(entry, cfg)))
    return {"state": state, "conv": _keep_tails(entry["conv"], seats, tails)}


def _lin_window(h: jnp.ndarray, lp: dict, cfg: ModelConfig,
                lens: jnp.ndarray, entry: dict | None = None,
                seats: jnp.ndarray | None = None,
                fresh: jnp.ndarray | None = None):
    """A linear layer's mixer over a window of rows a sequence: h (B, L,
    hidden), the first ``lens`` (B,) rows of each valid; ``entry``,
    ``seats`` and ``fresh`` as :func:`_ssm_window` takes them.  Returns
    (the residual stream after the mixer, entry with the seats' state and
    memory after the window -- None in, None out)."""
    from tpuserve.ops.pallas_gdn_update import from_slabs
    B, L, _ = h.shape
    H, dk, dv = (cfg.lin_num_value_heads, cfg.lin_key_head_dim,
                 cfg.lin_value_head_dim)
    W, sp = cfg.lin_conv_kernel, lp["lin"]
    qkv, gate, a_raw, b_raw = _lin_project(h, lp, cfg)
    s0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    tail = jnp.zeros((B, W - 1, cfg.lin_conv_dim), qkv.dtype)
    if fresh is not None:
        keep = ~fresh
        with jax.named_scope(scopes.SSM_SCAN):
            s0 = jnp.where(keep[:, None, None, None], from_slabs(
                entry["state"][seats], _lin_slabs(entry, cfg)), s0)
        with jax.named_scope(scopes.SSM_CONV):
            tail = jnp.where(keep[:, None, None], _read_tails(
                entry["conv"], seats, tail.shape), tail)
    conv_out, rows = ssm_ops.causal_conv(qkv, tail, sp["conv"]["kernel"],
                                         None)
    valid = jnp.arange(L)[None, :] < lens[:, None]
    x, g, beta = _lin_inputs(conv_out, a_raw, b_raw, sp, cfg, valid)
    # the scan's chunk: as _ssm_window chooses it
    Q = math.gcd(cfg.lin_chunk_size, L)
    o, finals = _lin_scan(cfg)(
        x.reshape(B * L, -1), g.reshape(B * L, *g.shape[2:]),
        beta.reshape(B * L, H),
        s0, jnp.repeat(jnp.arange(B, dtype=jnp.int32), L // Q), chunk=Q,
        split=partial(_lin_qkv, cfg=cfg), out_dtype=x.dtype)
    h = _lin_output(o.reshape(B, L, H, dv), gate, h, lp, cfg)
    if entry is None:
        return h, None
    return h, _lin_keep(entry, seats, finals,
                        ssm_ops.next_tail(rows, lens, W), cfg)


def _lin_packed(h: jnp.ndarray, lp: dict, cfg: ModelConfig,
                positions: jnp.ndarray, slot_ids: jnp.ndarray,
                blk_seq: jnp.ndarray, q_starts: jnp.ndarray,
                q_lens: jnp.ndarray, blk: int, entry: dict,
                seats: jnp.ndarray):
    """A linear layer's mixer over a PACKED prefill: h (T, hidden), laid
    out as :func:`_ssm_packed` says (padding rows carry PAD_SLOT as their
    cache slot); every prompt starts from zeros.
    Returns (the residual stream after the mixer (T, hidden), entry)."""
    T = h.shape[0]
    H, dk, dv = (cfg.lin_num_value_heads, cfg.lin_key_head_dim,
                 cfg.lin_value_head_dim)
    W, sp = cfg.lin_conv_kernel, lp["lin"]
    qkv, gate, a_raw, b_raw = _lin_project(h, lp, cfg)
    with jax.named_scope(scopes.SSM_CONV):
        valid = slot_ids != attn_ops.PAD_SLOT
        # the flat axis as one row of the convolution, a tap that would
        # reach back past its prompt's first row reading zero
        conv_out, _ = ssm_ops.causal_conv(
            qkv[None], jnp.zeros((1, W - 1, qkv.shape[-1]), qkv.dtype),
            sp["conv"]["kernel"], None, positions=positions[None])
        conv_out = conv_out[0]
    x, g, beta = _lin_inputs(conv_out, a_raw, b_raw, sp, cfg, valid)
    Q = math.gcd(cfg.lin_chunk_size, blk)
    o, finals = _lin_scan(cfg)(
        x, g, beta, jnp.zeros((q_lens.shape[0], H, dk, dv), jnp.float32),
        jnp.repeat(blk_seq, blk // Q), chunk=Q,
        split=partial(_lin_qkv, cfg=cfg), out_dtype=x.dtype)
    h = _lin_output(o, gate, h, lp, cfg)
    with jax.named_scope(scopes.SSM_CONV):
        # each prompt's last W - 1 inputs (zeros before its first row)
        back = jnp.arange(W - 1)[None, :] - (W - 1)
        idx = (q_starts + q_lens)[:, None] + back
        tails = jnp.where((q_lens[:, None] + back >= 0)[..., None],
                          qkv[jnp.clip(idx, 0, T - 1)], 0)
    return h, _lin_keep(entry, seats, finals, tails, cfg)


def _lin_decode(h: jnp.ndarray, lp: dict, cfg: ModelConfig,
                slot_ids: jnp.ndarray, entry: dict, seats: jnp.ndarray,
                attn_impl: str):
    """One token a row: h (B, hidden).  The convolution's memory taps
    the new row and shifts by one, the state is updated, both in place on
    the pool -- each by its Pallas kernel under ``attn_impl="pallas"``
    (ops/pallas_conv_tail.py, ops/pallas_gdn_update.py), by the same
    formulas in ``jax.numpy`` otherwise.  Padding rows (PAD_SLOT as their
    cache slot) carry the trash seat.  Returns (the residual stream after
    the mixer (B, hidden), entry)."""
    sp = lp["lin"]
    qkv, gate, a_raw, b_raw = _lin_project(h, lp, cfg)
    from tpuserve.ops import pallas_conv_tail as tap
    if cfg.lin_gate == "channel":
        from tpuserve.ops import pallas_kda_update as upd
        kernel, formula = (upd.kda_state_update,
                           upd.kda_state_update_reference)
    else:
        from tpuserve.ops import pallas_gdn_update as upd
        kernel, formula = (upd.gdn_state_update,
                           upd.gdn_state_update_reference)
    pallas = attn_impl == "pallas"
    with jax.named_scope(scopes.SSM_CONV):
        valid = slot_ids != attn_ops.PAD_SLOT
        conv_out, conv = (tap.conv_tail_step if pallas
                          else tap.conv_tail_step_reference)(
            entry["conv"], seats, qkv, sp["conv"]["kernel"], None)
    x, g, beta = _lin_inputs(conv_out, a_raw, b_raw, sp, cfg, valid)
    with jax.named_scope(scopes.SSM_SCAN):
        o, state = (kernel if pallas else formula)(
            entry["state"], seats, *_lin_qkv(x, cfg), g, beta)
    h = _lin_output(o, gate, h, lp, cfg)
    return h, {"state": state, "conv": conv}


def _embed(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
           positions: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope(scopes.EMBED):
        h = params["embed"]["weight"][tokens]
        if "scale" in params["embed"]:    # int8 embed: per-vocab-row scale
            dtype = jnp.dtype(cfg.dtype)
            h = (h.astype(dtype)
                 * params["embed"]["scale"][tokens][..., None].astype(dtype))
        if cfg.embed_scale_by_sqrt_dim:   # Gemma: normalizer in h's dtype,
            h = h * jnp.asarray(cfg.hidden_size ** 0.5, h.dtype)  # like HF
        h = _scaled(h, cfg.embedding_multiplier)            # Falcon-H1
        if cfg.pos == "learned":
            h = h + params["pos_embed"]["weight"][
                positions + cfg.learned_pos_offset]
        return h


def _unembed(params: Params, cfg: ModelConfig, h: jnp.ndarray,
             at: jnp.ndarray | None = None) -> jnp.ndarray:
    """Final norm and head over ``h`` (..., H) -> float32 logits.  ``at``
    (B,): only these rows: of each sequence's axis 1 where ``h`` is (B, T,
    H), of the flat axis where it is (T, H)."""
    with jax.named_scope(scopes.HEAD):
        if at is not None and h.ndim == 3:
            h = jnp.take_along_axis(h, at[:, None, None], axis=1)[:, 0]
        elif at is not None:
            h = h[at]
        if cfg.final_layernorm:
            h = _norm(h, params["final_norm"], cfg)
        if cfg.norm_placement == "post":            # a float32 stream
            h = h.astype(cfg.dtype)
        if cfg.tie_word_embeddings:
            ew = params["embed"]
            if "scale" in ew:             # tied int8: scale per logit column
                logits = (h @ ew["weight"].T.astype(h.dtype)) \
                    * ew["scale"][None, :]
            else:
                logits = h @ ew["weight"].T
        else:
            logits = _linear(h, params["lm_head"])
        logits = _scaled(logits.astype(jnp.float32), cfg.lm_head_multiplier)
        if cfg.final_logit_softcapping:
            cap = cfg.final_logit_softcapping
            logits = cap * jnp.tanh(logits / cap)
        return logits


def _with_ssm(out, new_cache: list, ssm, new_ssm: list,
              moe=None) -> tuple:
    """A cache trunk's result: ``(out, kv_cache)``, then the seat pool
    where the model has one, then the routing (:func:`_moe_routing`) where
    it has expert layers."""
    res = (out, new_cache)
    if ssm is not None:
        res += (new_ssm,)
    if moe is not None:
        res += (moe,)
    return res


def _moe_tally(cfg: ModelConfig) -> list | None:
    """What a cache trunk collects its expert layers' routing counts in;
    None for a model without experts, whose programs gain nothing."""
    return [] if cfg.routes_experts else None


def _moe_counts(tally: list):
    """A dispatch's routing counts from its expert layers' ``(E,)`` group
    sizes: ``(E + 1,)`` int32 — rows routed to each expert, summed over
    the layers, then the expert-layers that got at least one row (each
    touched expert's kernels are read once a layer).  Under a share
    (:func:`_moe_held_experts`) four more, summed over the layers: the
    rows that landed on held experts, the held expert-layers that got at
    least one, the rows of buffer moved for them and the pieces moved;
    behind a group-limited router a fifth, the rows one of whose
    surviving groups is held here (:func:`_moe_mlp`)."""
    with jax.named_scope(scopes.MOE_ROUTE):
        sizes = jnp.stack([s for s, _, _ in tally])            # (L, E)
        counts = [jnp.sum(sizes, axis=0),
                  jnp.sum(sizes > 0, dtype=jnp.int32)[None]]
        if tally[0][2] is not None:
            counts.append(jnp.sum(jnp.stack([h for _, _, h in tally]),
                                  axis=0))
        return jnp.concatenate(counts)


def _moe_routing(tally: list | None, rows: jnp.ndarray | None):
    """What a cache trunk of a model with expert layers returns last:
    ``(counts, picks, all picks)`` — :func:`_moe_counts`; the experts each
    expert layer picked for the rows the trunk returns logits of (``rows``
    (B,) indexes the layers' flat token axis): ``(B, expert layers, k)``
    int32; and the same for every row of that axis ``(T, expert layers,
    k)``, a prompt's positions, or None where the trunk returns logits of
    every row (``rows`` None: decode).  They stay on the device unless a
    request asked for logprobs, beside which the engine files them.
    None where the trunk collected nothing."""
    if tally is None:
        return None
    with jax.named_scope(scopes.MOE_ROUTE):
        picks = jnp.stack([t for _, t, _ in tally], axis=1)    # (T, L, k)
        if rows is None:
            return _moe_counts(tally), picks, None
        return _moe_counts(tally), picks[rows], picks


# --------------------------------------------------------------------------
# One layer of each trunk, traced once a KIND
# --------------------------------------------------------------------------
#
# A trunk walks ``params["layers"]`` in a Python loop.  Were the layer's
# body written out in that loop, every program would trace, and then lower
# to StableHLO, as many copies of it as the model has layers: most of a
# program's host time, a hundred programs a warm start (PERF.md §6, PR 44).
# So each trunk's per-layer body is a function under its OWN ``jax.jit``.
# Called inside a trace, such a function is traced once per (static
# arguments, abstract arguments) and lowered once, as one private function
# that the module calls; XLA inlines the calls before it optimises, so the
# device's program is what it was.  The static layer index is
# ``cfg.layer_like(li)``, the first layer that answers every per-layer
# question of the configuration as layer ``li`` does, so layers of one
# kind share a trace; layers whose weights differ in shape, dtype or tree
# (a dense layer beside sparse ones, int8 beside bfloat16) part by
# ``jax.jit``'s own key.  Nothing is donated here (the outer program
# donates).  The phase scope stays with the trunk and the part scopes with
# the helpers, so an operation's path gains a ``jit(_<body>_layer)``
# component and keeps its phase and part.  A cache body opens its trunk's
# phase ONCE MORE around itself: the chip's compiler rebuilds some
# operations (decode's K/V row scatter) under the name they have INSIDE
# the called function, without the call's prefix, and a scatter filed
# under no phase is time gone from ``trunk.decode_glue_ms``.  (What that
# compiler makes itself inside a called function it names after the CALL,
# ``.../jit(_decode_layer)``: a phase and no part, where in a flat module
# it carries no name at all.  tests/test_chip_compile_programs.py holds
# both.)
#
# A cache trunk's body takes and returns its layer's entry of the paged
# cache and of the seat pool (None where the layer holds none) and returns
# its expert layer's routing (:func:`_moe_mlp`'s ``tally`` entry) or None.
#
# Two host counts a body, read by ``Engine.warmup``'s closing log line and
# ``/metrics``: bodies TRACED (the first statement of each body: it runs
# only when JAX traces it) and calls made (at the call sites).

LAYER_BODIES = ("prefill", "chunk", "decode", "ragged", "nocache")
LAYER_TRACES = dict.fromkeys(LAYER_BODIES, 0)
LAYER_CALLS = dict.fromkeys(LAYER_BODIES, 0)


def _walk_layers(body: str, layer, params: Params, cfg: ModelConfig, h,
                 kv_cache: list, ssm: list | None, tally: list | None,
                 *rows, **static):
    """A cache trunk's loop: ``layer`` (the jitted body counted under
    ``body``) once a layer, on the residual stream, the layer's weights,
    its entry of the paged cache and of the seat pool (None where it holds
    none: ``ModelConfig.layer_mixer``), then ``rows``, the dispatch's own
    arrays.  Returns (h, kv_cache, seat pool) and collects the expert
    layers' routing in ``tally``."""
    new_cache, new_ssm = [], []
    for li, lp in enumerate(params["layers"]):
        LAYER_CALLS[body] += 1
        mixer = cfg.layer_mixer(li)
        entry = None if mixer == MIXER_LINEAR else kv_cache[len(new_cache)]
        pool = None if ssm is None or mixer == MIXER_ATTENTION \
            else ssm[len(new_ssm)]
        h, entry, pool, routed = layer(h, lp, entry, pool, *rows, cfg=cfg,
                                       li=cfg.layer_like(li), **static)
        if entry is not None:
            new_cache.append(entry)
        if pool is not None:
            new_ssm.append(pool)
        if routed is not None and tally is not None:
            tally.append(routed)
    return h, new_cache, new_ssm


def _walk_nocache(params: Params, cfg: ModelConfig, h, positions, lens,
                  moe_dense: bool = False):
    """A cache-less trunk's loop (embeddings, prompt scoring, the draft
    proposer, the plain forward): :func:`_nocache_layer` once a layer."""
    for li, lp in enumerate(params["layers"]):
        LAYER_CALLS["nocache"] += 1
        h = _nocache_layer(h, lp, positions, lens, cfg=cfg,
                           li=cfg.layer_like(li), moe_dense=moe_dense)
    return h


@partial(jax.jit, static_argnames=("cfg", "li", "attn_impl", "mesh",
                                   "moe_dense"))
def _prefill_layer(h, lp, entry, pool, positions, prompt_lens, slot_ids, ad,
                   seats, *, cfg: ModelConfig, li: int, attn_impl: str, mesh,
                   moe_dense: bool):
    """One layer of :func:`prefill`: h (B, T, hidden)."""
    LAYER_TRACES["prefill"] += 1
    with jax.named_scope(scopes.PREFILL):
        tally = _moe_tally(cfg)
        scale, sw = cfg.attn_scale, cfg.layer_window(li)
        if cfg.layer_mixer(li) == MIXER_LINEAR:
            h, pool = _lin_window(h, lp, cfg, prompt_lens, pool, seats)
        elif cfg.is_mla:
            # MLA prefill on the (B, L) grid: cache the latent, attend
            # naively (decompressed) over the fresh prompt K/V in XLA.
            # The engine sends an MLA model's batched prefills here only
            # where it runs no Pallas kernel (a mesh, int8 pages); with
            # them it packs the batch through :func:`forward_ragged`
            if attn_impl == "pallas":
                raise NotImplementedError(
                    "an MLA model's (B, L) prefill has no Pallas form: its "
                    "batched prefills go out packed (Engine._packed_prefill)")
            q_nope, q_rope, latent = _mla_proj(h, lp, cfg, positions, ad)
            entry = attn_ops.write_mla_entry(
                entry, latent, slot_ids, latent_split=cfg.mla_kv_lora_rank)
            out = _mla_prefill_out(q_nope, q_rope, latent, lp, cfg,
                                   prompt_lens, scale)
            h = _attn_residual(h, out, lp, cfg, ad)
        else:
            q, k, v, hn = _qkv(h, lp, cfg, positions, li, ad)
            # batched prefill attends over the FRESH k/v (full precision even
            # when the cache stores int8 — only cache READS see quantization)
            entry = attn_ops.write_kv_entry(entry, k, v, slot_ids)
            cap = cfg.attn_logit_softcapping
            if attn_impl == "pallas" and mesh is not None:
                from tpuserve.ops.pallas_tp import flash_prefill_attention_tp
                out = flash_prefill_attention_tp(
                    q, k, v, prompt_lens, scale, mesh, sliding_window=sw,
                    logit_softcap=cap)
            elif attn_impl == "pallas":
                from tpuserve.ops.pallas_flash_attention import \
                    flash_prefill_attention
                out = flash_prefill_attention(
                    q, k, v, prompt_lens, scale, sliding_window=sw,
                    logit_softcap=cap)
            else:
                out = attn_ops.prefill_attention(
                    q, k, v, prompt_lens, scale, sliding_window=sw,
                    logit_softcap=cap)
            m = None
            if pool is not None:
                m, pool = _ssm_window(hn, lp["ssm"], cfg, prompt_lens, pool,
                                      seats)
            h = _attn_residual(h, out, lp, cfg, ad, m)
        h = _mlp_residual(h, lp, cfg, ad, tally, moe_dense)
        return h, entry, pool, tally[0] if tally else None


@partial(jax.jit, static_argnames=("cfg", "li", "moe_dense"))
def _nocache_layer(h, lp, positions, lens, *, cfg: ModelConfig, li: int,
                   moe_dense: bool):
    """One layer of the cache-less trunks: h (B, T, hidden), plain causal
    attention over each row's first ``lens`` tokens, every recurrent state
    from zeros, nothing kept."""
    LAYER_TRACES["nocache"] += 1
    if cfg.layer_mixer(li) == MIXER_LINEAR:
        return _mlp_residual(_lin_window(h, lp, cfg, lens)[0], lp, cfg,
                             moe_dense=moe_dense)
    q, k, v, hn = (_mla_naive_qkv(h, lp, cfg, positions) if cfg.is_mla
                   else _qkv(h, lp, cfg, positions, li))
    out = attn_ops.prefill_attention(q, k, v, lens, cfg.attn_scale,
                                     sliding_window=cfg.layer_window(li),
                                     logit_softcap=cfg.attn_logit_softcapping)
    h = _attn_residual(h, out, lp, cfg) + _ssm_nocache(hn, lp, cfg, lens)
    return _mlp_residual(h, lp, cfg, moe_dense=moe_dense)


@partial(jax.jit, static_argnames=("cfg", "li", "phase", "attn_impl",
                                   "mesh", "moe_dense", "aligned"))
def _chunk_layer(h, lp, entry, pool, positions, ctx_lens, chunk_lens,
                 slot_ids, block_tables, ad, seats, *, cfg: ModelConfig,
                 li: int, phase: str, attn_impl: str, mesh, moe_dense: bool,
                 aligned: bool):
    """One layer of :func:`_chunk_trunk`: h (B, C, hidden), a window of
    rows a sequence against its cached context."""
    LAYER_TRACES["chunk"] += 1
    with jax.named_scope(phase):
        tally = _moe_tally(cfg)
        scale, sw = cfg.attn_scale, cfg.layer_window(li)
        if cfg.layer_mixer(li) == MIXER_LINEAR:
            h, pool = _lin_window(h, lp, cfg, chunk_lens, pool, seats,
                                  fresh=ctx_lens == 0)
        elif cfg.is_mla:
            # MLA window: write the latent, attend ABSORBED against the
            # latent pages (k == v == latent; value = first kv_lora cols)
            q_nope, q_rope, latent = _mla_proj(h, lp, cfg, positions, ad)
            entry = attn_ops.write_mla_entry(
                entry, latent, slot_ids, latent_split=cfg.mla_kv_lora_rank,
                aligned=aligned)
            q_eff = _mla_absorb_q(q_nope, q_rope, lp, cfg)
            if attn_impl == "pallas":
                out = _mla_window_attention(
                    q_eff, entry["k"], block_tables, ctx_lens, chunk_lens,
                    scale, cfg.mla_kv_lora_rank)
            else:
                out = attn_ops.chunked_prefill_attention(
                    q_eff, entry["k"], entry["k"], block_tables, ctx_lens,
                    chunk_lens, scale, k_scale=entry.get("ks"),
                    v_scale=entry.get("ks"),
                    scale_slices=(cfg.mla_kv_lora_rank,
                                  cfg.mla_qk_rope_head_dim))
            out = _mla_unabsorb(out, lp, cfg)
            h = _attn_residual(h, out, lp, cfg, ad)
        else:
            q, k, v, hn = _qkv(h, lp, cfg, positions, li, ad)
            entry = attn_ops.write_kv_entry(entry, k, v, slot_ids, aligned)
            ck, cv = entry["k"], entry["v"]
            ks, vs = entry.get("ks"), entry.get("vs")
            if attn_impl == "pallas" and mesh is not None:
                from tpuserve.ops.pallas_tp import paged_window_attention_tp
                out = paged_window_attention_tp(
                    q, ck, cv, block_tables, ctx_lens, chunk_lens, scale, mesh,
                    k_scale=ks, v_scale=vs, sliding_window=sw,
                    logit_softcap=cfg.attn_logit_softcapping)
            elif attn_impl == "pallas":
                from tpuserve.ops.pallas_chunked_prefill import \
                    paged_window_attention
                out = paged_window_attention(
                    q, ck, cv, block_tables, ctx_lens, chunk_lens, scale,
                    k_scale=ks, v_scale=vs, sliding_window=sw,
                    logit_softcap=cfg.attn_logit_softcapping)
            else:
                out = attn_ops.chunked_prefill_attention(
                    q, ck, cv, block_tables, ctx_lens, chunk_lens, scale,
                    k_scale=ks, v_scale=vs, sliding_window=sw,
                    logit_softcap=cfg.attn_logit_softcapping)
            m = None
            if pool is not None:
                m, pool = _ssm_window(hn, lp["ssm"], cfg, chunk_lens, pool,
                                      seats, fresh=ctx_lens == 0)
            h = _attn_residual(h, out, lp, cfg, ad, m)
        h = _mlp_residual(h, lp, cfg, ad, tally, moe_dense)
        return h, entry, pool, tally[0] if tally else None


@partial(jax.jit, static_argnames=("cfg", "li", "attn_impl", "mesh",
                                   "moe_dense"))
def _decode_layer(h, lp, entry, pool, positions, slot_ids, block_tables,
                  seq_lens, ad, seats, *, cfg: ModelConfig, li: int,
                  attn_impl: str, mesh, moe_dense: bool):
    """One layer of :func:`_decode_body`: h (B, hidden), one token a row."""
    LAYER_TRACES["decode"] += 1
    with jax.named_scope(scopes.DECODE):
        tally = _moe_tally(cfg)
        scale, sw = cfg.attn_scale, cfg.layer_window(li)
        if cfg.layer_mixer(li) == MIXER_LINEAR:
            h, pool = _lin_decode(h, lp, cfg, slot_ids, pool, seats, attn_impl)
        elif cfg.is_mla:
            # MLA decode: absorbed attention straight against the latent
            # pages — the step reads one latent page row per cached token
            # instead of 2 * Hkv * head_dim (the ~10x KV-bandwidth win);
            # the Pallas kernel reads V off the K page it landed
            q_nope, q_rope, latent = _mla_proj(h, lp, cfg, positions, ad)
            entry = attn_ops.write_mla_entry(entry, latent, slot_ids,
                                             latent_split=cfg.mla_kv_lora_rank)
            q_eff = _mla_absorb_q(q_nope, q_rope, lp, cfg)
            if attn_impl == "pallas":
                from tpuserve.ops.pallas_paged_attention import \
                    paged_decode_attention
                out = paged_decode_attention(
                    q_eff, entry["k"], None, block_tables, seq_lens, scale,
                    v_lanes=cfg.mla_kv_lora_rank)
            else:
                out = attn_ops.paged_decode_attention(
                    q_eff, entry["k"], entry["k"], block_tables, seq_lens,
                    scale, k_scale=entry.get("ks"), v_scale=entry.get("ks"),
                    scale_slices=(cfg.mla_kv_lora_rank,
                                  cfg.mla_qk_rope_head_dim))
            out = _mla_unabsorb(out, lp, cfg)
            h = _attn_residual(h, out, lp, cfg, ad)
        else:
            q, k, v, hn = _qkv(h, lp, cfg, positions, li, ad)  # (B, Hq/Hkv, D)
            entry = attn_ops.write_kv_entry(entry, k, v, slot_ids)
            ck, cv = entry["k"], entry["v"]
            ks, vs = entry.get("ks"), entry.get("vs")
            if attn_impl == "pallas" and mesh is not None:
                from tpuserve.ops.pallas_tp import paged_decode_attention_tp
                out = paged_decode_attention_tp(
                    q, ck, cv, block_tables, seq_lens, scale, mesh,
                    k_scale=ks, v_scale=vs, sliding_window=sw,
                    logit_softcap=cfg.attn_logit_softcapping)
            else:
                if attn_impl == "pallas":
                    from tpuserve.ops.pallas_paged_attention import \
                        paged_decode_attention as impl
                else:
                    impl = attn_ops.paged_decode_attention
                out = impl(q, ck, cv, block_tables, seq_lens, scale,
                           k_scale=ks, v_scale=vs, sliding_window=sw,
                           logit_softcap=cfg.attn_logit_softcapping)
            m = None
            if pool is not None:
                m, pool = _ssm_decode(
                    hn, lp["ssm"], cfg, slot_ids != attn_ops.PAD_SLOT, pool,
                    seats, attn_impl)
            h = _attn_residual(h, out, lp, cfg, ad, m)
        h = _mlp_residual(h, lp, cfg, ad, tally, moe_dense)
        return h, entry, pool, tally[0] if tally else None


@partial(jax.jit, static_argnames=("cfg", "li", "ragged_blk", "attn_impl",
                                   "decode_rows", "moe_dense", "aligned"))
def _ragged_layer(h, lp, entry, pool, positions, slot_ids, row_seq, row_lens,
                  block_tables, kv_lens, q_starts, q_lens, meta, blk_seq, ad,
                  seats, *, cfg: ModelConfig, li: int, ragged_blk: int,
                  attn_impl: str, decode_rows: bool, moe_dense: bool,
                  aligned: bool):
    """One layer of :func:`forward_ragged`: h (T, hidden), one flat token
    stream."""
    LAYER_TRACES["ragged"] += 1
    with jax.named_scope(scopes.PREFILL):
        tally = _moe_tally(cfg)
        scale, sw = cfg.attn_scale, cfg.layer_window(li)
        if cfg.layer_mixer(li) == MIXER_LINEAR:
            h, pool = _lin_packed(h, lp, cfg, positions, slot_ids, blk_seq,
                                  q_starts, q_lens, ragged_blk, pool, seats)
        elif cfg.is_mla:
            # MLA: absorbed attention against the latent pages just
            # written, like the chunk/decode trunks
            cq, latent = _mla_latents(h, lp, cfg, positions, ad)
            entry = attn_ops.write_mla_entry(
                entry, latent, slot_ids, latent_split=cfg.mla_kv_lora_rank,
                aligned=aligned)
            if attn_impl == "pallas":
                h = _mla_packed_attention(
                    h, cq, positions, lp, cfg, ad, entry["k"], block_tables,
                    kv_lens, q_starts, q_lens, meta, blk_seq, scale,
                    ragged_blk, decode_rows)
            else:
                q_nope, q_rope = _mla_queries(cq, lp, cfg, positions, ad)
                out = _mla_unabsorb(_ragged_reference_attn(
                    _mla_absorb_q(q_nope, q_rope, lp, cfg), entry["k"],
                    entry["k"], block_tables, row_seq, row_lens, blk_seq,
                    meta, ragged_blk, scale, entry.get("ks"),
                    entry.get("ks"), None, None,
                    scale_slices=(cfg.mla_kv_lora_rank,
                                  cfg.mla_qk_rope_head_dim),
                    decode_rows=decode_rows), lp, cfg)
                h = _attn_residual(h, out, lp, cfg, ad)
        else:
            q, k, v, hn = _qkv(h, lp, cfg, positions, li, ad)  # (T, H*, D)
            entry = attn_ops.write_kv_entry(
                entry, k, v, slot_ids, aligned,
                head=decode_region(block_tables.shape[0], ragged_blk)
                if decode_rows else 0)
            ck, cv = entry["k"], entry["v"]
            ks, vs = entry.get("ks"), entry.get("vs")
            if attn_impl == "pallas":
                from tpuserve.ops.pallas_ragged_attention import \
                    ragged_paged_attention
                out = ragged_paged_attention(
                    q, ck, cv, block_tables, kv_lens, q_starts, q_lens,
                    meta, blk_seq, scale, blk_q=ragged_blk, k_scale=ks,
                    v_scale=vs, sliding_window=sw,
                    logit_softcap=cfg.attn_logit_softcapping,
                    decode_rows=decode_rows)
            else:
                out = _ragged_reference_attn(
                    q, ck, cv, block_tables, row_seq, row_lens, blk_seq,
                    meta, ragged_blk, scale, ks, vs, sw,
                    cfg.attn_logit_softcapping, decode_rows=decode_rows)
            m = None
            if pool is not None:
                m, pool = _ssm_packed(
                    hn, lp["ssm"], cfg, positions,
                    slot_ids != attn_ops.PAD_SLOT, blk_seq, q_starts, q_lens,
                    ragged_blk, pool, seats)
            h = _attn_residual(h, out, lp, cfg, ad, m)
        h = _mlp_residual(h, lp, cfg, ad, tally, moe_dense)
        return h, entry, pool, tally[0] if tally else None


# --------------------------------------------------------------------------
# Prefill: process full (padded) prompts, write KV cache, return last logits
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "attn_impl", "mesh", "moe_dense"),
         donate_argnames=("kv_cache", "ssm"))
def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            prompt_lens: jnp.ndarray, slot_ids: jnp.ndarray,
            kv_cache: list, ad: jnp.ndarray | None = None,
            ssm: list | None = None, seats: jnp.ndarray | None = None, *,
            attn_impl: str = "reference", mesh=None,
            moe_dense: bool = False):
    """Run full prompts through the model.

    tokens: (B, T) right-padded prompts; prompt_lens: (B,); slot_ids: (B, T)
    flat cache slots per token (PAD_SLOT for padding); kv_cache: per-layer
    list of {"k","v"} paged caches.  Returns (last_logits (B, V), kv_cache).

    ``mesh``: static; when set with attn_impl="pallas", the Pallas kernels
    run head-parallel over the tp axis via shard_map (ops/pallas_tp.py) —
    GSPMD cannot partition a pallas_call on its own.

    A model with recurrent state (``cfg.has_ssm``) also takes ``ssm`` — the
    seat pool, per layer {"state", "conv"} — and ``seats`` (B,), and
    returns the pool third: this holds for every trunk below that takes
    the KV cache.
    """
    with jax.named_scope(scopes.PREFILL):
        B, T = tokens.shape
        positions = jnp.arange(T)[None, :].repeat(B, axis=0)
        h = _embed(params, cfg, tokens, positions)
        tally = _moe_tally(cfg)
        h, new_cache, new_ssm = _walk_layers(
            "prefill", _prefill_layer, params, cfg, h, kv_cache, ssm, tally,
            positions, prompt_lens, slot_ids, ad, seats,
            attn_impl=attn_impl, mesh=mesh, moe_dense=moe_dense)
        last_idx = jnp.maximum(prompt_lens - 1, 0)
        return _with_ssm(_unembed(params, cfg, h, last_idx), new_cache, ssm,
                         new_ssm,
                         _moe_routing(tally, jnp.arange(B) * T + last_idx))


# --------------------------------------------------------------------------
# Chunked prefill: one bounded chunk of a long prompt against the cache
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "attn_impl", "mesh", "moe_dense"),
         donate_argnames=("kv_cache", "ssm"))
def prefill_chunk(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  ctx_lens: jnp.ndarray, chunk_lens: jnp.ndarray,
                  slot_ids: jnp.ndarray, block_tables: jnp.ndarray,
                  kv_cache: list, ad: jnp.ndarray | None = None,
                  ssm: list | None = None, seats: jnp.ndarray | None = None,
                  *, attn_impl: str = "reference", mesh=None,
                  moe_dense: bool = False):
    """Process one chunk of each prompt against the paged cache.

    Long prompts run as a sequence of fixed-size chunks (bounded memory and
    one compiled shape instead of a per-length bucket — the vLLM
    chunked-prefill analog; the reference delegates this to the vLLM
    container, kubernetes-single-node.yaml:14).

    tokens: (B, C) chunk tokens (right-padded); ctx_lens: (B,) tokens
    already in cache before this chunk; chunk_lens: (B,) valid tokens in the
    chunk; slot_ids: (B, C) cache slots (PAD_SLOT on padding);
    block_tables: (B, max_blocks).  Returns (last_logits (B, V), kv_cache)
    where last_logits is taken at each sequence's final valid chunk row
    (only meaningful on its last chunk).

    ``attn_impl="pallas"`` runs the paged window kernel
    (ops/pallas_chunked_prefill.py); "reference" uses the segmented
    online-softmax einsum in ops/attention.py.  ``mesh``: static; when set
    with pallas, the kernel runs head-parallel over tp via shard_map.
    """
    with jax.named_scope(scopes.CHUNK):
        tally = _moe_tally(cfg)
        # a chunk starts at a whole number of cached blocks (the engine's
        # chunks before it were full ones): where its bucket is whole
        # pages, its K and V go out a page at a time
        h, new_cache, new_ssm = _chunk_trunk(
            params, cfg, tokens, ctx_lens, chunk_lens, slot_ids, block_tables,
            kv_cache, ad, ssm, seats, phase=scopes.CHUNK,
            attn_impl=attn_impl, mesh=mesh, tally=tally, moe_dense=moe_dense,
            aligned=attn_ops.kv_stream_by_page(kv_cache[0], tokens.shape[1],
                                               attn_impl, mesh))
        last_idx = jnp.maximum(chunk_lens - 1, 0)
        return _with_ssm(_unembed(params, cfg, h, last_idx), new_cache, ssm,
                         new_ssm,
                         _moe_routing(tally, jnp.arange(h.shape[0]) * h.shape[1]
                                      + last_idx))


# --------------------------------------------------------------------------
# Embeddings: trunk without KV cache, pooled final hidden states
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "pooling"))
def embed_forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  prompt_lens: jnp.ndarray, *, pooling: str = "mean"):
    """Pooled sentence embeddings for /v1/embeddings (the reference's
    serving stack is vLLM, whose OpenAI surface includes embeddings).

    tokens: (B, T) right-padded; prompt_lens: (B,).  Runs the decoder trunk
    with plain (non-paged) causal attention — no KV cache is written, so
    embedding traffic never touches the serving cache pool — applies the
    final norm, pools over valid positions ("mean" or "last"), and returns
    L2-normalised float32 (B, H).
    """
    with jax.named_scope(scopes.SCORE):
        B, T = tokens.shape
        positions = jnp.arange(T)[None, :].repeat(B, axis=0)
        h = _embed(params, cfg, tokens, positions)
        h = _walk_nocache(params, cfg, h, positions, prompt_lens)
        with jax.named_scope(scopes.HEAD):      # this trunk's head: a pool
            if cfg.final_layernorm:
                h = _norm(h, params["final_norm"], cfg)
            h = h.astype(jnp.float32)
            if pooling == "last":
                last_idx = jnp.maximum(prompt_lens - 1, 0)
                pooled = jnp.take_along_axis(h, last_idx[:, None, None],
                                             axis=1)[:, 0]
            else:                              # masked mean over valid positions
                mask = (jnp.arange(T)[None, :] < prompt_lens[:, None])[..., None]
                pooled = jnp.sum(h * mask, axis=1) / \
                    jnp.maximum(prompt_lens[:, None], 1).astype(jnp.float32)
            return pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


@partial(jax.jit, static_argnames=("cfg", "top_n", "chunk"))
def score_prompt(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 prompt_lens: jnp.ndarray, *, top_n: int = 0,
                 chunk: int = 16):
    """Per-position prompt logprobs (OpenAI ``echo`` + ``logprobs``; the
    vLLM ``prompt_logprobs`` surface — served by the stack the reference
    deploys).

    tokens: (B, T) right-padded, T a multiple of ``chunk``; prompt_lens:
    (B,).  Runs the cache-less causal trunk, then scores the UNEMBED in
    (B, chunk, V) slices — materialising all (B, T, V) float32 logits at
    a 150k vocab would cost GBs for a page of text.  Returns
    (chosen (B, T), ranks (B, T), top_ids (B, T, top_n),
    top_lps (B, T, top_n)) where ``chosen[:, i]`` is
    log p(token_{i+1} | tokens_{<=i}) and ``ranks[:, i]`` its 1-based
    FULL-VOCAB rank (vLLM's prompt_logprobs contract) — callers shift by
    one (the first prompt token has no conditional).
    """
    with jax.named_scope(scopes.SCORE):
        B, T = tokens.shape
        positions = jnp.arange(T)[None, :].repeat(B, axis=0)
        h = _embed(params, cfg, tokens, positions)
        h = _walk_nocache(params, cfg, h, positions, prompt_lens)
        # next-token targets: position i scores tokens[i+1]
        nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)],
                              axis=1)
        n_chunks = T // chunk
        hs = h.reshape(B, n_chunks, chunk, -1).swapaxes(0, 1)
        ns = nxt.reshape(B, n_chunks, chunk).swapaxes(0, 1)
        k_eff = min(top_n, cfg.vocab_size) if top_n else 0

        def one(args):
            hc, nc = args                            # (B, chunk, H), (B, chunk)
            logits = _unembed(params, cfg, hc)
            with jax.named_scope(scopes.SAMPLE):
                lps = jax.nn.log_softmax(logits, axis=-1)
                chosen = jnp.take_along_axis(lps, nc[..., None],
                                             axis=-1)[..., 0]
                rank = (jnp.sum(lps > chosen[..., None], axis=-1)
                        .astype(jnp.int32) + 1)      # 1-based full-vocab rank
                if k_eff:
                    tl, ti = jax.lax.top_k(lps, k_eff)
                else:
                    ti = jnp.zeros(nc.shape + (0,), jnp.int32)
                    tl = jnp.zeros(nc.shape + (0,), jnp.float32)
                return chosen, rank, ti.astype(jnp.int32), tl

        chosen, ranks, top_ids, top_lps = jax.lax.map(one, (hs, ns))
        merge = lambda x: x.swapaxes(0, 1).reshape((B, T) + x.shape[3:])
        return merge(chosen), merge(ranks), merge(top_ids), merge(top_lps)


# --------------------------------------------------------------------------
# Speculative verify: score a draft window, return per-row greedy argmax
# --------------------------------------------------------------------------

def _chunk_trunk(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 ctx_lens: jnp.ndarray, chunk_lens: jnp.ndarray,
                 slot_ids: jnp.ndarray, block_tables: jnp.ndarray,
                 kv_cache: list, ad: jnp.ndarray | None = None,
                 ssm: list | None = None, seats: jnp.ndarray | None = None,
                 *, phase: str, attn_impl: str = "reference", mesh=None,
                 tally: list | None = None, moe_dense: bool = False,
                 aligned: bool = False):
    """Shared layer loop for cache-relative windows: writes the window's KV
    and attends against cached context + causal-within-window.  Used by both
    prefill_chunk (last-row logits; ``aligned``: its rows are whole pages
    in order, ops/attention.py write_kv_entry) and decode_verify (all-row
    argmax); ``phase`` is the scope its caller opened.  Returns (h,
    kv_cache, seat pool or None)."""
    positions = ctx_lens[:, None] + jnp.arange(tokens.shape[1])[None, :]
    h = _embed(params, cfg, tokens, positions)
    h, new_cache, new_ssm = _walk_layers(
        "chunk", _chunk_layer, params, cfg, h, kv_cache, ssm, tally, positions,
        ctx_lens, chunk_lens, slot_ids, block_tables, ad, seats, phase=phase,
        attn_impl=attn_impl, mesh=mesh, moe_dense=moe_dense, aligned=aligned)
    return h, new_cache, new_ssm or None


@partial(jax.jit, static_argnames=("cfg", "attn_impl", "mesh"),
         donate_argnames=("kv_cache",))
def decode_verify(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  ctx_lens: jnp.ndarray, chunk_lens: jnp.ndarray,
                  slot_ids: jnp.ndarray, block_tables: jnp.ndarray,
                  kv_cache: list, *, attn_impl: str = "reference",
                  mesh=None):
    """Verify a speculative draft window in one pass.

    Same trunk as :func:`prefill_chunk` but returns the greedy argmax at
    EVERY row — ``pred[:, j]`` is the model's next token after consuming
    row j, which is all greedy draft acceptance needs (returning (B, K, V)
    logits would move hundreds of MB for nothing).

    tokens: (B, K) = [last_sampled, draft_0, ..]; ctx_lens: (B,) tokens in
    cache before the window; chunk_lens: (B,) valid rows; slot_ids: (B, K);
    block_tables: (B, max_blocks).  Returns (pred (B, K) int32, kv_cache).
    """
    with jax.named_scope(scopes.VERIFY):
        h, new_cache, _ = _chunk_trunk(params, cfg, tokens, ctx_lens,
                                       chunk_lens, slot_ids, block_tables,
                                       kv_cache, phase=scopes.VERIFY,
                                       attn_impl=attn_impl, mesh=mesh)
        logits = _unembed(params, cfg, h)                       # (B, K, V)
        with jax.named_scope(scopes.SAMPLE):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache


@partial(jax.jit, static_argnames=("cfg", "attn_impl", "mesh"),
         donate_argnames=("kv_cache",))
def decode_verify_sampled(params: Params, cfg: ModelConfig,
                          tokens: jnp.ndarray, ctx_lens: jnp.ndarray,
                          chunk_lens: jnp.ndarray, slot_ids: jnp.ndarray,
                          block_tables: jnp.ndarray, kv_cache: list,
                          keys: jnp.ndarray, temperature: jnp.ndarray,
                          top_k: jnp.ndarray, top_p: jnp.ndarray,
                          min_p: jnp.ndarray | None = None, *,
                          attn_impl: str = "reference", mesh=None):
    """Verify a speculative draft window under SAMPLING: same trunk as
    :func:`decode_verify`, but instead of greedy argmax the full (B,K,V)
    logits stay on device and run rejection-sampling acceptance
    (ops/sampling.py spec_accept_sampled) — so speculation composes with
    temperature/top-k/top-p instead of being greedy-only.  The draft
    tokens being judged are the verify INPUT rows shifted by one
    (``tokens[:, 1:]``).  temperature <= 0 rows degenerate to exact
    greedy acceptance.  Returns (accept (B, K-1) bool, pred (B, K) int32,
    kv_cache)."""
    with jax.named_scope(scopes.VERIFY):
        from tpuserve.ops.sampling import spec_accept_sampled
        h, new_cache, _ = _chunk_trunk(params, cfg, tokens, ctx_lens,
                                       chunk_lens, slot_ids, block_tables,
                                       kv_cache, phase=scopes.VERIFY,
                                       attn_impl=attn_impl, mesh=mesh)
        logits = _unembed(params, cfg, h)                       # (B, K, V)
        accept, pred = spec_accept_sampled(logits, tokens[:, 1:], chunk_lens,
                                           keys, temperature, top_k, top_p,
                                           min_p)
        return accept, pred, new_cache


# --------------------------------------------------------------------------
# Decode: one token per sequence against the paged cache
# --------------------------------------------------------------------------

def window_slot(block_tables: jnp.ndarray, pos: jnp.ndarray,
                active: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """On-device cache-slot derivation for one fused-window iteration —
    shared by :func:`decode_multi` and the pipelined
    parallel.pipeline.pp_decode_multi so the two window implementations
    can't drift.  Inactive (padding) rows write to PAD_SLOT (dropped)."""
    with jax.named_scope(scopes.CARRY):
        slot = (jnp.take_along_axis(block_tables,
                                    (pos // block_size)[:, None],
                                    axis=1)[:, 0]
                * block_size + pos % block_size)
        return jnp.where(active, slot, attn_ops.PAD_SLOT)


def window_extras(logits: jnp.ndarray, s: jnp.ndarray, cnt, presence,
                  frequency, repetition, bias, floor_bias,
                  floor_remaining):
    """Apply the in-window sampling extras to one iteration's logits:
    penalties from the (B, V) count carry, the dense per-row logit_bias,
    and the min_tokens floor mask (lifted when the row's output length —
    dispatch length + s — crosses its floor).  ONE home shared by
    decode_multi and pp_decode_multi so the two fused-window
    implementations cannot drift.  No-op when ``cnt`` is None (the
    extras always travel together; unused ones are zeros)."""
    if cnt is None:
        return logits
    from tpuserve.ops.sampling import penalize_from_counts
    with jax.named_scope(scopes.SAMPLE):
        logits = penalize_from_counts(logits, cnt, presence, frequency,
                                      repetition)
        if bias is not None:
            logits = logits + bias
        if floor_bias is not None:
            logits = logits + jnp.where(
                (s < floor_remaining)[:, None], floor_bias, 0.0)
        return logits


def window_count_update(cnt, nxt):
    """Fold the iteration's sampled tokens into the count carry (None
    passes through) — the other half of the in-window penalties
    contract, shared like :func:`window_extras`."""
    if cnt is None:
        return None
    with jax.named_scope(scopes.CARRY):
        return cnt.at[jnp.arange(cnt.shape[0]), nxt].add(1.0)


def window_unpack_lp(outs):
    """Unpack a fused window's scan outputs when in-window logprobs rode
    along: (tokens (B, steps), (chosen (B, steps), ids (B, steps, N),
    lps (B, steps, N))).  Scan stacks along the STEP axis; the engine's
    flush indexes [row, step], so everything swaps here — one home for
    the layout, shared by decode_multi and pp_decode_multi."""
    outs, (chosen_lp, top_ids, top_lps) = outs
    return jnp.swapaxes(outs, 0, 1), (jnp.swapaxes(chosen_lp, 0, 1),
                                      jnp.swapaxes(top_ids, 0, 1),
                                      jnp.swapaxes(top_lps, 0, 1))


def window_guided_mask(logits: jnp.ndarray, gstate: jnp.ndarray,
                       gmasks: jnp.ndarray) -> jnp.ndarray:
    """One fused-window iteration's grammar-FSM logit mask: gather each
    guided row's packed allow bitmask by its CURRENT FSM state and drop
    disallowed tokens to NEG_INF before sampling (ops/sampling.py
    apply_token_mask).  ``gstate`` (B,) int32, -1 = unguided row (passes
    through); ``gmasks`` (N, ceil(V/32)) uint32, the grammar's device-
    cached state-mask table (runtime/grammar/fsm.py layout).  Applied
    AFTER window_extras, exactly like the per-step path (penalties ->
    bias -> floor -> grammar mask -> sample), so the two paths stay
    token-identical."""
    from tpuserve.ops.sampling import apply_token_mask
    with jax.named_scope(scopes.SAMPLE):
        rows = gmasks[jnp.clip(gstate, 0, gmasks.shape[0] - 1)]
        return apply_token_mask(logits, rows, gstate >= 0)


def window_guided_advance(gstate: jnp.ndarray, nxt: jnp.ndarray,
                          gclass: jnp.ndarray,
                          gnext: jnp.ndarray) -> jnp.ndarray:
    """The other half of the in-window FSM contract: advance each guided
    row's state by its sampled token through the class-compressed
    transition table (``gclass`` (V,) token->class, ``gnext`` (N, C)
    delta).  Unguided rows (-1) stay -1.  The host replays the SAME
    table at window flush (engine._emit_one), so host mirror and device
    carry cannot drift."""
    with jax.named_scope(scopes.CARRY):
        ns = gnext[jnp.clip(gstate, 0, gnext.shape[0] - 1), gclass[nxt]]
        return jnp.where(gstate >= 0, ns, gstate)


def window_sample(logits: jnp.ndarray, keys: jnp.ndarray,
                  temperature: jnp.ndarray, s: jnp.ndarray,
                  mode: str, top_k: jnp.ndarray | None = None,
                  top_p: jnp.ndarray | None = None,
                  min_p: jnp.ndarray | None = None) -> jnp.ndarray:
    """One fused-window sampling step: greedy argmax, temperature, or
    "full" (per-row top-k/top-p/min-p truncation — so the common
    production sampling configs keep fused-window throughput instead of
    falling to per-token dispatches).  The per-row key's step word folds
    by +s, matching the engine's host-side per-step key construction.
    One source of truth for both window implementations."""
    from tpuserve.ops import sampling as sampling_ops
    with jax.named_scope(scopes.SAMPLE):
        if mode == "greedy":
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        B = logits.shape[0]
        step_key = jnp.array([0, 1], jnp.uint32)[None, :]
        stepped = keys + step_key * s.astype(jnp.uint32)
        if mode == "temperature":
            return sampling_ops.sample_tokens(
                logits, stepped, temperature,
                jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32),
                mode="temperature")
        return sampling_ops.sample_tokens(
            logits, stepped, temperature, top_k, top_p, min_p=min_p,
            mode="full")

def _decode_body(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 positions: jnp.ndarray, slot_ids: jnp.ndarray,
                 block_tables: jnp.ndarray, seq_lens: jnp.ndarray,
                 kv_cache: list, attn_impl: str, mesh,
                 ad: jnp.ndarray | None = None, ssm: list | None = None,
                 seats: jnp.ndarray | None = None,
                 moe_dense: bool = False):
    """Shared single-token decode trunk: write the token's KV, attend
    against the paged cache, return (logits (B, V), new kv_cache, seat
    pool or None, routing or None).  Used by :func:`decode_step` (one dispatch per token) and
    :func:`decode_multi` (scanned — one dispatch per window)."""
    h = _embed(params, cfg, tokens, positions)                 # (B, H)
    tally = _moe_tally(cfg)
    h, new_cache, new_ssm = _walk_layers(
        "decode", _decode_layer, params, cfg, h, kv_cache, ssm, tally,
        positions, slot_ids, block_tables, seq_lens, ad, seats,
        attn_impl=attn_impl, mesh=mesh, moe_dense=moe_dense)
    return (_unembed(params, cfg, h), new_cache, new_ssm or None,
            _moe_routing(tally, None))


@partial(jax.jit, static_argnames=("cfg", "attn_impl", "mesh", "moe_dense"),
         donate_argnames=("kv_cache", "ssm"))
def decode_step(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                positions: jnp.ndarray, slot_ids: jnp.ndarray,
                block_tables: jnp.ndarray, seq_lens: jnp.ndarray,
                kv_cache: list, ad: jnp.ndarray | None = None,
                ssm: list | None = None, seats: jnp.ndarray | None = None,
                *, attn_impl: str = "reference", mesh=None,
                moe_dense: bool = False):
    """One decode step for a batch of sequences.

    tokens/positions/slot_ids/seq_lens: (B,); block_tables: (B, max_blocks).
    seq_lens includes the token being decoded (its K/V is written first).
    Returns (logits (B, V), kv_cache).

    ``mesh``: static; see :func:`prefill` — head-parallel Pallas under tp.
    """
    with jax.named_scope(scopes.DECODE):
        logits, new_cache, new_ssm, moe = _decode_body(
            params, cfg, tokens, positions, slot_ids, block_tables, seq_lens,
            kv_cache, attn_impl, mesh, ad=ad, ssm=ssm, seats=seats,
            moe_dense=moe_dense)
        return _with_ssm(logits, new_cache, ssm, new_ssm, moe)


@partial(jax.jit,
         static_argnames=("cfg", "steps", "mode", "logprobs_n", "attn_impl",
                          "mesh", "out_mesh", "moe_dense"),
         donate_argnames=("kv_cache", "ssm"))
def decode_multi(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                 positions: jnp.ndarray, block_tables: jnp.ndarray,
                 seq_lens: jnp.ndarray, active: jnp.ndarray,
                 keys: jnp.ndarray, temperature: jnp.ndarray,
                 kv_cache: list, ad: jnp.ndarray | None = None,
                 ssm: list | None = None, seats: jnp.ndarray | None = None,
                 *, steps: int, mode: str = "greedy",
                 top_k: jnp.ndarray | None = None,
                 top_p: jnp.ndarray | None = None,
                 min_p: jnp.ndarray | None = None,
                 logprobs_n: int = 0,
                 counts: jnp.ndarray | None = None,
                 presence: jnp.ndarray | None = None,
                 frequency: jnp.ndarray | None = None,
                 repetition: jnp.ndarray | None = None,
                 bias: jnp.ndarray | None = None,
                 floor_bias: jnp.ndarray | None = None,
                 floor_remaining: jnp.ndarray | None = None,
                 gstate: jnp.ndarray | None = None,
                 gmasks: jnp.ndarray | None = None,
                 gclass: jnp.ndarray | None = None,
                 gnext: jnp.ndarray | None = None,
                 attn_impl: str = "reference", mesh=None, out_mesh=None,
                 moe_dense: bool = False):
    """``steps`` fused decode+sample iterations in ONE dispatch.

    The sampled token feeds the next iteration entirely on device
    (``lax.scan`` over the shared decode trunk), so the host syncs once per
    window instead of once per token — the decisive lever when dispatch
    latency is non-trivial (remote TPU backends, multi-host lockstep
    broadcasts).  This is the JetStream-style on-device decode loop that
    replaces the per-step CUDA launches inside the vLLM image the reference
    deploys (reference: kubernetes-single-node.yaml:14).

    tokens/positions/seq_lens: (B,) first-iteration state, same meaning as
    :func:`decode_step`; active: (B,) bool marking real rows (padding rows
    never write KV); keys: (B, 2) uint32 per-row sampling keys whose second
    word is the row's step index (folded +s each iteration, matching the
    engine's per-step key construction); temperature: (B,).
    ``mode``: "greedy" (argmax; keys/temperature ignored), "temperature",
    or "full" (per-row ``top_k``/``top_p``/``min_p`` truncation inside the
    window — ops/sampling.py sample_tokens semantics).  Cache slots for
    the whole window must be pre-reserved: slot ids are computed on device
    from ``block_tables`` and the advancing positions.

    Guided decoding rides the window via the grammar-FSM carry
    (runtime/grammar/): ``gstate`` (B,) int32 per-row FSM state (-1 =
    unguided row) with the grammar's device-cached tables — ``gmasks``
    (N, ceil(V/32)) uint32 packed allow bitmasks, ``gclass`` (V,) int32
    token->class, ``gnext`` (N, C) int32 delta.  Each iteration masks
    logits by the row's current state BEFORE sampling and advances the
    state by the sampled token, folding the per-step host-FSM loop
    entirely into the scan.

    Returns (tokens (B, steps) int32, kv_cache[, logprobs][, gstate']
    [, ssm][, moe]) — the logprobs triple when ``logprobs_n`` (with the
    rows' picks ``(B, steps, expert layers, k)`` fourth for a model with
    experts), the final (B,) FSM states when ``gstate`` was passed, the
    seat pool (a carry of the scan like the cache, updated in place) when
    ``ssm`` was, the routing (:func:`_moe_routing`: the counts summed over
    the steps, and None for the picks, which ride with the logprobs here)
    for a model with experts.
    """
    with jax.named_scope(scopes.DECODE):
        B = tokens.shape[0]
        block_size = kv_cache[0]["k"].shape[1]
        guided = gstate is not None

        def one(carry, s):
            if guided:
                toks, pos, lens, cache, cnt, pool, gst = carry
            else:
                (toks, pos, lens, cache, cnt, pool), gst = carry, None
            slot = window_slot(block_tables, pos, active, block_size)
            logits, cache, pool, moe = _decode_body(
                params, cfg, toks, pos, slot, block_tables, lens, cache,
                attn_impl, mesh, ad=ad, ssm=pool, seats=seats,
                moe_dense=moe_dense)
            # extras ordered before sampling AND before logprobs, exactly
            # like the per-step path (penalties -> bias -> floor); whichever
            # features aren't in play ride along as zeros so one executable
            # family covers them all
            logits = window_extras(logits, s, cnt, presence, frequency,
                                   repetition, bias, floor_bias,
                                   floor_remaining)
            if guided:
                # grammar-FSM mask LAST, like the per-step path: the sampler
                # renormalises over exactly the legal token set
                logits = window_guided_mask(logits, gst, gmasks)
            nxt = window_sample(logits, keys, temperature, s, mode,
                                top_k=top_k, top_p=top_p, min_p=min_p)
            if guided:
                gst = window_guided_advance(gst, nxt, gclass, gnext)
            cnt = window_count_update(cnt, nxt)
            ys = nxt
            if logprobs_n:
                # sampled-token + top-N logprobs computed in-window, so
                # logprobs requests keep fused-window throughput (the engine
                # previously dropped them to per-token dispatches)
                from tpuserve.ops.sampling import compute_logprobs
                ys = (nxt, compute_logprobs(logits, nxt, logprobs_n))
            if moe is not None:
                # a step's routing counts ride out beside its tokens, and its
                # rows' picks where their logprobs do
                ys = (ys, moe[:2] if logprobs_n else moe[:1])
            with jax.named_scope(scopes.CARRY):
                new_carry = (nxt, pos + 1, lens + 1, cache, cnt, pool)
            if guided:
                new_carry += (gst,)
            return new_carry, ys

        carry = (tokens, positions, seq_lens, kv_cache, counts, ssm)
        if guided:
            carry += (gstate,)
        final, outs = jax.lax.scan(
            one, carry, jnp.arange(steps, dtype=jnp.int32))
        kv_cache = final[3]
        moe = None
        lp = None
        # what the window hands back, laid out [row, step]
        with jax.named_scope(scopes.CARRY):
            if cfg.routes_experts:
                outs, moe = outs
                picks = moe[1:]                         # ((steps, B, L, k),)
                moe = jnp.sum(moe[0], axis=0), None, None   # over the steps
            if logprobs_n:
                out, lp = window_unpack_lp(outs)
                if moe is not None:
                    lp += (jnp.swapaxes(picks[0], 0, 1),)   # like lp
            else:
                out = jnp.swapaxes(outs, 0, 1)                     # (B, steps)
        if out_mesh is not None:
            # Multi-host lockstep device_gets the window on the coordinator;
            # force the small token matrix to be fully replicated/addressable.
            # ``out_mesh`` is the engine's full mesh — distinct from ``mesh``,
            # which is only set when the Pallas kernels are head-partitionable.
            from jax.sharding import NamedSharding, PartitionSpec
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(out_mesh, PartitionSpec()))
        res = (out, kv_cache)
        if logprobs_n:
            res += (lp,)
        if guided:
            res += (final[6],)
        if ssm is not None:
            res += (final[5],)
        if moe is not None:
            res += (moe,)
        return res


# --------------------------------------------------------------------------
# Ragged mixed prefill+decode: one flat token stream, no phase split
# --------------------------------------------------------------------------

def _ragged_reference_attn(q, ck, cv, block_tables, row_seq, row_lens,
                           blk_seq, meta, blk: int, scale, ks, vs, sw,
                           softcap, scale_slices=None, decode_rows=True):
    """Reference (non-Pallas) ragged attention for one mixed layer:

    - prefill-chunk blocks take the BLOCK-gather path (one KV gather per
      ``blk`` rows — attn_ops.ragged_blocked_attention; the gather is
      what dominates a pure-JAX mixed step);
    - decode rows (the first ``meta[0]`` rows, always within the first
      ``max_num_seqs`` rows) are overlaid with the per-row DENSE paged
      decode attention — the exact math of the phase-split decode trunk,
      so decode-row logits are bit-identical between mixed and
      phase-split (the seeded-sampling token-identity contract).
      ``decode_rows=False`` (a packed batched prefill: ``meta`` is zero)
      leaves the overlay out.
    """
    with jax.named_scope(scopes.ATTN_KERNEL):
        T = q.shape[0]
        out = attn_ops.ragged_blocked_attention(
            q, ck, cv, block_tables[jnp.clip(blk_seq, 0, None)], row_lens,
            blk, scale, k_scale=ks, v_scale=vs, sliding_window=sw,
            logit_softcap=softcap, scale_slices=scale_slices)
        if not decode_rows:
            return out
        # static head slice: decode rows r < meta[0] are rows r themselves,
        # and meta[0] <= max_num_seqs <= block_tables.shape[0]
        Bc = min(block_tables.shape[0], T)
        head = attn_ops.paged_decode_attention(
            q[:Bc], ck, cv, block_tables[row_seq[:Bc]], row_lens[:Bc], scale,
            k_scale=ks, v_scale=vs, sliding_window=sw, logit_softcap=softcap,
            scale_slices=scale_slices)
        head = jnp.pad(head, ((0, T - Bc), (0, 0), (0, 0)))
        is_dec = (jnp.arange(T) < meta[0])[:, None, None]
        return jnp.where(is_dec, head, out)


def decode_region(seqs: int, ragged_blk: int) -> int:
    """Rows at the head of a mixed step's flat stream that its decode rows
    own, whatever their number: whole ragged blocks for the descriptor's
    ``seqs`` sequences, one row each.  Static, so the prompt chunks behind
    it start at the same row in every dispatch of an executable (the
    engine packs them there, the scheduler charges the region, the K/V
    write sends what lies behind it out by the page)."""
    return -(-seqs // ragged_blk) * ragged_blk


@partial(jax.jit,
         static_argnames=("cfg", "ragged_blk", "attn_impl", "decode_rows",
                          "moe_dense"),
         donate_argnames=("kv_cache", "ssm"))
def forward_ragged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                   positions: jnp.ndarray, slot_ids: jnp.ndarray,
                   row_seq: jnp.ndarray, block_tables: jnp.ndarray,
                   kv_lens: jnp.ndarray, q_starts: jnp.ndarray,
                   q_lens: jnp.ndarray, meta: jnp.ndarray,
                   blk_seq: jnp.ndarray, last_rows: jnp.ndarray,
                   kv_cache: list, ad: jnp.ndarray | None = None,
                   ssm: list | None = None,
                   seats: jnp.ndarray | None = None, *,
                   ragged_blk: int = 8, attn_impl: str = "reference",
                   decode_rows: bool = True, moe_dense: bool = False):
    """One MIXED prefill+decode step over a flat token stream.

    The phase-split engine runs prefill batches and decode steps as
    separate dispatches with separate (batch x length) padding grids;
    this trunk serves decode rows (q_len 1) and prefill chunks (q_len
    > 1) from ONE (T,) token stream in one dispatch ("Ragged Paged
    Attention", PAPERS.md) — bucketing collapses to the single flat-token
    dimension T.

    tokens/positions/slot_ids/row_seq: (T,) — per-row token id, global
    sequence position (drives per-row rope), flat cache slot (PAD_SLOT on
    padding rows), owning-sequence index.  block_tables (B, max_blocks) /
    kv_lens / q_starts / q_lens: (B,) per-sequence descriptors (kv_lens
    INCLUDES this window's tokens); meta (2,) [num_decode_rows,
    num_decode_blocks] and blk_seq (T // ragged_blk,) describe the Pallas
    kernel's block layout (ops/pallas_ragged_attention.py — ignored on
    the reference path); last_rows: (B,) flat row of each sequence's last
    valid token, where the logits are taken (meaningful for decode rows
    and for a prompt's final chunk — exactly the prefill_chunk contract).
    ``decode_rows=False`` (static): the stream holds prompts only (``meta``
    is zero: the engine's packed batched prefill), and the attention is
    built without its decode part.  With decode rows the stream's first
    ``decode_region(B, ragged_blk)`` rows are theirs (padding past the
    last of them) and every prompt chunk starts behind that region.

    Semantics per row are exactly the cache-relative window semantics:
    each row's KV is written first, then the row attends its own
    sequence's cached keys at positions ``<= position``.  Returns
    (last_logits (B, V), kv_cache).

    With recurrent state (``ssm``, ``seats`` (B,)) the stream must be a
    packed prefill of prompts that each start at position 0: decode rows
    lie one a row, so a chunk of the scan would straddle sequences, and a
    prompt continued from the cache would need its seat's state mid-stream
    (the engine keeps mixed steps and the prefix cache off for such a
    model).
    """
    with jax.named_scope(scopes.PREFILL):
        if ssm is not None and decode_rows:
            raise ValueError("a model with recurrent state takes the ragged "
                             "trunk for packed prefills only (decode_rows=False)")
        h = _embed(params, cfg, tokens, positions)                 # (T, H)
        row_lens = positions + 1
        tally = _moe_tally(cfg)
        # a prompt (chunk) starts on a ragged-block boundary at a whole
        # number of cached blocks (Engine._pack_ragged): where that block
        # is whole pages, its K and V go out a page at a time; a mixed
        # step's decode region (``decode_region``: its rows lie one to a
        # page) stands before them and keeps the row scatter, and under
        # latent attention its whole stream does
        aligned = attn_ops.kv_stream_by_page(
            kv_cache[0], ragged_blk, attn_impl) and not (
                decode_rows and cfg.is_mla)
        h, new_cache, new_ssm = _walk_layers(
            "ragged", _ragged_layer, params, cfg, h, kv_cache, ssm, tally,
            positions, slot_ids, row_seq, row_lens, block_tables, kv_lens,
            q_starts, q_lens, meta, blk_seq, ad, seats, ragged_blk=ragged_blk,
            attn_impl=attn_impl, decode_rows=decode_rows, moe_dense=moe_dense,
            aligned=aligned)
        return _with_ssm(_unembed(params, cfg, h, last_rows), new_cache, ssm,
                         new_ssm, _moe_routing(tally, last_rows))


@partial(jax.jit, static_argnames=("cfg", "k"))
def draft_propose(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lens: jnp.ndarray, *, k: int):
    """Stateless draft-model proposal for speculative decoding.

    tokens: (B, W + k) — the last W context tokens right-padded with k
    scratch slots; lens: (B,) valid context lengths.  Runs the cache-less
    causal trunk k times, each pass extending every row by its greedy
    next token — no draft KV cache, so the draft needs no block-manager
    mirroring of the target's sequence lifecycle (the design risk of
    draft-model speculation; vLLM manages a second paged cache instead).
    k cache-less passes over a W-token window on a SMALL draft model cost
    less than one verify pass on the target; the truncated context is the
    quality trade the acceptance governor prices online.

    Returns (B, k) int32 proposals.
    """
    with jax.named_scope(scopes.DRAFT):
        B, T = tokens.shape

        positions = jnp.arange(T)[None, :].repeat(B, axis=0)

        def one(carry, j):
            toks, cur = carry
            h = _embed(params, cfg, toks, positions)
            h = _walk_nocache(params, cfg, h, positions, cur)
            # unembed ONLY each row's last position — the full (B, T, V)
            # logits would be GBs at serving batch sizes
            logits = _unembed(params, cfg, h, cur - 1)
            with jax.named_scope(scopes.SAMPLE):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            with jax.named_scope(scopes.CARRY):
                toks = jnp.where(jnp.arange(T)[None, :] == cur[:, None],
                                 nxt[:, None], toks)
                return (toks, cur + 1), nxt

        (_, _), outs = jax.lax.scan(one, (tokens, lens),
                                    jnp.arange(k, dtype=jnp.int32))
        return jnp.swapaxes(outs, 0, 1)                      # (B, k)


# --------------------------------------------------------------------------
# Plain forward (no cache) — for fine-tuning / the graft entry point
# --------------------------------------------------------------------------

def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            seq_lens: jnp.ndarray | None = None) -> jnp.ndarray:
    """Causal LM forward over (B, T) tokens -> (B, T, V) float32 logits.
    What ``jax.grad`` differentiates (parallel/train.py), so an expert
    layer runs its dense form here: the grouped-product kernel has no
    derivative rule (and the serving trunks never come this way)."""
    B, T = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((B,), T, jnp.int32)
    positions = jnp.arange(T)[None, :].repeat(B, axis=0)
    h = _embed(params, cfg, tokens, positions)
    h = _walk_nocache(params, cfg, h, positions, seq_lens, moe_dense=True)
    return _unembed(params, cfg, h)
