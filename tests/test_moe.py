"""MoE / expert-parallel tests (Qwen3-MoE family).

The reference's default model is dense (llm-d-deploy.yaml:118), but the vLLM
image it deploys serves MoE checkpoints too; here the routed-experts MLP
(models/transformer._moe_mlp), its EP sharding (parallel/sharding.py), the
HF expert-weight loader, and int8 expert quantization each get direct
assertions — the r2 verdict's "shipped-untested" gap.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.models import transformer, weights
from tpuserve.models.config import config_from_hf_json, get_model_config
from tpuserve.parallel import MeshConfig, cache_shardings, make_mesh, shard_params
from tpuserve.parallel.mesh import AXIS_EP
from tpuserve.runtime.kv_cache import CacheConfig, create_kv_cache


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_model_config("tiny-moe"), dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return weights.init_params(cfg, seed=3)


def naive_moe(x, lp, cfg):
    """Per-token python-loop reference for _moe_mlp: for each token, run only
    its top-k experts and combine with (renormalised) router weights."""
    x = np.asarray(x, np.float32)
    router = x @ np.asarray(lp["router"]["kernel"], np.float32)
    probs = np.exp(router - router.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    gk = np.asarray(lp["experts"]["gate_proj"]["kernel"], np.float32)
    uk = np.asarray(lp["experts"]["up_proj"]["kernel"], np.float32)
    dk = np.asarray(lp["experts"]["down_proj"]["kernel"], np.float32)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(probs[t])[::-1][: cfg.num_experts_per_tok]
        w = probs[t][top]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        for e, we in zip(top, w):
            g = x[t] @ gk[e]
            u = x[t] @ uk[e]
            h = (g / (1 + np.exp(-g))) * u          # silu(g) * u
            out[t] += we * (h @ dk[e])
    return out


def test_moe_mlp_matches_per_token_loop(cfg, params):
    lp = params["layers"][0]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((6, cfg.hidden_size)),
                    jnp.float32)
    got = np.asarray(transformer._mlp(x, lp, cfg))
    want = naive_moe(x, lp, cfg)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_moe_reduces_to_dense_when_experts_identical(cfg, params):
    """With every expert holding expert-0's weights and norm_topk_prob=True,
    the combine weights sum to 1 and the routed MLP must equal the plain
    dense gated MLP with those weights."""
    assert cfg.norm_topk_prob
    lp = dict(params["layers"][0])
    ek = lp["experts"]
    tiled = {
        proj: {"kernel": jnp.broadcast_to(
            ek[proj]["kernel"][:1], ek[proj]["kernel"].shape)}
        for proj in ("gate_proj", "up_proj", "down_proj")}
    lp["experts"] = tiled
    x = jnp.asarray(np.random.default_rng(1).standard_normal((5, cfg.hidden_size)),
                    jnp.float32)
    moe_out = np.asarray(transformer._mlp(x, lp, cfg))

    dense_cfg = dataclasses.replace(
        cfg, num_experts=0, intermediate_size=cfg.expert_intermediate_size)
    dense_lp = {
        "gate_proj": {"kernel": ek["gate_proj"]["kernel"][0]},
        "up_proj": {"kernel": ek["up_proj"]["kernel"][0]},
        "down_proj": {"kernel": ek["down_proj"]["kernel"][0]},
    }
    dense_out = np.asarray(transformer._mlp(x, dense_lp, dense_cfg))
    np.testing.assert_allclose(moe_out, dense_out, atol=1e-5, rtol=1e-5)


def test_moe_engine_greedy_matches_forward_rollout(cfg, params):
    """The serving engine (paged cache, bucketed prefill/decode) greedy-decodes
    the same continuation as argmax over full-context forward recomputes."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)
    eng = Engine(
        EngineConfig(
            model="tiny-moe",
            cache=CacheConfig(block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, dtype="float32"),
            scheduler=SchedulerConfig(min_prefill_bucket=8, min_decode_bucket=2)),
        params=params, model_cfg=cfg)
    prompt = [5, 6, 7, 8, 9]
    n_gen = 6
    out = eng.generate([prompt], SamplingParams(
        max_tokens=n_gen, temperature=0.0, ignore_eos=True))[0]

    ids = list(prompt)
    for _ in range(n_gen):
        logits = transformer.forward(params, cfg, jnp.asarray([ids], jnp.int32))
        ids.append(int(jnp.argmax(logits[0, -1])))
    assert out.output_token_ids == ids[len(prompt):]


def test_ep_sharded_decode_matches_single_device(cfg, params):
    """ep=4 (x tp=2) GSPMD sharding only changes layout, not math: prefill
    and paged-decode logits must match the unsharded run."""
    mesh = make_mesh(MeshConfig(dp=1, ep=4, tp=2))
    sh = shard_params(params, cfg, mesh)
    ek = sh["layers"][0]["experts"]["gate_proj"]["kernel"]
    assert ek.sharding.spec == jax.sharding.PartitionSpec(AXIS_EP, None, None)

    cache_cfg = CacheConfig(block_size=4, num_blocks=16, max_blocks_per_seq=4,
                            dtype="float32")
    from tpuserve.ops.attention import PAD_SLOT

    def run(params_in, cache_in):
        tokens = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        lens = jnp.asarray([4, 3], jnp.int32)
        slots = np.full((2, 4), PAD_SLOT, np.int32)
        for b in range(2):
            for t in range(int(lens[b])):
                slots[b, t] = (2 * b) * 4 + t
        logits_p, cache_in, _ = transformer.prefill(
            params_in, cfg, tokens, lens, jnp.asarray(slots), cache_in)
        bt = jnp.asarray([[0, 1, 0, 0], [2, 3, 0, 0]], jnp.int32)
        logits_d, _, _ = transformer.decode_step(
            params_in, cfg, jnp.asarray([9, 9], jnp.int32),
            jnp.asarray([4, 3], jnp.int32),
            jnp.asarray([1 * 4, 2 * 4 + 3], jnp.int32), bt,
            jnp.asarray([5, 4], jnp.int32), cache_in)
        return np.asarray(logits_p), np.asarray(logits_d)

    ref_p, ref_d = run(params, create_kv_cache(cfg, cache_cfg))
    ep_p, ep_d = run(sh, jax.device_put(create_kv_cache(cfg, cache_cfg),
                                        cache_shardings(cfg, mesh)))
    np.testing.assert_allclose(ep_p, ref_p, atol=2e-4)
    np.testing.assert_allclose(ep_d, ref_d, atol=2e-4)


def test_int8_quantizes_expert_kernels(cfg, params):
    """int8 must cover the stacked expert kernels (the bulk of an MoE
    model's weights — r2 advisor finding) with (E, out) scales, and the
    quantized forward must stay close to full precision."""
    q = weights.quantize_params_int8(params)
    ek = q["layers"][0]["experts"]
    E, ei, h = cfg.num_experts, cfg.expert_intermediate_size, cfg.hidden_size
    for proj, out_dim in (("gate_proj", ei), ("up_proj", ei), ("down_proj", h)):
        assert ek[proj]["kernel"].dtype == jnp.int8
        assert ek[proj]["scale"].shape == (E, out_dim)
    # router (tiny) is quantized like any linear
    assert q["layers"][0]["router"]["kernel"].dtype == jnp.int8

    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9]], jnp.int32)
    ref = np.asarray(transformer.forward(params, cfg, tokens))
    got = np.asarray(transformer.forward(q, cfg, tokens))
    # int8 error bound: relative per-logit agreement, not exactness
    assert np.mean(np.abs(got - ref)) < 0.1 * np.mean(np.abs(ref)) + 0.05
    # greedy next-token choice agrees on a well-separated distribution
    assert np.argmax(got[0, -1]) == np.argmax(ref[0, -1])


def test_int8_ep_sharded_matches_unsharded(cfg, params):
    """Quantized expert scales (E, out) shard over ep and still reproduce the
    unsharded quantized logits."""
    q = weights.quantize_params_int8(params)
    mesh = make_mesh(MeshConfig(dp=1, ep=4, tp=2))
    sq = shard_params(q, cfg, mesh)
    sc = sq["layers"][0]["experts"]["gate_proj"]["scale"]
    assert sc.sharding.spec == jax.sharding.PartitionSpec(AXIS_EP, None)
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9]], jnp.int32)
    ref = np.asarray(transformer.forward(q, cfg, tokens))
    got = np.asarray(transformer.forward(sq, cfg, tokens))
    np.testing.assert_allclose(got, ref, atol=2e-4)


def test_moe_config_rejects_interleaved_dense():
    base = dict(
        model_type="qwen3_moe", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=32)
    with pytest.raises(ValueError, match="mlp_only_layers"):
        config_from_hf_json("x", {**base, "mlp_only_layers": [0]})
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        config_from_hf_json("x", {**base, "decoder_sparse_step": 2})
    cfg = config_from_hf_json("x", {**base, "mlp_only_layers": [],
                                    "decoder_sparse_step": 1})
    assert cfg.num_experts == 4 and cfg.moe_intermediate_size == 32


# --------------------------------------------------------------------------
# sparse dispatch (ops/pallas_moe_gmm.py) against the dense form
# --------------------------------------------------------------------------

def top_k_selection(choice, cfg):
    """A router's picks by ``lax.top_k`` throughout, as the trunk chose
    them before its group-limited selection went sort-free: the groups'
    top-2 (or maximum), the surviving groups and a scatter, the top-k.
    Kept here as the plain reference: ``(topi, gmask, group_rows)``."""
    T, E = choice.shape
    gmask = group_rows = None
    if cfg.moe_n_group > 1:
        G = cfg.moe_n_group
        grouped = choice.reshape(T, G, E // G)
        if cfg.moe_scoring == "sigmoid":
            group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        else:
            group_scores = jnp.max(grouped, axis=-1)
        _, gidx = jax.lax.top_k(group_scores, cfg.moe_topk_group)
        gmask = jnp.zeros_like(group_scores).at[
            jnp.arange(T)[:, None], gidx].set(1.0)
        choice = jnp.where(gmask[..., None] > 0, grouped, 0.0).reshape(T, E)
        if cfg.moe_experts_held:
            per = E // G
            lo = cfg.moe_first_expert // per
            hi = -(-(cfg.moe_first_expert + cfg.moe_experts_held) // per)
            group_rows = jnp.sum(jnp.any(gmask[:, lo:hi] > 0, axis=-1),
                                 dtype=jnp.int32)
    _, topi = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    return topi, gmask, group_rows


def dense_oracle(x, p, cfg):
    """The expert layer as it was before the sparse dispatch: every expert
    on every token (``"th,ehi->tei"``), the unpicked ones weighted zero.
    Kept here as the oracle."""
    xt = x.reshape(-1, x.shape[-1])
    T = xt.shape[0]
    router = transformer._linear(xt, p["router"]).astype(jnp.float32)
    scores = (jax.nn.sigmoid(router) if cfg.moe_scoring == "sigmoid"
              else jax.nn.softmax(router, axis=-1))
    choice = scores
    if "router_bias" in p:
        choice = choice + p["router_bias"]["bias"][None, :]
    topi, _, _ = top_k_selection(choice, cfg)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.norm_topk_prob:
        eps = 1e-20 if cfg.moe_scoring == "sigmoid" else 0.0
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + eps)
    topv = topv * cfg.moe_routed_scaling
    combine = jnp.zeros_like(scores).at[
        jnp.arange(T)[:, None], topi].set(topv)

    def proj(spec, inp, ep):
        y = jnp.einsum(spec, inp, ep["kernel"].astype(inp.dtype))
        if "scale" in ep:
            y = y * ep["scale"][None].astype(y.dtype)
        return y

    ek = p["experts"]
    g = proj("th,ehi->tei", xt, ek["gate_proj"])
    u = proj("th,ehi->tei", xt, ek["up_proj"])
    o = proj("tei,eih->teh", jax.nn.silu(g) * u, ek["down_proj"])
    y = jnp.einsum("teh,te->th", o, combine.astype(o.dtype))
    if "shared" in p:
        y = y + transformer._mlp(xt, p["shared"], cfg)
    return y.reshape(x.shape), combine


def _deepseek():
    c = dataclasses.replace(get_model_config("tiny-deepseek"),
                            dtype="float32")
    return c, weights.init_params(c, seed=5)["layers"][1]    # layer 0 is dense


def _case(name, cfg, params):
    """``(cfg, one layer's params)`` of a named case."""
    lp = dict(params["layers"][0])
    if name == "renormalised":
        return cfg, lp
    if name == "not-renormalised":
        return dataclasses.replace(cfg, norm_topk_prob=False), lp
    if name == "every-token-to-one-expert":
        # a router of zeros ties every score: top-1 takes expert 0
        lp["router"] = {"kernel": jnp.zeros_like(lp["router"]["kernel"])}
        return dataclasses.replace(cfg, num_experts_per_tok=1), lp
    if name == "an-expert-with-no-row":
        # expert 2's score is driven to nothing for every token
        k = lp["router"]["kernel"]
        lp["router"] = {"kernel": k.at[:, 2].set(0.0)}
        lp["router_bias"] = {"bias": jnp.asarray([0.0, 0.0, -1e9, 0.0])}
        return cfg, lp
    if name == "int8-scales":
        return cfg, weights.quantize_params_int8(params)["layers"][0]
    if name == "shared-experts-sigmoid-grouped":
        return _deepseek()
    raise KeyError(name)


CASES = ["renormalised", "not-renormalised", "every-token-to-one-expert",
         "an-expert-with-no-row", "int8-scales",
         "shared-experts-sigmoid-grouped"]


@pytest.mark.parametrize("T", [1, 7, 64, 300])
@pytest.mark.parametrize("case", CASES)
def test_sparse_dispatch_is_the_dense_form(cfg, params, case, T):
    """Sorted rows through the grouped products give what every expert on
    every token gives, for every routing the presets use; the rows routed
    to each expert are counted as the oracle's combine matrix has them;
    the dense form the program keeps for a mesh agrees too."""
    c, lp = _case(case, cfg, params)
    x = jnp.asarray(np.random.default_rng(T).standard_normal(
        (T, c.hidden_size)), jnp.float32)
    want, combine = dense_oracle(x, lp, c)
    tally = []
    got = transformer._moe_mlp(x, lp, c, tally)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    picked = np.asarray(combine != 0).sum(0)
    # (a weight of exactly zero cannot come out of a softmax or a sigmoid)
    (sizes, picks, landed), = tally
    assert landed is None               # every expert held: no share
    np.testing.assert_array_equal(np.asarray(sizes), picked)
    assert int(np.asarray(sizes).sum()) == T * c.num_experts_per_tok
    # and each token's picks are the columns of its row that weigh
    assert picks.shape == (T, c.num_experts_per_tok)
    np.testing.assert_array_equal(
        np.sort(np.asarray(picks), axis=1),
        np.stack([np.flatnonzero(row) for row in np.asarray(combine)]))
    if case == "every-token-to-one-expert":
        assert list(np.asarray(sizes)) == [T, 0, 0, 0]
    if case == "an-expert-with-no-row":
        assert int(sizes[2]) == 0
    np.testing.assert_allclose(
        np.asarray(transformer._moe_mlp(x, lp, c, None, True)),
        np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ["renormalised", "int8-scales"])
def test_a_tokens_expert_output_does_not_depend_on_the_batch(cfg, params,
                                                             case):
    """The sparse layer in bfloat16: a token's output is the same bits
    alone, among 6 others and among 299 others (as the dense form's is).
    Rows are sorted into other places and the row tiles differ, but a
    row's product with its expert's kernel reads that row only."""
    c, lp = _case(case, cfg, params)
    lp = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                      if a.dtype == jnp.float32 else a, lp)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (300, c.hidden_size)), jnp.bfloat16)
    whole = np.asarray(transformer._moe_mlp(x, lp, c).astype(jnp.float32))
    for T in (1, 7):
        part = transformer._moe_mlp(x[:T], lp, c).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(part), whole[:T])
        dense = transformer._moe_mlp(x[:T], lp, c, None, True)
        np.testing.assert_array_equal(
            np.asarray(dense.astype(jnp.float32)),
            np.asarray(transformer._moe_mlp(x, lp, c, None, True)
                       .astype(jnp.float32))[:T])


@pytest.mark.parametrize("m,k,n,tiles", [
    (2, 64, 32, None),               # fewer rows than a tile: padded
    (48, 64, 32, (16, 32, 32)),      # two contraction blocks
    (48, 64, 32, (16, 48, 32)),      # a contraction block past the edge
    (600, 64, 96, (128, 64, 96)),    # rows not a multiple of the tile
    (64, 32, 256, (32, 32, 128)),    # two output blocks
])
def test_the_grouped_product_is_the_row_by_row_product(m, k, n, tiles):
    """``_moe_grouped_matmul`` in interpret mode against one kernel a row,
    with groups that are empty, that start inside a tile and that span
    several, and with int8 kernels converted block by block."""
    from tpuserve.ops.pallas_moe_gmm import (_grouped_matmul,
                                             grouped_matmul_reference, tiling)

    def grouped_matmul(lhs, rhs, sizes):
        tm, tk, tn = tiles or tiling(m, k, n)
        return _grouped_matmul(lhs, rhs, sizes, tm=tm, tk=tk, tn=tn,
                               interpret=True)
    rs = np.random.RandomState(m + n)
    E = 8
    sizes = np.bincount(rs.randint(0, E, m), minlength=E).astype(np.int32)
    sizes[3] += sizes[5]
    sizes[5] = 0                                    # an empty group
    lhs = jnp.asarray(rs.randn(m, k), jnp.float32)
    rhs = jnp.asarray(rs.randn(E, k, n), jnp.float32)
    want = np.asarray(grouped_matmul_reference(lhs, rhs, jnp.asarray(sizes)))
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.lax.ragged_dot(lhs, rhs, jnp.asarray(sizes))), want,
        atol=1e-4, rtol=1e-5)
    q = jnp.asarray(rs.randint(-127, 128, (E, k, n)), jnp.int8)
    np.testing.assert_allclose(
        np.asarray(grouped_matmul(lhs, q, jnp.asarray(sizes))),
        np.asarray(grouped_matmul_reference(lhs, q, jnp.asarray(sizes))),
        atol=2e-2, rtol=1e-5)


# --------------------------------------------------------------------------
# the group-limited router's selection (transformer._group_limited_select)
# --------------------------------------------------------------------------

ROUTERS = [("sigmoid", 8, 4), ("sigmoid", 2, 1), ("softmax", 8, 3)]


def _group_limited(scoring, n_group, topk_group, experts=512):
    """A configuration whose router limits its picks to ``topk_group`` of
    ``n_group`` groups, the LAST group held here (a share that starts at no
    zero, so the slice of the mask is a real one)."""
    per = experts // n_group
    return dataclasses.replace(
        get_model_config("tiny-deepseek"), num_experts=experts,
        num_experts_per_tok=8, moe_scoring=scoring, moe_n_group=n_group,
        moe_topk_group=topk_group, moe_experts_held=per,
        moe_first_expert=experts - per)


def _selection_scores(draw, scoring, T, E, seed):
    """``(T, E)`` float32 selection scores: ``continuous`` (a router's
    scores under a selection bias), ``halves`` (quantised so that exact
    ties stand inside and across groups) or ``equal``."""
    if draw == "equal":
        return jnp.full((T, E), 0.5, jnp.float32)
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((T, E)).astype(np.float32)
    if draw == "halves":
        return jnp.asarray(np.round(logits * 2.0) / 2.0)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(jnp.asarray(logits), axis=-1))
    return scores + jnp.asarray(0.1 * rng.standard_normal(E), jnp.float32)


@pytest.mark.parametrize("draw", ["continuous", "halves", "equal"])
@pytest.mark.parametrize("T", [1, 128, 1536])
@pytest.mark.parametrize("scoring,n_group,topk_group", ROUTERS)
def test_the_group_limited_selection_is_the_top_k_form(scoring, n_group,
                                                       topk_group, T, draw):
    """The picks, the surviving groups and the count of rows sent here are
    what ``lax.top_k`` throughout gives, exact ties included (the lower
    index first, among groups and among experts)."""
    c = _group_limited(scoring, n_group, topk_group)
    choice = _selection_scores(draw, scoring, T, c.num_experts,
                               seed=T + n_group)
    want_i, want_mask, want_rows = top_k_selection(choice, c)
    topi, gmask, group_rows = jax.jit(
        lambda x: transformer._group_limited_select(x, c))(choice)
    assert gmask.dtype == jnp.bool_ and gmask.shape == (T, n_group)
    np.testing.assert_array_equal(np.asarray(gmask),
                                  np.asarray(want_mask) > 0)
    assert (np.asarray(gmask).sum(-1) == topk_group).all()
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    assert topi.dtype == want_i.dtype
    assert int(group_rows) == int(want_rows)
    if draw == "equal":         # the lowest groups, their first experts
        assert np.asarray(gmask)[:, :topk_group].all()
        assert list(np.asarray(topi)[0]) == list(range(8))
        assert int(group_rows) == 0
    # without a share no rows are counted
    assert transformer._group_limited_select(
        choice, dataclasses.replace(c, moe_experts_held=0,
                                    moe_first_expert=0))[2] is None


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_the_group_limited_selection_issues_one_top_k_and_no_sort():
    """At a decode step's ``(128, 512)`` scores: no ``sort``, no
    ``scatter``, and at most one ``top_k`` (the picks')."""
    c = _group_limited("sigmoid", 8, 4)
    names = list(_primitives(jax.make_jaxpr(
        lambda x: transformer._group_limited_select(x, c))(
            jnp.zeros((128, 512), jnp.float32)).jaxpr))
    assert not [n for n in names if "sort" in n or "scatter" in n], names
    assert names.count("top_k") <= 1
