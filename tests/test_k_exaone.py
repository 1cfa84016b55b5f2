"""K-EXAONE on the normal path, as one chip's share of a deployment whose
chips share each expert layer: an expert layer that is told which experts
it holds behind a sigmoid router and beside a shared expert, a dense layer
first, windowed layers that rotate to each full layer that does not.

Everything is compared with the plain reference the benchmark scores this
family by (``benchmark/reference/k_exaone.py``: float32, every HELD expert
on every token weighted by the router's choice over ALL experts, the
per-layer mask written out; no code shared with ``tpuserve``), on the
registered ``tiny-k-exaone`` (float32; two periods of L L L G, window 8,
16 query heads on 2 KV heads under a q/k norm, a dense layer and then 32
experts, 4 a token, scaled 2.5, beside a shared one) under seeded random
weights; a share is 8 of the 32 experts.  Logits, not tokens.

Tolerances: both sides are float32 on the CPU, so what separates them is
the ORDER of the same sums (grouped products over sorted rows and a
scatter-add against a loop over experts, blocked attention against a dense
softmax): a few 1e-6 on logits of size ~1-3.  ``ATOL`` 2e-4 leaves two
orders of magnitude over that; the same path in bfloat16 is off by over
1e-2 (``test_bfloat16_where_float32_is_stated_fails``), as is a flipped
expert, a rotated full layer or a shifted share
(``tests/benchmark/test_benchmark_k_exaone_rehearsal.py`` has each as a
fault).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_routes import (BLOCK, FAMILIES, Served, engine_for, plan,
                           prompts_of, ref_greedy, ref_logits, run_route)
from tpuserve.models import transformer
from tpuserve.models.config import (ModelConfig, config_from_hf_json,
                                    get_model_config)
from tpuserve.models.weights import init_params
from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SamplingParams

FAMILY = FAMILIES["k_exaone"]
ATOL = FAMILY.atol
MODEL = FAMILY.model
PUBLISHED = "LGAI-EXAONE/K-EXAONE-236B-A23B"
HELD = 8                # experts of the tiny model's 32 one share holds
SHARES = 4

ref = FAMILY.ref


def catalog_config() -> dict:
    """The catalog's ``config`` of the model (model-configs guide,
    architectures.jsonl), rebuilt from its periods: 48 layers of L L L G,
    a dense layer and 47 sparse ones."""
    period = ["sliding_attention"] * 3 + ["full_attention"]
    return {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "layer_types": period * 12, "max_position_embeddings": 262144,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
        "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "sliding_windows": [128, 128, 128, 0] * 12,
        "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600,
    }


def share_of(cfg: ModelConfig, params, share: int):
    """``(cfg, params)`` of one share: the ModelConfig told which experts
    it holds and the tree with those experts' kernels alone (everything
    else, the router's every column too, as it was)."""
    first = share * HELD
    layers = []
    for lp in params["layers"]:
        if "experts" in lp:
            lp = dict(lp, experts={
                name: {"kernel": p["kernel"][first:first + HELD]}
                for name, p in lp["experts"].items()})
        layers.append(lp)
    return (dataclasses.replace(cfg, name=f"{cfg.name}-share{share}",
                                moe_experts_held=HELD,
                                moe_first_expert=first),
            dict(params, layers=layers))


@pytest.fixture(scope="module")
def whole():
    cfg = get_model_config(MODEL)
    return cfg, init_params(cfg, seed=13)


@pytest.fixture(scope="module")
def shared(whole):
    """The share the cell holds: the first experts."""
    return share_of(*whole, 0)



def rows_of(n, seed=1, hidden=64):
    return jnp.asarray(np.random.RandomState(seed).randn(n, hidden),
                       jnp.float32)


def ref_layer(lp, h, cfg):
    """The reference's expert layer on rows ``h``: the held experts' part
    and the shared expert, apart."""
    none = jnp.full((h.shape[0], cfg.num_experts_per_tok), -1, jnp.int32)
    return (np.asarray(ref._held_experts(lp, h, cfg, none)),
            np.asarray(ref._gated_mlp(h, lp["shared"])))


# --------------------------------------------------------------------------
# (a) the shares add up
# --------------------------------------------------------------------------

@pytest.mark.parametrize("share", range(SHARES))
def test_a_share_is_the_reference_given_the_same_share(whole, share):
    """The served layer told it holds experts ``8 share .. 8 share + 7``
    gives what the reference gives when handed that share: the held
    experts' part, weighted by a router over all 32 renormalised over all
    4 picks, plus the shared expert whole."""
    cfg, params = share_of(*whole, share)
    lp, h = params["layers"][2], rows_of(37)
    routed, always = ref_layer(lp, h, cfg)
    tally = []
    got = np.asarray(transformer._moe_mlp(h, lp, cfg, tally))
    np.testing.assert_allclose(got, routed + always, atol=1e-5)
    (sizes, picks, landed), = tally
    first = share * HELD
    assert int(sizes.sum()) == 37 * cfg.num_experts_per_tok
    assert int(landed[0]) == int(sizes[first:first + HELD].sum())
    assert int(landed[1]) == int((sizes[first:first + HELD] > 0).sum())
    assert int(landed[2]) % transformer.held_piece_rows(
        37 * cfg.num_experts_per_tok, HELD, cfg.num_experts) == 0
    assert int(landed[2]) >= int(landed[0])


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """What ties the share to the model: the four shares' routed parts,
    with the shared expert (which every chip computes alike) counted once,
    are the uncut reference layer, and the uncut served layer."""
    cfg, params = whole
    lp, h = params["layers"][2], rows_of(37)
    uncut_routed, always = ref_layer(lp, h, cfg)
    parts = []
    for share in range(SHARES):
        scfg, sparams = share_of(cfg, params, share)
        parts.append(np.asarray(transformer._moe_mlp(
            h, sparams["layers"][2], scfg)) - always)
    np.testing.assert_allclose(sum(parts) + always, uncut_routed + always,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(transformer._moe_mlp(h, lp, cfg)),
                               uncut_routed + always, atol=1e-5)
    # and no share is the whole: each leaves most of the routed sum out
    assert all(np.max(np.abs(p - uncut_routed)) > 1e-2 for p in parts)


# --------------------------------------------------------------------------
# (b) no pick is dropped at any skew
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [3, 40, 200])
@pytest.mark.parametrize("skew", ["every pick here", "no pick here"])
def test_no_pick_is_dropped_at_any_skew(shared, skew, rows):
    """A selection bias of 2 (a sigmoid is under 1) on the held experts
    sends EVERY pick to them: ``rows x 4`` rows land here, several pieces
    of the buffer, and the layer is still the reference's.  On the absent
    experts it sends none: no piece, the shared expert alone."""
    cfg, params = shared
    here = np.arange(cfg.num_experts) < HELD
    bias = jnp.asarray(np.where(here == (skew == "every pick here"),
                                2.0, 0.0), jnp.float32)
    lp = dict(params["layers"][1], router_bias={"bias": bias})
    h = rows_of(rows, seed=2)
    routed, always = ref_layer(lp, h, cfg)
    tally = []
    got = np.asarray(transformer._moe_mlp(h, lp, cfg, tally))
    np.testing.assert_allclose(got, routed + always, atol=1e-5)
    landed = np.asarray(tally[0][2])
    pairs = rows * cfg.num_experts_per_tok
    piece = transformer.held_piece_rows(pairs, HELD, cfg.num_experts)
    if skew == "every pick here":
        assert landed[0] == pairs and landed[2] == -(-pairs // piece) * piece
        assert landed[3] * piece == landed[2]
        assert landed[2] // piece > 1 or pairs <= piece
        assert np.max(np.abs(routed)) > 1e-2
    else:
        assert list(landed) == [0, 0, 0, 0]
        np.testing.assert_allclose(got, always, atol=1e-6)


def test_the_buffer_follows_the_share_not_every_pick():
    """A piece is what lands here under even routing plus three standard
    deviations, a whole number of the grouped product's row tiles, and
    never more than every pick: at the cell's sizes an eighth of the
    picks and a little, where ``T k`` rows would be eight times that."""
    piece = transformer.held_piece_rows
    assert piece(64 * 8, 16, 128) == 96                # a decode window
    assert piece(8192 * 8, 16, 128) == 8448            # the top prefill rung
    assert piece(8192 * 8, 16, 128) * 7 < 8192 * 8
    assert piece(8, 16, 128) == 16 and piece(8, 8, 8) == 16
    for pairs in (8, 512, 4096, 65536):
        rows = piece(pairs, 16, 128)
        assert rows % 16 == 0 and rows <= -(-pairs // 16) * 16
        from tpuserve.ops.pallas_moe_gmm import tiling
        assert rows % tiling(rows, 6144, 2048)[0] == 0  # never padded there


# --------------------------------------------------------------------------
# (c) every route through the paged cache, against the full forward pass
# --------------------------------------------------------------------------


@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
@pytest.mark.parametrize("route", ["prefill", "packed", "chunks"])
def test_every_route_matches_the_reference_under_a_share(
        shared, route, attn_impl):
    """(B, L) prefill, a packed prefill of three uneven prompts, a prompt
    over three chunks; then ``decode_step`` and a fused ``decode_multi``
    window, on L L L G with the dense layer first and 8 of 32 experts
    held.  The prompt of 40 is five windows of 8 long; the prompt of 6
    crosses the window while it decodes.  ``pallas``: the paged attention
    kernels in interpret mode (the grouped product is a kernel on both)."""
    cfg, params = shared
    served = run_route(FAMILY, cfg, params, route, attn_impl)
    E, per = cfg.num_experts, cfg.num_experts_per_tok * 7   # expert layers
    assert served.counts.shape == (E + 5,)
    assert served.counts[:E].sum() % per == 0
    # what landed here is what was routed to the first 8 experts
    assert served.counts[E + 1] == served.counts[:HELD].sum() > 0
    assert 0 < served.counts[E + 2] <= served.counts[E]
    assert served.counts[E + 3] >= served.counts[E + 1]
    assert 0 < served.counts[E + 4] <= served.counts[E + 3] // 16


@pytest.mark.parametrize("route", ["packed", "chunks"])
def test_every_route_matches_the_reference_with_every_expert_held(
        whole, route):
    """The family without a share: the layer that holds every expert."""
    cfg, params = whole
    served = run_route(FAMILY, cfg, params, route, "reference")
    assert served.counts.shape == (cfg.num_experts + 1,)


def test_bfloat16_where_float32_is_stated_fails(shared):
    """The comparison is tight enough to tell a precision: the same model
    with bfloat16 weights, activations and cache is off the float32
    reference (on the weights as bfloat16 holds them) by far more than
    ``ATOL``."""
    cfg, params = shared
    low = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if x.dtype == jnp.float32 and x.ndim > 1 else x, params)
    low_cfg = dataclasses.replace(cfg, dtype="bfloat16")
    seqs = prompts_of(40, 6, 29)
    served = Served(FAMILY, low_cfg, low, 3, dtype="bfloat16")
    first = served.packed(seqs)
    off = max(np.max(np.abs(
        first[i].astype(np.float32)
        - ref_logits(FAMILY, low, cfg, s, [len(s) - 1])[0]))
        for i, s in enumerate(seqs))
    assert off > 50 * ATOL, off


def test_the_share_the_layer_kinds_and_the_scaling_are_live(shared):
    """A rotated full layer, a window ignored, the scaling dropped or the
    share shifted by one expert each moves the reference's logits at a
    position past the window by far more than ATOL: the agreement above
    is not vacuous."""
    cfg, params = shared
    seq = prompts_of(40, seed=3)[0]
    want = ref_logits(FAMILY, params, cfg, seq, [39])[0]
    broken = {
        "every layer full": dataclasses.replace(
            cfg, window_layers=(False,) * 8),
        "no scaling": dataclasses.replace(cfg, moe_routed_scaling=1.0),
        "share shifted by one": dataclasses.replace(cfg, moe_first_expert=1),
    }
    for what, bad in broken.items():
        got = np.asarray(ref.logits_at(
            params, bad, np.asarray([seq], np.int32), [(0, 39)]))[0]
        assert np.max(np.abs(got - want)) > 1e-2, what


# --------------------------------------------------------------------------
# (d) which layers carry positions
# --------------------------------------------------------------------------

def test_a_full_layer_carries_no_position_and_a_windowed_one_does(shared):
    """``_qkv`` on a full layer gives the same q and k wherever the rows
    stand; on a windowed layer they turn with the position.  (Rotary is
    relative: a windowed layer's attention OUTPUT would not move under one
    shift of every position either, so the layers are told apart here,
    where the position enters; a served full layer that rotates is a
    fault of tests/benchmark/test_benchmark_k_exaone_rehearsal.py.)"""
    cfg, params = shared
    h = rows_of(12, seed=5)
    here = jnp.arange(12, dtype=jnp.int32)
    for li, lp in enumerate(params["layers"]):
        q0, k0, v0, _ = transformer._qkv(h, lp, cfg, here, li)
        q1, k1, v1, _ = transformer._qkv(h, lp, cfg, here + 7, li)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        moved = max(float(jnp.max(jnp.abs(q0 - q1))),
                    float(jnp.max(jnp.abs(k0 - k1))))
        if cfg.layer_window(li) is None:
            assert not cfg.layer_rotates(li) and moved == 0.0, li
        else:
            assert cfg.layer_rotates(li) and moved > 0.1, li


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------


@pytest.mark.parametrize("multi_step,attn_impl", [
    (1, "reference"), (4, "reference"), (4, "pallas")])
def test_served_greedy_tokens_are_the_references(shared, multi_step,
                                                 attn_impl):
    cfg, params = shared
    eng = engine_for(FAMILY, params, cfg, multi_step=multi_step,
                     attn_impl=attn_impl)
    prompts = prompts_of(40, 9, seed=5)
    outs = eng.generate(prompts, SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True))
    for p, o in zip(prompts, outs):
        assert o.output_token_ids == ref_greedy(FAMILY, params, cfg, p, 10)
    assert eng.block_manager.num_seqs() == 0


def test_what_landed_here_comes_back_with_the_tokens(shared, whole):
    """Under a share the step records of prefill and window steps carry
    ``moe_held_rows``, ``moe_held_hits``, ``moe_buffer_rows`` and
    ``moe_held_pieces`` beside ``moe_rows``; the engine's totals are their sums and what
    ``tpuserve_moe_held_rows`` exports; a model that holds every expert
    has none of the four."""
    cfg, params = shared
    eng = engine_for(FAMILY, params, cfg, multi_step=4)
    eng.generate(prompts_of(21, 6, seed=9), SamplingParams(
        max_tokens=9, temperature=0.0, ignore_eos=True))
    steps = [s for s in eng.flight.steps_snapshot(limit=1 << 20)
             if "moe_rows" in s]
    assert {s["kind"] for s in steps} >= {"prefill", "window"}
    per = cfg.num_experts_per_tok * 7
    for s in steps:
        assert s["moe_rows"] == s["padded_tokens"] * per, s
        assert 0 <= s["moe_held_rows"] <= s["moe_rows"]
        assert s["moe_held_hits"] <= s["moe_expert_hits"]
        assert s["moe_buffer_rows"] >= s["moe_held_rows"]
        assert 0 < s["moe_held_pieces"] <= s["moe_buffer_rows"] // 16
    st = eng.stats
    assert st.moe_held_rows == sum(s["moe_held_rows"] for s in steps) > 0
    assert st.moe_held_hits == sum(s["moe_held_hits"] for s in steps) > 0
    assert st.moe_buffer_rows == sum(s["moe_buffer_rows"] for s in steps)
    assert st.moe_held_pieces == sum(s["moe_held_pieces"] for s in steps)
    assert st.moe_held_rows == st.moe_expert_rows[:HELD].sum()
    assert eng._moe_inflight == []

    cfg, params = whole
    eng = engine_for(FAMILY, params, cfg, multi_step=4)
    eng.generate(prompts_of(21, seed=9), SamplingParams(
        max_tokens=5, temperature=0.0, ignore_eos=True))
    assert eng.stats.moe_routed_rows > 0 and eng.stats.moe_held_rows == 0
    assert all("moe_held_rows" not in s
               for s in eng.flight.steps_snapshot(limit=1 << 20))


def test_logprobs_name_the_picks_of_the_expert_layers(shared):
    """A request that asks for logprobs gets the experts each EXPERT layer
    routed a position to (7 of the 8 layers), over all 32 experts whether
    held or not: what the reference replays."""
    cfg, params = shared
    eng = engine_for(FAMILY, params, cfg, multi_step=4)
    prompt = prompts_of(19, seed=7)[0]
    (out,) = eng.generate([prompt], SamplingParams(
        max_tokens=5, temperature=0.0, ignore_eos=True, logprobs=2))
    got = np.asarray(out.logprobs[0]["prompt_routed_experts"]
                     + [e["routed_experts"] for e in out.logprobs[1:]])
    assert got.shape == (19 + 4, 7, cfg.num_experts_per_tok)
    assert got.min() >= 0 and got.max() >= HELD
    probes = [(prompt, out.output_token_ids, {
        "prompt_routed_experts": out.logprobs[0]["prompt_routed_experts"],
        "routed_experts": [e["routed_experts"] for e in out.logprobs]})]
    rows = np.asarray(ref.score_probes(params, cfg, probes))
    assert rows.shape == (5, cfg.vocab_size)
    assert [int(r.argmax()) for r in rows] == out.output_token_ids


def test_a_share_under_a_mesh_is_refused_with_a_sentence(shared):
    from tpuserve.parallel.mesh import MeshConfig, make_mesh
    cfg, params = shared
    with pytest.raises(ValueError, match="one device's share"):
        Engine(EngineConfig(model=MODEL, cache=CacheConfig(
            block_size=BLOCK, num_blocks=32, max_blocks_per_seq=8)),
            params=params, model_cfg=cfg, mesh=make_mesh(MeshConfig(dp=1, ep=2, tp=1)))


# --------------------------------------------------------------------------
# (e) the configuration, the weights
# --------------------------------------------------------------------------

def test_config_json_maps_onto_the_registered_model():
    """The catalog's ``config`` gives the preset, field for field, and the
    properties a configuration file's keys are held to spell it back."""
    hf = catalog_config()
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if os.path.isfile(row):             # the catalog itself, where it is
        with open(row) as f:
            entry = next(json.loads(line) for line in f
                         if '"K-EXAONE-236B-A23B"' in line)
        assert entry["config"] == hf
    got = config_from_hf_json(PUBLISHED, hf)
    assert got == get_model_config(PUBLISHED)
    assert get_model_config("k-exaone-236b") is get_model_config(PUBLISHED)
    for key, field in {**plan.FIXED, **plan.CUTTABLE, **ref.FIXED}.items():
        if key in hf:
            assert getattr(got, field) == hf[key], key
    assert set(hf) <= set(plan.FIXED) | set(plan.CUTTABLE) \
        | set(ref.FIXED) | set(plan.DESCRIPTIVE) | set(ref.DESCRIPTIVE)
    assert got.moe_experts_held == 0 and got.moe_local_experts == 128
    ref.check_family(got)
    tiny = get_model_config(MODEL)
    ref.check_family(tiny)
    assert tiny.sliding_window_pattern == "LLLG"
    assert tiny.mlp_layer_types == ["dense"] + ["sparse"] * 7


@pytest.mark.parametrize("bad,why", [
    ({"layer_types": None}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 47}, "layer_types"),
    ({"n_group": 8, "topk_group": 4}, "grouped routing"),
    ({"sliding_window": None}, "sliding window"),
    ({"sliding_windows": [128] * 48}, "sliding_windows"),
    ({"sliding_window_pattern": "LG"}, "sliding_window_pattern"),
    ({"mlp_layer_types": ["sparse"] * 48}, "mlp_layer_types"),
    ({"rope_parameters": {"rope_type": "yarn", "factor": 4}},
     "rope_parameters"),
])
def test_what_the_family_does_not_implement_raises(bad, why):
    with pytest.raises(ValueError, match=why):
        config_from_hf_json("x", {**catalog_config(), **bad})


@pytest.mark.parametrize("held,first", [(8, 28), (0, 4), (-8, 0), (33, 0)])
def test_a_share_outside_the_experts_is_refused(held, first):
    with pytest.raises(ValueError, match="cannot hold experts"):
        dataclasses.replace(get_model_config(MODEL), moe_experts_held=held,
                            moe_first_expert=first)


def test_weights_under_a_share_are_the_held_experts(whole, shared):
    """``init_params`` draws the held experts' stacks under a router as
    wide as the model; a checkpoint loads its held experts, and a sliced
    vocabulary raises one sentence."""
    from tpuserve.models.weights import _load_llama_family
    cfg, params = whole
    scfg = dataclasses.replace(cfg, moe_experts_held=HELD, moe_first_expert=8)
    drawn = jax.eval_shape(lambda: init_params(scfg, 0))
    lp = drawn["layers"][1]
    assert lp["experts"]["gate_proj"]["kernel"].shape == (HELD, 64, 32)
    assert lp["experts"]["down_proj"]["kernel"].shape == (HELD, 32, 64)
    assert lp["router"]["kernel"].shape == (64, 32)
    assert lp["router_bias"]["bias"].shape == (32,)
    assert lp["shared"]["gate_proj"]["kernel"].shape == (64, 32)
    assert "experts" not in drawn["layers"][0]
    assert drawn["layers"][0]["gate_proj"]["kernel"].shape == (64, 192)
    raw = {"model.embed_tokens.weight": params["embed"]["weight"],
           "model.norm.weight": params["final_norm"]["scale"],
           "lm_head.weight": params["lm_head"]["kernel"].T}
    for i, lp in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        raw[pre + "input_layernorm.weight"] = lp["attn_norm"]["scale"]
        raw[pre + "post_attention_layernorm.weight"] = lp["mlp_norm"]["scale"]
        for p in ("q", "k", "v", "o"):
            raw[pre + f"self_attn.{p}_proj.weight"] = \
                lp[f"{p}_proj"]["kernel"].T
        for p in ("q", "k"):
            raw[pre + f"self_attn.{p}_norm.weight"] = lp[f"{p}_norm"]["scale"]
        if "experts" not in lp:
            for p in ("gate", "up", "down"):
                raw[pre + f"mlp.{p}_proj.weight"] = lp[f"{p}_proj"]["kernel"].T
            continue
        raw[pre + "mlp.gate.weight"] = lp["router"]["kernel"].T
        raw[pre + "mlp.gate.e_score_correction_bias"] = \
            lp["router_bias"]["bias"]
        for p in ("gate_proj", "up_proj", "down_proj"):
            raw[pre + f"mlp.shared_experts.{p}.weight"] = \
                lp["shared"][p]["kernel"].T
            for e in range(cfg.num_experts):
                raw[pre + f"mlp.experts.{e}.{p}.weight"] = \
                    lp["experts"][p]["kernel"][e].T
    loaded = _load_llama_family(scfg, raw, jnp.float32)
    _, want = share_of(cfg, params, 1)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    with pytest.raises(ValueError, match="sliced vocabulary"):
        _load_llama_family(dataclasses.replace(scfg, vocab_size=64), raw,
                           jnp.float32)


# --------------------------------------------------------------------------
# (f) a model that holds every expert traces the programs it traced
# --------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["tiny-mellum2", "tiny-moe",
                                   "tiny-k-exaone"])
def test_a_model_that_holds_every_expert_gains_nothing(model, whole):
    """No share: the expert layer is the one it always was: three grouped
    products over every pick, no loop over pieces, the same counts
    ``(E + 1,)`` and no new output.  (That the jaxprs of Mellum 2's and
    ``tiny-moe``'s layers are LETTER FOR LETTER what the parent commit
    traced was checked when the share came, CHANGES.md PR 41; this holds
    the structure.)  Under a share the loop and the four counts appear."""
    cfg = get_model_config(model)
    params = jax.eval_shape(lambda: init_params(cfg, 0))
    lp = next(lp for lp in params["layers"] if "experts" in lp)
    h = jax.ShapeDtypeStruct((24, cfg.hidden_size), jnp.dtype(cfg.dtype))

    def layer(cfg, lp):
        def run(h, lp):
            tally = []
            y = transformer._moe_mlp(h, lp, cfg, tally)
            return y, transformer._moe_counts(tally)
        return jax.make_jaxpr(run)(h, lp)

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    whole_layer = layer(cfg, lp)
    names = list(primitives(whole_layer.jaxpr))
    assert "while" not in names
    assert names.count("pallas_call") == 3
    assert [v.aval.shape for v in whole_layer.jaxpr.outvars] \
        == [(24, cfg.hidden_size), (cfg.num_experts + 1,)]
    assert str(layer(dataclasses.replace(cfg, moe_experts_held=0), lp)) \
        == str(whole_layer)

    held = cfg.num_experts // 2
    scfg = dataclasses.replace(cfg, moe_experts_held=held)
    slp = dict(lp, experts={
        n: {"kernel": jax.ShapeDtypeStruct(
            (held,) + p["kernel"].shape[1:], p["kernel"].dtype)}
        for n, p in lp["experts"].items()})
    part = layer(scfg, slp)
    names = list(primitives(part.jaxpr))
    assert "while" in names and names.count("pallas_call") == 3
    assert [v.aval.shape for v in part.jaxpr.outvars] \
        == [(24, cfg.hidden_size), (cfg.num_experts + 5,)]
