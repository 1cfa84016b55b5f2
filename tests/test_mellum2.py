"""Mellum 2 on the normal path: layers of two kinds (three windowed with
the plain rotary table to each full one under a YaRN table) and a sparse
expert layer in every one.

Everything is compared with the plain reference the benchmark scores this
family by (``benchmark/reference/mellum2.py``: float32, every expert on
every token weighted by the router's choice, the per-layer mask and table
written out; no code shared with ``tpuserve``), on the registered
``tiny-mellum2`` (float32; two periods of S S S F, window 16, YaRN factor
4 over an original 32, 8 experts and 2 a token, 8 query heads on 2 KV
heads) under seeded random weights.  Logits, not tokens.

Tolerances: both sides are float32 on the CPU, so what separates them is
the ORDER of the same sums (grouped products over sorted rows against a
loop over experts, blocked attention against a dense softmax): a few 1e-6
on logits of size ~1-3.  The tolerance (``FAMILIES``' 2e-4) leaves two
orders of magnitude over that; a flipped expert, a table of the wrong kind
or a window ignored moves logits by over 1e-2 (``tests/benchmark/
test_benchmark_mellum2_rehearsal.py`` has each as a fault).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_routes import (BLOCK, FAMILIES, engine_for, plan, prompts_of,
                           ref_greedy, ref_logits, run_route)
from tpuserve.models.config import config_from_hf_json, get_model_config
from tpuserve.models.weights import init_params
from tpuserve.ops import rope as rope_ops
from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SamplingParams
from tpuserve.runtime.scheduler import SchedulerConfig

FAMILY = FAMILIES["mellum2"]
MODEL = FAMILY.model
PUBLISHED = "JetBrains/Mellum2-12B-A2.5B-Instruct"

ref = FAMILY.ref

# the catalog's ``config`` of the model (model-configs guide,
# architectures.jsonl), verbatim
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CATALOG_CONFIG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}


@pytest.fixture(scope="module")
def cfg():
    return get_model_config(MODEL)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, seed=11)


# --------------------------------------------------------------------------
# the trunks, driven by hand: logits against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
@pytest.mark.parametrize("route", ["prefill", "packed", "chunks"])
def test_every_route_matches_the_reference_across_the_window(
        cfg, params, route, attn_impl):
    """(B, L) prefill, a packed prefill of three uneven prompts, a prompt
    over three chunks; then ``decode_step`` and a fused ``decode_multi``
    window.  The prompt of 48 is three windows long; the prompt of 14
    crosses the window of 16 while it decodes.  ``pallas``: the paged
    attention kernels in interpret mode (the grouped product is a kernel
    on both)."""
    served = run_route(FAMILY, cfg, params, route, attn_impl)
    # every dispatch counted k rows a slot a layer, padding included
    assert served.counts[:-1].sum() % (cfg.num_experts_per_tok
                                       * cfg.num_layers) == 0
    assert served.counts[-1] > 0


def test_both_layer_kinds_and_their_tables_are_live(cfg, params):
    """A window ignored, or the full layers' table taken for the plain
    one, moves the reference's logits at a position past the window by far
    more than the tolerance: the agreement above is not vacuous."""
    seq = prompts_of(40, seed=3)[0]
    want = ref_logits(FAMILY, params, cfg, seq, [39])[0]
    all_full = dataclasses.replace(cfg, window_layers=(False,) * 8)
    plain = dataclasses.replace(cfg, rope_full_yarn=(1.0000001, 32, 1, 32))
    for broken in (all_full, plain):
        got = np.asarray(ref.logits_at(params, broken,
                                       np.asarray([seq], np.int32),
                                       [(0, 39)]))[0]
        assert np.max(np.abs(got - want)) > 1e-2


def test_the_reference_replays_a_named_pick_only_at_a_near_tie(cfg, params):
    """``route`` with the server's picks: a row whose named experts all
    score within ``TIE`` of the router's own k-th takes them (and weighs
    them by its own float32 scores); a row with one named expert further
    behind keeps the router's own, so a wrong pick still shows."""
    fresh = plan.load_reference({"reference": "mellum2"})
    lp, k = params["layers"][0], cfg.num_experts_per_tok
    h = jnp.asarray(np.random.RandomState(4).randn(6, cfg.hidden_size),
                    jnp.float32)
    logits = np.asarray(fresh._linear(h, lp["router"]))
    order = np.argsort(-logits, axis=1)
    none = jnp.full((6, k), -1, jnp.int32)
    own = np.asarray(fresh.route(lp, h, cfg, none))
    assert [sorted(np.flatnonzero(r)) for r in own] \
        == [sorted(o[:k]) for o in order]
    # the server took the (k+1)-th for the k-th
    named = order[:, :k].copy()
    named[:, -1] = order[:, k]
    gap = logits[np.arange(6), order[:, k - 1]] \
        - logits[np.arange(6), order[:, k]]
    fresh.TIE = float(np.sort(gap)[2]) * 1.0001     # three rows are ties
    got = np.asarray(fresh.route(lp, h, cfg, jnp.asarray(named, jnp.int32)))
    for row in range(6):
        want = named[row] if gap[row] <= fresh.TIE else order[row, :k]
        assert sorted(np.flatnonzero(got[row])) == sorted(want), row
        np.testing.assert_allclose(got[row].sum(), 1.0, rtol=1e-6)
        scores = np.exp(logits[row, want])
        np.testing.assert_allclose(np.sort(got[row][want]),
                                   np.sort(scores / scores.sum()), rtol=1e-5)
    assert sum(gap <= fresh.TIE) == 3
    # the worst expert of all is no tie at any sensible tolerance
    fresh.TIE = 0.1
    named[:, -1] = order[:, -1]
    np.testing.assert_array_equal(
        np.asarray(fresh.route(lp, h, cfg, jnp.asarray(named, jnp.int32))),
        own)


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------


@pytest.mark.parametrize("multi_step,attn_impl", [
    (1, "reference"), (4, "reference"), (4, "pallas")])
def test_served_greedy_tokens_are_the_references(cfg, params, multi_step,
                                                 attn_impl):
    eng = engine_for(FAMILY, params, cfg, multi_step=multi_step,
                     attn_impl=attn_impl)
    prompts = prompts_of(40, 9, seed=5)
    outs = eng.generate(prompts, SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True))
    for p, o in zip(prompts, outs):
        assert o.output_token_ids == ref_greedy(FAMILY, params, cfg, p, 10)
    assert eng.block_manager.num_seqs() == 0


def test_routing_counts_come_back_with_the_tokens(cfg, params):
    """Step records of prefill and window steps carry ``moe_rows`` and
    ``moe_expert_hits``; the engine's totals are their sums; nothing is
    left in flight once the engine is drained; a model without experts
    has none of it."""
    eng = engine_for(FAMILY, params, cfg, multi_step=4)
    eng.generate(prompts_of(21, 6, seed=9), SamplingParams(
        max_tokens=9, temperature=0.0, ignore_eos=True))
    steps = [s for s in eng.flight.steps_snapshot(limit=1 << 20)
             if "moe_rows" in s]
    assert {s["kind"] for s in steps} >= {"prefill", "window"}
    per = cfg.num_experts_per_tok * cfg.num_layers
    for s in steps:
        # the rows a dispatch computed: every padded slot, k picks a layer
        assert s["moe_rows"] == s["padded_tokens"] * per, s
        assert 0 < s["moe_expert_hits"] <= (
            cfg.num_experts * cfg.num_layers
            * max(1, s["padded_tokens"] // max(s["rows"], 1)))
    st = eng.stats
    assert st.moe_routed_rows == sum(s["moe_rows"] for s in steps)
    assert st.moe_expert_hits == sum(s["moe_expert_hits"] for s in steps)
    assert st.moe_expert_rows.shape == (cfg.num_experts,)
    assert st.moe_expert_rows.sum() == st.moe_routed_rows
    assert eng._moe_inflight == []

    dense = Engine(EngineConfig(
        model="tiny-llama",
        cache=CacheConfig(block_size=BLOCK, num_blocks=64,
                          max_blocks_per_seq=16)))
    dense.generate([[5, 6, 7]], SamplingParams(max_tokens=4,
                                               temperature=0.0))
    assert dense.stats.moe_expert_rows is None
    assert all("moe_rows" not in s
               for s in dense.flight.steps_snapshot(limit=1 << 20))


def ref_picks(params, cfg, seq):
    """The reference router's own picks at every position of ``seq``:
    (positions, layers, k), each layer's picks sorted."""
    x = ref._f32(params["embed"]["weight"][jnp.asarray([seq], jnp.int32)])
    positions = jnp.arange(len(seq), dtype=jnp.int32)[None]
    none = jnp.full((1, len(seq), cfg.num_experts_per_tok), -1, jnp.int32)
    out = []
    for li, lp in enumerate(params["layers"]):
        x = ref._attention_branch(lp, x, positions, cfg,
                                  bool(cfg.window_layers[li]))
        h = ref._rmsnorm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)[0]
        out.append(np.sort(np.asarray(jax.lax.top_k(
            ref._linear(h, lp["router"]), cfg.num_experts_per_tok)[1])))
        x = ref._expert_branch(lp, x, cfg, none)
    return np.stack(out, axis=1)


@pytest.mark.parametrize("route,kw", [
    ("packed", {"multi_step": 4}),
    ("packed, single steps", {"multi_step": 1}),
    ("chunks", {"multi_step": 4, "chunk": 16}),
    ("mixed", {"multi_step": 1, "mixed": True}),
    ("batched", {"multi_step": 4, "kv": "bfloat16"}),
])
def test_logprobs_carry_every_pick_their_logits_went_through(cfg, params,
                                                             route, kw):
    """A request that asks for logprobs gets, beside each token, the
    experts each layer routed its position to, and beside the first the
    same for every position of the prompt: on every prefill route and from
    fused windows they are the float32 reference router's own (the tiny
    model is float32; under bf16 pages, which only the batched route
    takes, a near-tie may fall the other way).  A request that does not
    ask keeps nothing."""
    eng = Engine(EngineConfig(
        model=MODEL, multi_step=kw["multi_step"],
        cache=CacheConfig(block_size=BLOCK, num_blocks=96,
                          max_blocks_per_seq=24,
                          dtype=kw.get("kv", "float32")),
        scheduler=SchedulerConfig(
            min_prefill_bucket=8, min_decode_bucket=2,
            prefill_chunk_size=kw.get("chunk", 2048),
            mixed_batching=kw.get("mixed", False))),
        params=params, model_cfg=cfg)
    assert eng._packed_prefill == (route != "batched")
    prompts = prompts_of(40, 9, seed=5)
    outs = eng.generate(prompts, SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True, logprobs=2))
    kinds = {s["kind"] for s in eng.flight.steps_snapshot(limit=1 << 20)}
    assert {"chunks": "prefill_chunk", "mixed": "mixed"}.get(
        route, "prefill") in kinds, kinds
    for p, o in zip(prompts, outs):
        want = ref_picks(params, cfg, p + o.output_token_ids)
        got = np.sort(np.asarray(
            o.logprobs[0]["prompt_routed_experts"]
            + [e["routed_experts"] for e in o.logprobs[1:]]), axis=-1)
        assert got.shape == want[:-1].shape == (
            len(p) + 5, cfg.num_layers, cfg.num_experts_per_tok)
        # the prompt's last position is the first token's own
        assert o.logprobs[0]["routed_experts"] \
            == o.logprobs[0]["prompt_routed_experts"][-1]
        assert all("prompt_routed_experts" not in e for e in o.logprobs[1:])
        same = np.mean(np.all(got == want[:-1], axis=-1))
        assert same == 1.0 if route != "batched" else same > 0.9, same
    (plain,) = eng.generate(prompts[:1], SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    assert plain.output_token_ids == outs[0].output_token_ids
    assert plain.logprobs in (None, [])
    assert not eng.requests or all(
        not r.prompt_picks for r in eng.requests.values())


def test_window_dead_tokens_are_what_no_step_will_read(cfg, params):
    """Token-layers a windowed layer holds more than window + one block
    behind its sequence's end: 6 of the 8 layers are windowed, so a
    sequence of n tokens holds 6 * max(0, n - 16 - BLOCK) of them; layers
    of two kinds release nothing, so they stay held."""
    eng = engine_for(FAMILY, params, cfg, multi_step=4)
    assert eng.window_dead_tokens() == 0
    eng.add_request("a", prompts_of(40, seed=2)[0], SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    eng.add_request("b", prompts_of(7, seed=3)[0], SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    eng.step()                                  # the prefill
    # (the first token may or may not be appended yet)
    assert eng.window_dead_tokens() in (6 * (40 - 16 - BLOCK),
                                        6 * (41 - 16 - BLOCK))
    while eng.has_work():
        eng.step()
    assert eng.window_dead_tokens() == 0
    assert eng.stats.released_blocks == 0


# --------------------------------------------------------------------------
# the configuration and its tables
# --------------------------------------------------------------------------

def test_config_json_maps_onto_the_registered_model():
    reg = get_model_config(PUBLISHED)
    assert config_from_hf_json(PUBLISHED, CATALOG_CONFIG) == reg
    assert get_model_config("mellum2-12b") is reg
    # what the configuration file's keys are held to
    assert reg.layer_types == CATALOG_CONFIG["layer_types"]
    assert reg.mlp_layer_types == CATALOG_CONFIG["mlp_layer_types"]
    assert reg.rope_parameters == CATALOG_CONFIG["rope_parameters"]
    assert reg.max_window_layers == 0
    assert reg.attn_scale == 128 ** -0.5
    assert not reg.uniform_window and reg.routes_experts
    cut = dataclasses.replace(reg, num_layers=12)
    assert cut.layer_types == PERIOD * 3
    assert round(cut.num_params / 1e9, 2) == 5.47
    assert [cut.layer_window(i) for i in range(4)] == [1024] * 3 + [None]
    assert cut.layer_yarn(0) is None
    assert cut.layer_yarn(3) == (16, 32, 1, 0, 0, 8192)


@pytest.mark.parametrize("bad", [
    {"mlp_layer_types": ["sparse"] * 27 + ["dense"]},
    {"layer_types": None},
    {"max_window_layers": 4},
    {"rope_parameters": {"full_attention": {"rope_type": "default",
                                            "rope_theta": 500000},
                         "sliding_attention": {"rope_type": "default",
                                               "rope_theta": 500000}}},
    {"use_sliding_window": False},
])
def test_what_the_family_does_not_implement_raises(bad):
    with pytest.raises(ValueError):
        config_from_hf_json("x", {**CATALOG_CONFIG, **bad})


def hf_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """NumPy transcription of HF ``_compute_yarn_parameters`` (no mscale
    pair, ``truncate`` true)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv = interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor
    return inv, 0.1 * math.log(factor) + 1.0, low, high


def test_the_yarn_table_is_hfs_at_the_published_numbers():
    reg = get_model_config(PUBLISHED)
    inv, att, low, high = hf_yarn_inv_freq(128, 500000.0, 16, 8192, 32, 1)
    assert (low, high) == (18, 35)
    assert att == pytest.approx(1.27726, abs=1e-5)
    assert att == CATALOG_CONFIG["rope_parameters"]["full_attention"][
        "attention_factor"]
    pos = np.asarray([0, 1, 17, 1023, 1024, 4095])
    # a full layer: the engine's table and the reference's
    cos, sin = rope_ops.rope_freqs(jnp.asarray(pos), 128, reg.rope_theta,
                                   yarn_scaling=reg.layer_yarn(3))
    ang = pos[:, None].astype(np.float64) * inv[None, :]
    np.testing.assert_allclose(np.asarray(cos), att * np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), att * np.sin(ang), atol=2e-3)
    ref_inv, ref_att = ref.rotary_table(reg, windowed=False)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_att == att
    # below the low correction dim nothing is scaled, above the high one
    # every frequency is divided by the factor
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-12)
    # a windowed layer: the plain table, no factor
    cos, sin = rope_ops.rope_freqs(jnp.asarray(pos[:4]), 128, reg.rope_theta,
                                   yarn_scaling=reg.layer_yarn(0))
    np.testing.assert_allclose(
        np.asarray(cos), np.cos(pos[:4, None] * plain[None, :]), atol=2e-4)
    ref_inv, ref_att = ref.rotary_table(reg, windowed=True)
    np.testing.assert_allclose(ref_inv, plain, rtol=1e-6)
    assert ref_att == 1.0


def test_each_family_is_kept_from_the_other_references(cfg):
    ref.check_family(cfg)
    ref.check_family(get_model_config(PUBLISHED))
    for other in ("tiny-moe", "tiny-mistral", "tiny-falcon-h1",
                  "tiny-deepseek", "tiny-gemma3"):
        with pytest.raises(ValueError):
            ref.check_family(get_model_config(other))
    for name in ("dense_gqa", "falcon_h1"):
        with pytest.raises(ValueError):
            plan.load_reference({"reference": name}).check_family(cfg)
