"""openPangu-Ultra-MoE on the normal path: latent attention whose ONE
cached vector a token a layer is read in the absorbed form, by the XLA path
and by the paged Pallas kernels' latent entry alike, under sandwich norms
and behind a sigmoid router with no groups and no selection bias, as one
chip's share of a deployment whose chips share each expert layer.

Everything is compared with the plain reference the benchmark scores this
family by (``benchmark/reference/openpangu_moe.py``: float32, latent
attention in its NAIVE form, every HELD expert on every token weighted by
the router's choice over ALL experts; no code shared with ``tpuserve``), on
the registered ``tiny-pangu`` (float32; 8 heads of 16 + 12 wide keys and
16 wide values from a latent of 136 under a query latent of 40, so the
cached vector is 148 wide, stored as 256; two dense layers, then 16
experts, 4 a token, scaled 2.5, beside a shared one) under seeded random
weights; a share is 4 of the 16 experts.  Logits, not tokens.

Tolerances: both sides are float32 on the CPU, so what separates them is
the ORDER of the same sums (the absorbed products ``(q W_uk) . c`` against
``q . (c W_uk)``, blocked attention with an online softmax against a dense
one, grouped products over sorted rows against a loop over experts): a few
1e-6 on logits of size ~1-3.  ``ATOL`` 2e-4 leaves two orders of magnitude
over that; each of the five wrong-mathematics cases below is off by over
twenty times ``ATOL``.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_routes import (FAMILIES, Served, engine_for, plan, prompts_of,
                           ref_greedy, ref_logits, run_route)
from tpuserve.models import transformer
from tpuserve.models.config import (ModelConfig, config_from_hf_json,
                                    get_model_config)
from tpuserve.models.weights import init_params
from tpuserve.ops import attention as attn_ops
from tpuserve.ops import rope as rope_ops
from tpuserve.runtime import SamplingParams

FAMILY = FAMILIES["openpangu"]
ATOL = FAMILY.atol
MODEL = FAMILY.model
PUBLISHED = "FreedomIntelligence/openPangu-Ultra-MoE-718B"
HELD = 4                # experts of the tiny model's 16 one share holds
SHARES = 4
EXPERT_LAYER = 2        # the first layer after the two dense ones

ref = FAMILY.ref


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
    return cfg, init_params(cfg, seed=17)


@pytest.fixture(scope="module")
def shared(whole):
    """The share the cell holds: the first experts."""
    return share_of(*whole, 0)


def rows_of(n, seed=1, hidden=64):
    return jnp.asarray(np.random.RandomState(seed).randn(n, hidden),
                       jnp.float32)


def ref_layer(lp, h, cfg):
    """The reference's expert layer on rows ``h``: the held experts' part
    and the shared expert, apart (before the sandwich's post-norm)."""
    none = jnp.full((h.shape[0], cfg.num_experts_per_tok), -1, jnp.int32)
    return (np.asarray(ref._held_experts(lp, h, cfg, none)),
            np.asarray(ref._gated_mlp(h, lp["shared"])))


# --------------------------------------------------------------------------
# (a) the shares add up
# --------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(whole):
    """What ties the share to the model: at the tiny size the held parts of
    all four chips that share a layer, with the shared expert (which every
    chip computes alike) counted once, are the uncut reference layer and
    the uncut served layer; each share is the reference handed that share;
    and no share is the whole."""
    cfg, params = whole
    lp, h = params["layers"][EXPERT_LAYER], rows_of(37)
    uncut_routed, always = ref_layer(lp, h, cfg)
    parts = []
    for share in range(SHARES):
        scfg, sparams = share_of(cfg, params, share)
        slp = sparams["layers"][EXPERT_LAYER]
        got = np.asarray(transformer._moe_mlp(h, slp, scfg))
        routed, _ = ref_layer(slp, h, scfg)
        np.testing.assert_allclose(got, routed + always, atol=1e-5)
        parts.append(got - always)
    np.testing.assert_allclose(sum(parts) + always, uncut_routed + always,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(transformer._moe_mlp(h, lp, cfg)),
                               uncut_routed + always, atol=1e-5)
    assert all(np.max(np.abs(p - uncut_routed)) > 1e-2 for p in parts)


def test_the_router_has_no_groups_and_no_selection_bias(whole):
    """The picks are the 4 largest sigmoid scores over all 16 experts, and
    the weights 2.5 p / (sum of the 4 + 1e-20): no parameter of the layer
    is a selection bias."""
    cfg, params = whole
    lp, h = params["layers"][EXPERT_LAYER], rows_of(23, seed=4)
    assert "router_bias" not in lp
    tally = []
    transformer._moe_mlp(h, lp, cfg, tally)
    (_, picks, _), = tally
    p = 1 / (1 + np.exp(-np.asarray(h, np.float64)
                        @ np.asarray(lp["router"]["kernel"], np.float64)))
    want = np.argsort(-p, axis=1)[:, :cfg.num_experts_per_tok]
    assert np.array_equal(np.sort(np.asarray(picks), 1), np.sort(want, 1))
    none = jnp.full((23, cfg.num_experts_per_tok), -1, jnp.int32)
    w = np.asarray(ref.route(lp, h, cfg, none))
    top = np.take_along_axis(p, want, 1)
    np.testing.assert_allclose(
        np.take_along_axis(w, want, 1),
        2.5 * top / (top.sum(1, keepdims=True) + 1e-20), atol=1e-6)


# --------------------------------------------------------------------------
# (b) the absorbed form is the naive form
# --------------------------------------------------------------------------

def _attend(cfg, lp, h, absorbed, attn_impl="reference", scale=None):
    """One layer's attention output (T, heads, v) for a sequence of rows
    ``h``, every row against the rows up to it: naive over the fresh
    decompressed K and V, or absorbed against latent pages through the
    paged decode op (row t as a decode row of length t + 1)."""
    T = h.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    scale = scale or cfg.attn_scale
    q_nope, q_rope, latent = transformer._mla_proj(h[None], lp, cfg,
                                                   pos[None])
    if not absorbed:
        k, v = transformer._mla_decompress(latent, lp, cfg, jnp.float32)
        q = jnp.concatenate([q_nope, q_rope], -1)
        return attn_ops.prefill_attention(
            q, k, v, jnp.asarray([T], jnp.int32), scale)[0]
    page, nb = 4, -(-T // 4)
    entry = {"k": jnp.zeros((nb, page, 1, cfg.cache_head_dim), jnp.float32)}
    entry = attn_ops.write_mla_entry(entry, latent[0], pos)
    q_eff = transformer._mla_absorb_q(q_nope[0], q_rope[0], lp, cfg)
    tables = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32), (T, nb))
    if attn_impl == "pallas":
        from tpuserve.ops.pallas_paged_attention import \
            paged_decode_attention
        out = paged_decode_attention(q_eff, entry["k"], None, tables,
                                     pos + 1, scale,
                                     v_lanes=cfg.mla_kv_lora_rank,
                                     pages_per_group=2, seqs_per_program=4)
    else:
        out = attn_ops.paged_decode_attention(q_eff, entry["k"], entry["k"],
                                              tables, pos + 1, scale)
    return transformer._mla_unabsorb(out, lp, cfg)


@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
def test_absorbed_decode_is_the_naive_form_at_float32(whole, attn_impl):
    """Folding ``W_uk`` into the query and ``W_uv`` into the output changes
    the order of the sums and nothing else: to 1e-5 at float32, on the XLA
    path and in the kernel's latent entry (V the first 136 lanes of the K
    page it landed, the page 256 lanes wide with zeros past 148)."""
    cfg, params = whole
    lp, h = params["layers"][0], rows_of(19, seed=2)
    naive = np.asarray(_attend(cfg, lp, h, absorbed=False))
    got = np.asarray(_attend(cfg, lp, h, True, attn_impl))
    assert got.shape == (19, cfg.num_heads, cfg.mla_v_head_dim)
    np.testing.assert_allclose(got, naive, atol=1e-5)


# --------------------------------------------------------------------------
# (c) every route through the paged latent cache, against the full forward
# --------------------------------------------------------------------------

@pytest.mark.parametrize("route,attn_impl", [
    ("prefill", "reference"), ("packed", "reference"), ("packed", "pallas"),
    ("chunks", "reference"), ("chunks", "pallas"),
    ("mixed", "reference"), ("mixed", "pallas")])
def test_every_route_matches_the_reference_under_a_share(
        shared, route, attn_impl):
    """(B, L) prefill (the XLA path's alone: it has no Pallas form), a
    packed prefill of three uneven prompts, a prompt over three chunks (the
    second and third against cached latents), a mixed step (two running
    rows, each against its own latent pages, riding the third prompt's
    dispatch); then ``decode_step`` and a
    fused ``decode_multi`` window, with 4 of 16 experts held.  ``pallas``:
    the paged attention kernels' latent entry in interpret mode (the
    grouped product is a kernel on both)."""
    cfg, params = shared
    served = run_route(FAMILY, cfg, params, route, attn_impl)
    assert served.kv[0]["k"].shape[-2:] == (1, 256) and len(served.kv) == 5
    assert all(set(entry) == {"k"} for entry in served.kv)
    E, per = cfg.num_experts, cfg.num_experts_per_tok * 3   # expert layers
    assert served.counts.shape == (E + 5,)
    assert served.counts[:E].sum() % per == 0
    # what landed here is what was routed to the first 4 experts
    assert served.counts[E + 1] == served.counts[:HELD].sum() > 0


def test_the_packed_route_with_every_expert_held(whole):
    cfg, params = whole
    served = run_route(FAMILY, cfg, params, "packed", "pallas")
    assert served.counts.shape == (cfg.num_experts + 1,)


def test_a_long_packed_prefill_attends_a_piece_of_the_stream_at_a_time(
        shared, monkeypatch):
    """The absorbed queries of a whole rung never stand at once: past
    ``MLA_PACKED_ROWS`` the packed route attends its stream in pieces,
    each the same kernel on a slice with the sequences' first rows counted
    from the slice's own.  At 16 rows a piece the three prompts (40, 6 and
    29 tokens on 88 rows) span six pieces, two prompts cross a piece's
    edge, and the last piece is half padding."""
    cfg, params = shared
    monkeypatch.setattr(transformer, "MLA_PACKED_ROWS", 16)
    assert _packed_off_by(_named(cfg, "pieces"), params, ref_cfg=cfg,
                          attn_impl="pallas") < ATOL


def test_the_grid_prefill_has_no_pallas_form(shared):
    """An MLA model's batched prefills go out packed wherever the kernels
    are on; the (B, L) trunk says so instead of running XLA's attention
    under the kernels' name."""
    cfg, params = shared
    with pytest.raises(NotImplementedError, match="go out packed"):
        Served(FAMILY, cfg, params, 3, "pallas").prefill(
            prompts_of(*FAMILY.prompts))


# --------------------------------------------------------------------------
# (d) computing something else would fail
# --------------------------------------------------------------------------

def _packed_off_by(cfg, params, ref_cfg=None, dtype="float32",
                   attn_impl="reference"):
    """How far the packed route's first logits stand from the reference's,
    over the family's three prompts."""
    seqs = prompts_of(*FAMILY.prompts)
    first = Served(FAMILY, cfg, params, 3, attn_impl, dtype).packed(seqs)
    return max(np.max(np.abs(
        first[i].astype(np.float32)
        - ref_logits(FAMILY, params, ref_cfg or cfg, s, [len(s) - 1])[0]))
        for i, s in enumerate(seqs))


def _named(cfg, what, **kw):
    # a name of its own: the layer bodies are jitted by the ModelConfig
    return dataclasses.replace(cfg, name=f"{cfg.name}-{what}", **kw)


def test_the_sound_path_is_inside_the_tolerance(shared):
    cfg, params = shared
    assert _packed_off_by(cfg, params) < ATOL
    assert _packed_off_by(cfg, params, attn_impl="pallas") < ATOL


@pytest.mark.parametrize("fault", [
    "scale by the latent's width", "values from lanes 12 on",
    "a rope key rotated per head", "a dropped post-norm",
    "bfloat16 latents"])
def test_wrong_mathematics_fails_the_tolerance(shared, monkeypatch, fault):
    """Each is a plausible way to serve this family wrong that computes no
    more than the sound path: the score scale taken from the cached
    vector's width (148^-0.5, as 576^-0.5 would be) and not from the
    key's (28^-0.5); the values read from the page's lanes 12-147 (behind
    a rope key stored FIRST) and not 0-135; the shared rope key turned by a
    different angle for every head; the sandwich's post-norms left out;
    the latent stored in bfloat16 under a float32 configuration."""
    cfg, params = shared
    bad, dtype = _named(cfg, "fault"), "float32"
    if fault == "scale by the latent's width":
        bad = _named(cfg, "scale", query_pre_attn_scalar=cfg.mla_latent_dim)
    elif fault == "values from lanes 12 on":
        rope, rank = cfg.mla_qk_rope_head_dim, cfg.mla_kv_lora_rank
        sound = transformer._mla_unabsorb
        monkeypatch.setattr(
            transformer, "_mla_unabsorb", lambda out, lp, c: sound(
                out[..., rope:rope + rank], lp, c))
    elif fault == "a rope key rotated per head":
        sound = transformer._mla_queries

        def per_head(cq, lp, c, positions, ad=None):
            q_nope, q_rope = sound(cq, lp, c, positions, ad)
            # turning head i's query back by i positions is the key
            # turned forward by i for that head
            cos, sin = rope_ops.rope_freqs(
                -jnp.arange(c.num_heads), c.mla_qk_rope_head_dim,
                c.rope_theta)
            half = cos.shape[-1]
            x1, x2 = q_rope[..., :half], q_rope[..., half:]
            return q_nope, jnp.concatenate(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
        monkeypatch.setattr(transformer, "_mla_queries", per_head)
    elif fault == "a dropped post-norm":
        bad = _named(cfg, "no-post-norm", sandwich_norms=False)
    else:
        dtype = "bfloat16"
    off = _packed_off_by(bad, params, ref_cfg=cfg, dtype=dtype)
    assert off > 20 * ATOL, (fault, off)


def test_the_references_own_switches_are_live(shared):
    """The scaling dropped, the share shifted by one expert or the
    sandwich's norms ignored each moves the REFERENCE's logits by far more
    than ATOL: the agreement above is not vacuous on that side either."""
    cfg, params = shared
    seq = prompts_of(40, seed=3)[0]
    want = ref_logits(FAMILY, params, cfg, seq, [39])[0]
    broken = {
        "no scaling": dataclasses.replace(cfg, moe_routed_scaling=1.0),
        "share shifted by one": dataclasses.replace(cfg, moe_first_expert=1),
        "theta of 10,000": dataclasses.replace(cfg, rope_theta=1e4),
    }
    for what, bad in broken.items():
        got = np.asarray(ref.logits_at(
            params, bad, np.asarray([seq], np.int32), [(0, 39)]))[0]
        assert np.max(np.abs(got - want)) > 1e-2, what
    with pytest.raises(ValueError, match="norms"):
        ref.check_family(dataclasses.replace(cfg, sandwich_norms=False))


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("multi_step,attn_impl,rides", [
    (1, "reference", False), (4, "reference", False), (4, "pallas", False),
    (4, "reference", True), (4, "pallas", True)])
def test_served_greedy_tokens_are_the_references(shared, multi_step,
                                                 attn_impl, rides,
                                                 monkeypatch):
    """Through ``Engine.step``: packed prefills, then decode steps or fused
    windows, the latent kernels under ``pallas``; and the counter of
    context tokens attended against latent pages is the step records'
    ``ctx_tokens`` of the dispatches that are no prefill, summed.
    ``rides``: the engine observes that its decode step is bound by its
    weights (the floor under which the host binds it steered to 0, as
    ``tests/test_mixed.py`` does), so the second and third prompts arrive
    on mixed steps that carry the running rows."""
    from tpuserve.runtime import engine as engine_mod
    cfg, params = shared
    if rides:
        monkeypatch.setattr(engine_mod, "HOST_BOUND_WEIGHT_BYTES", 0)
    eng = engine_for(FAMILY, params, cfg, multi_step=multi_step,
                     attn_impl=attn_impl)
    assert eng.attn_impl == attn_impl and eng._packed_prefill
    assert eng._route["rides"] is rides, eng._route
    prompts = prompts_of(40, 9, 21, seed=5)
    sampling = SamplingParams(max_tokens=10, temperature=0.0,
                              ignore_eos=True)
    rids = [eng.add_request(prompt_token_ids=prompts[0], params=sampling)]
    got, cycles = {}, 0
    while eng.has_work():
        for o in eng.step():
            got.setdefault(o.request_id, []).extend(o.new_token_ids)
        cycles += 1
        if cycles == 2:         # the first prompt's row is running by now
            rids += [eng.add_request(prompt_token_ids=p, params=sampling)
                     for p in prompts[1:]]
    for p, rid in zip(prompts, rids):
        assert got[rid] == ref_greedy(FAMILY, params, cfg, p, 10)
    assert eng.block_manager.num_seqs() == 0
    records = eng.flight.steps_snapshot(1024)
    assert ("mixed" in {s["kind"] for s in records}) is rides
    assert (eng.stats.decode_tokens_ridden > 0) is rides
    steps = [s for s in records if s["kind"] in ("decode", "window", "mixed")]
    assert steps and eng.stats.kv_latent_tokens_attended_total == sum(
        s["ctx_tokens"] for s in steps) > 0


def test_a_model_with_k_and_v_pages_attends_no_latent_tokens():
    eng = engine_for(FAMILIES["k_exaone"])
    eng.generate(prompts_of(9), SamplingParams(max_tokens=4, temperature=0.0))
    assert eng.stats.kv_latent_tokens_attended_total == 0


def test_logprobs_name_the_picks_of_the_expert_layers(shared):
    """What the reference replays: a response's logprobs object names, for
    the prompt's positions and for each served token, the 4 experts each
    of the 3 expert layers picked (the two dense layers have none)."""
    cfg, params = shared
    eng = engine_for(FAMILY, params, cfg, multi_step=4)
    (out,) = eng.generate(prompts_of(12, seed=8), SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True, logprobs=1))
    first = out.logprobs[0]
    assert np.asarray(first["prompt_routed_experts"]).shape == (12, 3, 4)
    for entry in out.logprobs:
        picks = np.asarray(entry["routed_experts"])
        assert picks.shape == (3, 4)
        assert picks.min() >= 0 and picks.max() < cfg.num_experts


# --------------------------------------------------------------------------
# the configuration
# --------------------------------------------------------------------------

def catalog_config() -> dict:
    """The catalog's ``config`` of the model (model-configs guide,
    architectures.jsonl)."""
    return {
        "attention_bias": False, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "sandwich_norm": True,
        "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600,
    }


def test_config_json_maps_onto_the_registered_model():
    """The catalog's ``config`` gives the preset, field for field, and the
    properties a configuration file's keys are held to spell it back."""
    hf = catalog_config()
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if os.path.isfile(row):             # the catalog itself, where it is
        with open(row) as f:
            entry = next(json.loads(line) for line in f
                         if '"openPangu-Ultra-MoE-718B"' in line)
        assert entry["config"] == hf
    got = config_from_hf_json(PUBLISHED, hf)
    assert got == get_model_config(PUBLISHED)
    assert get_model_config("openpangu-ultra-718b") is get_model_config(
        PUBLISHED)
    for key, field in {**plan.FIXED, **plan.CUTTABLE, **ref.FIXED}.items():
        if key in hf:
            assert getattr(got, field) == hf[key], key
    assert set(hf) <= set(plan.FIXED) | set(plan.CUTTABLE) \
        | set(ref.FIXED) | set(plan.DESCRIPTIVE) | set(ref.DESCRIPTIVE)
    assert got.moe_experts_held == 0 and got.moe_local_experts == 256
    # the cached vector: 576 values, stored as five whole lane tiles
    assert (got.mla_latent_dim, got.cache_head_dim) == (576, 640)
    assert got.attn_scale == 192 ** -0.5 and got.cache_kv_heads == 1
    assert not got.moe_router_bias and got.moe_n_group == 1
    ref.check_family(got)
    tiny = get_model_config(MODEL)
    ref.check_family(tiny)
    assert (tiny.mla_latent_dim, tiny.cache_head_dim) == (148, 256)
    assert tiny.mla_latent_dim % 8 and tiny.mla_latent_dim % 128


@pytest.mark.parametrize("bad,why", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"n_group": 8, "topk_group": 4}, "grouped routing"),
    ({"scoring_func": "softmax"}, "router"),
    ({"topk_method": "group_limited_greedy"}, "router"),
])
def test_what_the_family_does_not_implement_raises(bad, why):
    with pytest.raises(ValueError, match=why):
        config_from_hf_json("x", {**catalog_config(), **bad})
