"""Falcon-H1 on the normal path: Mamba-2 state-space heads beside attention
heads in every layer, a recurrent state a SEAT beside the paged KV cache.

Everything is compared with the plain reference the benchmark scores this
family by (``benchmark/reference/falcon_h1.py``: float32, the recurrence
token by token, no code shared with ``tpuserve``), on the registered
``tiny-falcon-h1`` (float32; 10 query heads on 2 KV heads, 2 B/C groups, a
scan chunk of 8, every multiplier off 1) under seeded random weights.

Tolerances: both sides are float32 on the CPU, so what separates them is
the ORDER of the same sums (the chunked scan's matrix products against the
reference's token loop, blocked attention against a dense softmax): a few
1e-6 on logits of size ~1-4.  ``ATOL`` 2e-4 leaves two orders of
magnitude over that and sits three orders under what a left-out term
moves (``test_every_term_of_the_layer_is_live``: over 1e-2 each).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_routes import (FAMILIES, ROOT, engine_for, plan, prompts_of,
                           run_route, serve)
from tpuserve.models import transformer
from tpuserve.models.config import (config_from_hf_json, get_model_config)
from tpuserve.models.weights import init_params
from tpuserve.ops import ssm as ssm_ops
from tpuserve.runtime.kv_cache import ssm_state_bytes

FAMILY = FAMILIES["falcon_h1"]
ATOL = FAMILY.atol
MODEL = FAMILY.model

ref = FAMILY.ref

@pytest.fixture(scope="module")
def cfg():
    return get_model_config(MODEL)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, seed=7)


# --------------------------------------------------------------------------
# the trunks, driven by hand: logits against the reference at every position
# --------------------------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
@pytest.mark.parametrize("route", ["prefill", "packed", "chunks"])
def test_every_route_matches_the_reference_at_every_position(
        cfg, params, route, attn_impl):
    """(B, L) prefill, a packed prefill of three uneven prompts, a prompt
    over several chunks; then ``decode_step`` and a fused ``decode_multi``
    window.  ``pallas``: the paged kernels and the state-update kernel in
    interpret mode."""
    run_route(FAMILY, cfg, params, route, attn_impl)


@pytest.mark.parametrize("length", [1, 5, 8, 13, 27])
def test_the_chunked_scan_is_the_plain_recurrence(length):
    """``ssd_chunk_scan`` (chunk 8) against the token-by-token loop, from a
    non-zero state, at lengths that are not multiples of the chunk: the
    rows past the end carry dt = 0 and must change nothing.  Float32
    against float64: 1e-5 on values of size ~1."""
    H, P, N, G, Q = 4, 6, 5, 2, 8
    T = -(-length // Q) * Q
    rs = np.random.RandomState(length)
    x, bm, cm = rs.randn(T, H, P), rs.randn(T, G, N), rs.randn(T, G, N)
    dt = np.where(np.arange(T)[:, None] < length,
                  rs.uniform(0.01, 0.5, (T, H)), 0.0)
    a = -rs.uniform(0.5, 8.0, H)
    s0 = rs.randn(1, H, P, N)
    state, want = s0[0].copy(), []
    for t in range(length):
        bh, ch = np.repeat(bm[t], H // G, 0), np.repeat(cm[t], H // G, 0)
        state = (np.exp(dt[t] * a)[:, None, None] * state
                 + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :])
        want.append(np.einsum("hpn,hn->hp", state, ch))
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    y, finals = ssm_ops.ssd_chunk_scan(
        f32(x), f32(dt), f32(a), f32(bm), f32(cm), f32(s0),
        jnp.zeros((T // Q,), jnp.int32), chunk=Q)
    np.testing.assert_allclose(np.asarray(y)[:length], want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(finals)[0], state, atol=1e-5)


@pytest.mark.parametrize("shape", [(4, 4, 8, 16, 2), (5, 32, 16, 128, 2),
                                   (3, 6, 8, 128, 1)],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_state_update_kernel_is_the_formula(shape):
    """``_ssm_state_update`` in interpret mode against the formula on
    gathered rows, on a pool with more seats than rows: the rows' seats are
    updated in place, every other seat is left as it was."""
    from tpuserve.ops.pallas_ssm_update import (ssm_state_update,
                                                ssm_state_update_reference)
    B, H, P, N, G = shape
    k = jax.random.split(jax.random.key(B), 5)
    state = jax.random.normal(k[0], (B + 4, H, P, N), jnp.float32)
    seats = jnp.asarray(np.random.RandomState(B).permutation(B + 4)[:B],
                        jnp.int32)
    decay = jax.random.uniform(k[1], (B, H))
    dtx = jax.random.normal(k[2], (B, H, P))
    bm, cm = (jax.random.normal(kk, (B, G, N)) for kk in k[3:])
    want_y, want_s = ssm_state_update_reference(state, seats, decay, dtx,
                                                bm, cm)
    got_y, got_s = ssm_state_update(state + 0.0, seats, decay, dtx, bm, cm,
                                    interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    untouched = np.setdiff1d(np.arange(B + 4), np.asarray(seats))
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(state)[untouched])


# --------------------------------------------------------------------------
# no equation dropped: every multiplier and every term moves the logits
# --------------------------------------------------------------------------

def _scaled(tree, path, factor):
    """``tree`` with the leaf at ``path`` times ``factor`` in layer 0."""
    out = jax.tree.map(lambda x: x, tree)
    node = out["layers"][0]
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = node[path[-1]] * factor
    return out


MULTIPLIERS = (
    [(name, None) for name in (
        "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "ssm_in_multiplier", "ssm_out_multiplier")]
    + [("mlp_multipliers", i) for i in range(2)]
    + [("ssm_multipliers", i) for i in range(5)])
TERMS = {
    "D": ("ssm", "D"),
    "dt_bias": ("ssm", "dt_bias"),
    "conv_bias": ("ssm", "conv", "bias"),
    "A_log": ("ssm", "A_log"),
}


@pytest.mark.parametrize("what", [f"{n}[{i}]" if i is not None else n
                                  for n, i in MULTIPLIERS]
                         + sorted(TERMS) + ["gate"])
def test_every_term_of_the_layer_is_live(cfg, params, what):
    """Each of the fixed multipliers, and each of ``D``, ``dt_bias``, the
    convolution's bias, ``A`` and the gate: changed on BOTH sides, the
    served trunk and the reference still agree (the term is implemented,
    and in the same place); changed on one side only, they part by far
    more than the tolerance (it is not a no-op under these weights, so
    leaving it out could not pass)."""
    tokens = np.asarray(prompts_of(21, seed=3), np.int32)
    rows = [(0, t) for t in range(21)]
    base = np.asarray(transformer.forward(params, cfg, jnp.asarray(tokens)))[0]
    cfg2, params2 = cfg, params
    if what == "gate":
        # the gate is silu(z): scale the z columns of the input projection
        d = cfg.mamba_d_ssm
        kern = params["layers"][0]["ssm"]["in_proj"]["kernel"]
        params2 = _scaled(params, ("ssm", "in_proj", "kernel"), 1.0)
        params2["layers"][0]["ssm"]["in_proj"]["kernel"] = \
            kern.at[:, :d].multiply(1.5)
    elif what in TERMS:
        params2 = _scaled(params, TERMS[what], 1.5)
    else:
        name, _, idx = what.partition("[")
        value = getattr(cfg, name)
        if idx:
            i = int(idx[:-1])
            value = value[:i] + (value[i] * 1.5,) + value[i + 1:]
        else:
            value = value * 1.5
        cfg2 = dataclasses.replace(cfg, **{name: value})
    moved = np.asarray(transformer.forward(params2, cfg2,
                                           jnp.asarray(tokens)))[0]
    want = np.asarray(ref.logits_at(params2, cfg2, tokens, rows))
    np.testing.assert_allclose(moved, want, atol=ATOL)
    assert np.abs(moved - base).max() > 1e-2, what


# --------------------------------------------------------------------------
# through the engine (what it shares word for word with Olmo-Hybrid, the
# other family with a seat pool: tests/test_seat_pool.py)
# --------------------------------------------------------------------------

def test_a_preempted_sequence_serves_the_same_tokens():
    """A cache too small for four growing sequences pre-empts; the victim
    re-prefills prompt plus generated tokens from a zeroed seat (nothing
    snapshots its state) and the tokens are those of a roomy engine."""
    prompts = prompts_of(10, 12, 9, 11, seed=4)
    roomy = serve(engine_for(FAMILY, multi_step=1), prompts, max_tokens=24)
    tight = engine_for(FAMILY, multi_step=1, cache={"num_blocks": 26})
    assert serve(tight, prompts, max_tokens=24) == roomy
    assert tight.stats.preemptions > 0
    assert tight.stats.ssm_rebuilt_tokens > 0
    assert tight.stats.ssm_state_resets == 4 + tight.stats.preemptions
    assert tight.block_manager.seats.in_use == 0


def test_what_the_engine_observes_of_recurrent_state(caplog):
    """No option: with recurrent state the prefix cache, the KV tier and
    mixed batching are off, each with its logged sentence, and the pool is
    accounted beside the KV cache, not inside it."""
    import logging
    with caplog.at_level(logging.INFO, logger="tpuserve.engine"):
        engine = engine_for(FAMILY, enable_prefix_caching=True,
                            kv_tiers=True,
                            scheduler={"mixed_batching": True})
    assert not engine.block_manager.enable_prefix_caching
    assert engine._kv_tiers is None
    assert not engine.scheduler.cfg.mixed_batching
    said = caplog.text
    assert "prefix caching and the KV tier are off" in said
    assert "mixed ragged batching is off" in said
    cfg = engine.model_cfg
    want = ssm_state_bytes(cfg, 4)
    assert want == cfg.num_layers * 5 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert sum(x.nbytes for x in jax.tree.leaves(engine.ssm_state)) == want
    hbm = engine.devprof.hbm_snapshot()
    assert hbm["state_bytes"] == want
    assert hbm["kv_reserved_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(engine.kv_cache))
    assert all(set(layer) == {"k", "v"} for layer in engine.kv_cache)
    # /debug/engine and the dump bundles show the pool
    assert engine.flight.engine_snapshot()["devprof"]["hbm"][
        "state_bytes"] == want
    assert engine.flight.dump_bundle("test")["engine"][
        "ssm_state_seats"] == 4


def test_the_auto_sizer_subtracts_the_state_pool(monkeypatch):
    from tpuserve.models.weights import param_nbytes
    from tpuserve.runtime.kv_cache import bytes_per_block
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(4 << 20))
    engine = engine_for(FAMILY, cache={"num_blocks": 0},
                        scheduler={"max_num_seqs": 64})
    cfg, cc = engine.model_cfg, engine.cache_cfg
    budget = int((4 << 20) * 0.9) - param_nbytes(engine.params) \
        - ssm_state_bytes(cfg, 64)
    assert cc.num_blocks == budget // bytes_per_block(cfg, cc)


def test_swap_model_rebuilds_the_pool():
    """To a model without recurrent state and back: the pool goes and
    comes with the model, seats and all."""
    engine = engine_for(FAMILY, multi_step=1)
    prompt = prompts_of(9, seed=5)
    before = serve(engine, prompt)
    cache = engine.config.cache
    engine.swap_model(dataclasses.replace(engine.config, model="tiny-llama",
                                          cache=cache))
    assert engine.ssm_state is None
    assert engine.block_manager.seats is None
    serve(engine, prompt)
    engine.swap_model(dataclasses.replace(engine.config, model=MODEL))
    assert len(engine.ssm_state) == engine.model_cfg.num_layers
    assert engine.block_manager.seats.num_seats == 4
    assert serve(engine, prompt) == before


# --------------------------------------------------------------------------
# the configuration and the reference's family check
# --------------------------------------------------------------------------

def test_config_json_maps_onto_the_registered_model():
    """The published config.json (the benchmark's configuration file holds
    every key of it) through ``config_from_hf_json`` is the registered
    model, depth aside."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        hf = json.load(f)
    got = config_from_hf_json("x", hf)
    want = get_model_config("tiiuae/Falcon-H1-34B-Instruct")
    skip = {"name", "num_layers", "bos_token_id", "eos_token_id"}
    for field in dataclasses.fields(want):
        if field.name not in skip:
            assert getattr(got, field.name) == getattr(want, field.name), \
                field.name
    assert got.num_layers == 6 and want.num_layers == 72
    assert want.num_params == pytest.approx(33.6e9, rel=0.01)
    for bad in ({"attn_layer_indices": [0, 2]}, {"mamba_use_mlp": False},
                {"rope_scaling": {"type": "linear", "factor": 2}}):
        with pytest.raises(ValueError):
            config_from_hf_json("x", {**hf, **bad})


def test_an_hf_checkpoint_loads_into_the_same_forward(cfg, params):
    """HF ``modeling_falcon_h1`` tensor names through the loader give the
    tree ``init_params`` builds: same logits."""
    from tpuserve.models.weights import _load_llama_family
    raw = {"model.embed_tokens.weight": params["embed"]["weight"],
           "model.final_layernorm.weight": params["final_norm"]["scale"],
           "lm_head.weight": params["lm_head"]["kernel"].T}
    for i, lp in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        raw[pre + "input_layernorm.weight"] = lp["attn_norm"]["scale"]
        raw[pre + "pre_ff_layernorm.weight"] = lp["mlp_norm"]["scale"]
        for p in ("q", "k", "v", "o"):
            raw[pre + f"self_attn.{p}_proj.weight"] = \
                lp[f"{p}_proj"]["kernel"].T
        for p in ("gate", "up", "down"):
            raw[pre + f"feed_forward.{p}_proj.weight"] = \
                lp[f"{p}_proj"]["kernel"].T
        sp = lp["ssm"]
        raw[pre + "mamba.in_proj.weight"] = sp["in_proj"]["kernel"].T
        raw[pre + "mamba.out_proj.weight"] = sp["out_proj"]["kernel"].T
        raw[pre + "mamba.conv1d.weight"] = sp["conv"]["kernel"].T[:, None, :]
        raw[pre + "mamba.conv1d.bias"] = sp["conv"]["bias"]
        raw[pre + "mamba.norm.weight"] = sp["norm"]["scale"]
        for name in ("A_log", "dt_bias", "D"):
            raw[pre + "mamba." + name] = sp[name]
    loaded = _load_llama_family(cfg, raw, jnp.float32)
    tokens = jnp.asarray(prompts_of(17, seed=6), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(transformer.forward(loaded, cfg, tokens)),
        np.asarray(transformer.forward(params, cfg, tokens)))


def test_each_family_is_kept_from_the_other_reference(cfg):
    """``falcon_h1.check_family`` refuses every dense model.  The other way
    round it is the harness that refuses: ``dense_gqa.check_family`` reads
    none of the fields this family adds (the file is the accepted
    benchmark's and is not this PR's to edit), but a Falcon-H1
    configuration that named it would leave every mixer size and every
    multiplier checked against nothing, which ``plan.lint`` reports."""
    ref.check_family(cfg)
    ref.check_family(get_model_config("tiiuae/Falcon-H1-34B-Instruct"))
    for name in ("tiny-qwen3", "tiny-mistral", "tiny-llama"):
        with pytest.raises(ValueError, match="not the Falcon-H1 family"):
            ref.check_family(get_model_config(name))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        config = json.load(f)
    assert plan.unchecked_keys(config, ref) == []
    loose = plan.unchecked_keys(
        config, plan.load_reference({"reference": "dense_gqa"}))
    assert {"mamba_d_state", "ssm_multipliers", "lm_head_multiplier"} \
        <= set(loose)
    # and the file describes what runs: lists compare equal to lists
    model_cfg = dataclasses.replace(
        get_model_config(config["model"]),
        **plan.architecture_overrides(config))
    assert plan.architecture_mismatches(config, model_cfg, ref) == []
    wrong = {**config, "ssm_multipliers": [1, 1, 1, 1, 1]}
    assert plan.architecture_mismatches(wrong, model_cfg, ref) == [
        f"ssm_multipliers: file [1, 1, 1, 1, 1], runs "
        f"{model_cfg.ssm_multiplier_list!r}"]

