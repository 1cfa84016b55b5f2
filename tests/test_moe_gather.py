"""An expert layer's rows are moved in the form that is faster on the chip
at their shapes (``transformer._gather_rows``: the plain row gather, or the
gather of ``(tiles, 128)`` slices that was the only form before PR 42).
Rows are moved, never computed: whatever the form, the layer's output is
the same bits, and the engine counts from shapes alone how many moves took
the plain form."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_scopes import trunk_programs
from tpuserve.models import transformer, weights
from tpuserve.models.config import get_model_config
from tpuserve.ops import scopes
from tpuserve.ops.pallas_moe_gmm import DECODE_ROWS

REPEATS = transformer._REPEATS_PLAIN_ROWS


def sliced(x, idx):
    """The one form the trunk had before PR 42 (its scope with it)."""
    width = x.shape[-1]
    with jax.named_scope(scopes.MOE_GATHER):
        if width % 128:
            return x[idx]
        return x.reshape(x.shape[0], width // 128, 128)[idx].reshape(
            idx.shape[0], width)


def bits(a):
    a = jnp.asarray(a)
    return np.asarray(jax.lax.bitcast_convert_type(
        a, {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]))


def _order(tokens: int, k: int, experts: int, seed: int):
    picks = np.random.default_rng(seed).integers(0, experts, tokens * k)
    return np.argsort(picks, kind="stable").astype(np.int32)


# (case, source rows, width, dtype, indices, the form expected)
def _case(name):
    k = 8
    if name.startswith("repeats"):
        tokens, width = {"repeats-decode": (64, 2304),
                         "repeats-decode-edge": (DECODE_ROWS // k, 2304),
                         "repeats-prefill": (256, 6144),
                         "repeats-refused-rung": (1536, 256),
                         "repeats-under-plain": (REPEATS // k - 128, 256),
                         "repeats-at-plain": (REPEATS // k, 256)}[name]
        idx = _order(tokens, k, 64, 1) // k
        return tokens, width, jnp.bfloat16, idx, \
            "plain" if idx.shape[0] >= REPEATS else "sliced"
    if name.startswith("permutation"):
        rows = {"permutation-decode-edge": DECODE_ROWS,
                "permutation-above": DECODE_ROWS + 256,
                "permutation-float32": DECODE_ROWS + 256}[name]
        order = _order(rows // k, k, 64, 2)
        back = np.zeros_like(order)
        back[order] = np.arange(rows, dtype=np.int32)
        return rows, 2304, (jnp.float32 if name.endswith("float32")
                            else jnp.bfloat16), back, \
            "plain" if rows > DECODE_ROWS else "sliced"
    if name == "padded-tail":
        # a piece of a share's sorted picks, padded with zeros past them
        order = np.pad(_order(1024, k, 128, 3), (0, 256))[:1280]
        return 1024, 6144, jnp.bfloat16, order // k, "sliced"
    if name == "width-no-whole-tiles":
        return 300, 200, jnp.bfloat16, _order(300, k, 16, 4) // k, "plain"
    raise KeyError(name)


CASES = ["repeats-decode", "repeats-decode-edge", "repeats-prefill",
         "repeats-refused-rung", "repeats-under-plain", "repeats-at-plain",
         "permutation-decode-edge", "permutation-above",
         "permutation-float32", "padded-tail", "width-no-whole-tiles"]


@pytest.mark.parametrize("name", CASES)
def test_gathered_rows_are_the_indexed_rows_in_the_form_for_their_shape(name):
    source_rows, width, dtype, idx, form = _case(name)
    x = jnp.asarray(np.random.default_rng(7).standard_normal(
        (source_rows, width)), dtype)
    idx = jnp.asarray(idx)
    got = transformer._gather_rows(x, idx)
    assert got.shape == (idx.shape[0], width) and got.dtype == x.dtype
    np.testing.assert_array_equal(bits(got), bits(x[idx]))
    np.testing.assert_array_equal(bits(got), bits(sliced(x, idx)))
    # the form: what the gather's operand looks like in the traced program
    eqns = jax.make_jaxpr(transformer._gather_rows)(x, idx).jaxpr.eqns
    gathers = [e for e in eqns if e.primitive.name == "gather"] + [
        e for sub in eqns if sub.primitive.name == "pjit"
        for e in sub.params["jaxpr"].jaxpr.eqns
        if e.primitive.name == "gather"]
    ranks = {len(e.invars[0].aval.shape) for e in gathers}
    assert ranks == ({2} if form == "plain" else {3}), (name, ranks)
    assert transformer._gathers_plain(idx.shape[0], source_rows, width) \
        == (form == "plain")


def _layer(model: str, layer: int, *, int8: bool = False, share: int = 0):
    """``(cfg, one expert layer's params)`` of a preset widened to a hidden
    size of whole 128-lane tiles (at the presets' 64 both forms are
    ``x[idx]``), in bfloat16."""
    cfg = dataclasses.replace(get_model_config(model), hidden_size=128,
                              dtype="bfloat16")
    params = weights.init_params(cfg, seed=11)
    if int8:
        params = weights.quantize_params_int8(params)
    lp = params["layers"][layer]
    if share:
        cfg = dataclasses.replace(cfg, moe_experts_held=share)
        lp = dict(lp, experts={n: {key: a[:share] for key, a in p.items()}
                               for n, p in lp["experts"].items()})
    return cfg, lp


LAYERS = {
    "tiny-moe": lambda: _layer("tiny-moe", 0),
    "tiny-mellum2": lambda: _layer("tiny-mellum2", 0),
    "shared-expert-int8-scale": lambda: _layer("tiny-deepseek", 1, int8=True),
    "tiny-k-exaone-share": lambda: _layer("tiny-k-exaone", 1, share=8),
}


@pytest.mark.parametrize("tokens", [300, 700, REPEATS // 2])
@pytest.mark.parametrize("model", sorted(LAYERS))
def test_the_layer_is_its_pre_change_form_bit_for_bit(model, tokens,
                                                      monkeypatch):
    """``_moe_mlp`` with the rows moved as ``_gather_rows`` now chooses
    against the same layer with every move in the sliced form, at token
    counts on both sides of both thresholds (two picks a token: 600 and
    1,400 rows around ``DECODE_ROWS``, and ``_REPEATS_PLAIN_ROWS`` rows)."""
    cfg, lp = LAYERS[model]()
    x = jnp.asarray(np.random.default_rng(tokens).standard_normal(
        (tokens, cfg.hidden_size)), jnp.bfloat16)
    into, back = transformer.moe_plain_moves(cfg, tokens)
    pairs = tokens * cfg.num_experts_per_tok
    if not cfg.moe_experts_held:
        assert (into, back) == (pairs >= REPEATS, pairs > DECODE_ROWS)
    else:
        assert not back
    tally = []
    got = transformer._moe_mlp(x, lp, cfg, tally)
    monkeypatch.setattr(transformer, "_gather_rows", sliced)
    was_tally = []
    was = transformer._moe_mlp(x, lp, cfg, was_tally)
    np.testing.assert_array_equal(bits(got), bits(was))
    for a, b in zip(jax.tree.leaves(tally), jax.tree.leaves(was_tally)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_engine_counts_the_plain_moves_of_each_dispatch():
    """A prefill whose add-back permutes more than ``DECODE_ROWS`` rows
    and decode windows that do not: the step records, the engine's total
    and ``/metrics`` carry the moves that went plain, from shapes alone."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams)
    from tpuserve.server.metrics import ServerMetrics

    cfg = dataclasses.replace(get_model_config("tiny-mellum2"),
                              hidden_size=128, max_position_embeddings=1024)
    eng = Engine(EngineConfig(
        model="tiny-mellum2",
        cache=CacheConfig(block_size=4, num_blocks=256,
                          max_blocks_per_seq=192)),
        params=weights.init_params(cfg, seed=5), model_cfg=cfg)
    k, layers = cfg.num_experts_per_tok, cfg.num_layers
    assert transformer.moe_plain_moves(cfg, 4) == (False, False)
    eng.generate([[5 + i % 200 for i in range(600)]],
                 SamplingParams(max_tokens=6, temperature=0.0))
    steps = [s for s in eng.flight.steps_snapshot() if s.get("moe_rows")]
    prefills = [s for s in steps if s["moe_moves_plain"]]
    assert prefills and len(steps) > len(prefills)
    for s in prefills:
        # the add-back of 600 tokens x 2 picks (padded to a bucket) is a
        # permutation of more than DECODE_ROWS rows; going into expert
        # order repeats rows and stays under _REPEATS_PLAIN_ROWS
        assert s["moe_rows"] // layers > DECODE_ROWS
        assert s["moe_moves_plain"] == s["moe_rows"]
    for s in steps:
        assert s["moe_rows"] % (k * layers) == 0
    assert eng.stats.moe_row_moves_plain \
        == sum(s["moe_rows"] for s in prefills) \
        < eng.stats.moe_routed_rows == sum(s["moe_rows"] for s in steps)
    assert any(m.name == "tpuserve_moe_row_moves_plain"
               for m in ServerMetrics("m").registry.collect())


@pytest.mark.parametrize("tokens,want", [
    (64, (False, False)), (128, (False, False)), (256, (False, True)),
    (1536, (False, True)), (2048, (True, True)), (8192, (True, True))])
def test_the_benchmarks_expert_model_moves_plain_from_these_rungs(tokens,
                                                                  want):
    cfg = get_model_config("JetBrains/Mellum2-12B-A2.5B-Instruct")
    assert transformer.moe_plain_moves(cfg, tokens) == want
    # K-EXAONE's share gathers a piece of at most 8,448 rows of 6,144 and
    # adds back by scatter: every program of its cell is what it was
    held = dataclasses.replace(
        get_model_config("LGAI-EXAONE/K-EXAONE-236B-A23B"),
        moe_experts_held=16)
    assert transformer.moe_plain_moves(held, tokens) == (False, False)


# K-EXAONE's 17 packed-prefill rungs (64-row ragged blocks) and its decode
# buckets
@pytest.mark.parametrize("tokens", [
    8, 64, 128, 256, 384, 512, 640, 768, 896, 1024, 1536, 2048, 3072, 4096,
    5120, 6144, 7168, 8192])
def test_a_share_of_k_exaone_moves_its_rows_as_it_did(tokens):
    held = dataclasses.replace(
        get_model_config("LGAI-EXAONE/K-EXAONE-236B-A23B"),
        moe_experts_held=16)
    assert transformer.moe_plain_moves(held, tokens) == (False, False)


def _wide(model, **more):
    return dataclasses.replace(get_model_config(model), hidden_size=128,
                               **more)


UNCHANGED = {
    # no expert layer: the traffic never reaches the code
    "tiny-qwen3": lambda: get_model_config("tiny-qwen3"),
    "tiny-llama": lambda: get_model_config("tiny-llama"),
    "tiny-falcon-h1": lambda: get_model_config("tiny-falcon-h1"),
    # expert layers at a width of whole tiles, at shapes under both
    # thresholds (a decode window, a short prefill; a share's piece)
    "tiny-mellum2-128": lambda: _wide("tiny-mellum2"),
    "tiny-k-exaone-128-share": lambda: _wide("tiny-k-exaone",
                                             moe_experts_held=8),
}


@pytest.mark.parametrize("program", ["decode_multi", "forward_ragged",
                                     "prefill_chunk"])
@pytest.mark.parametrize("model", sorted(UNCHANGED))
def test_these_trunks_lower_to_the_parents_text(model, program, monkeypatch):
    """With its debug info (an operation's ``op_name`` is part of the
    compile cache's key): a trunk without expert layers, and one whose
    expert layers move fewer rows than either threshold, is the program it
    was when every move was sliced."""
    cfg = UNCHANGED[model]()
    # as the server runs (utils/compile_cache.py): each operation's name
    # in its location, no Python frame
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)

    def reset():
        jax.config.update("jax_traceback_in_locations_limit", limit)

    def text():
        jax.clear_caches()
        fn, args, kwargs = trunk_programs(cfg)[program]
        return fn.lower(*args, **kwargs).as_text(debug_info=True)

    try:
        now = text()
        monkeypatch.setattr(transformer, "_gather_rows", sliced)
        assert text() == now
        if cfg.routes_experts and cfg.hidden_size % 128 == 0:
            # (the comparison can fail: every move plain is another
            # program)
            monkeypatch.setattr(transformer, "_gather_rows",
                                lambda x, i: x[i])
            assert text() != now
    finally:
        reset()
