"""Mesh/TP sharding tests on the 8-virtual-device CPU mesh (SURVEY.md §4:
the multi-chip "fake backend" the reference never had)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.models import transformer, weights
from tpuserve.models.config import get_model_config
from tpuserve.ops.attention import PAD_SLOT
from tpuserve.parallel import (MeshConfig, cache_shardings, make_mesh,
                               param_shardings, shard_params)
from tpuserve.parallel.mesh import AXIS_TP
from tpuserve.runtime.kv_cache import CacheConfig, create_kv_cache


@pytest.fixture(scope="module")
def tp4_mesh():
    return make_mesh(MeshConfig(dp=2, tp=4))


@pytest.fixture(scope="module")
def cfg():
    # head/vocab dims divisible by tp=4
    return dataclasses.replace(get_model_config("tiny-qwen3"),
                               num_heads=8, num_kv_heads=4, dtype="float32")


def test_mesh_shapes(tp4_mesh):
    assert tp4_mesh.shape == {"dp": 2, "ep": 1, "pp": 1, "tp": 4}


def test_mesh_too_large():
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(dp=4, tp=4))


def test_param_shardings_rules(cfg, tp4_mesh):
    params = weights.init_params(cfg)
    sh = param_shardings(params, cfg, tp4_mesh)
    lp = sh["layers"][0]
    assert lp["q_proj"]["kernel"].spec == jax.sharding.PartitionSpec(None, AXIS_TP)
    assert lp["o_proj"]["kernel"].spec == jax.sharding.PartitionSpec(AXIS_TP, None)
    assert lp["down_proj"]["kernel"].spec == jax.sharding.PartitionSpec(AXIS_TP, None)
    assert sh["embed"]["weight"].spec == jax.sharding.PartitionSpec(AXIS_TP, None)
    assert sh["final_norm"]["scale"].spec == jax.sharding.PartitionSpec()


def test_tp_decode_matches_single_device(cfg, tp4_mesh):
    """The sharded decode step must equal the unsharded one (GSPMD only
    changes layout, not math)."""
    params = weights.init_params(cfg)
    cache_cfg = CacheConfig(block_size=4, num_blocks=16, max_blocks_per_seq=4)

    def run(params_in, cache_in):
        tokens = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        lens = jnp.asarray([4, 3], jnp.int32)
        slots = np.full((2, 4), PAD_SLOT, np.int32)
        for b in range(2):
            for t in range(int(lens[b])):
                slots[b, t] = (2 * b) * 4 + t
        logits_p, cache_in = transformer.prefill(
            params_in, cfg, tokens, lens, jnp.asarray(slots), cache_in)
        bt = jnp.asarray([[0, 1, 0, 0], [2, 3, 0, 0]], jnp.int32)
        logits_d, cache_in = transformer.decode_step(
            params_in, cfg, jnp.asarray([9, 9], jnp.int32),
            jnp.asarray([4, 3], jnp.int32),
            jnp.asarray([1 * 4, 2 * 4 + 3], jnp.int32), bt,
            jnp.asarray([5, 4], jnp.int32), cache_in)
        return np.asarray(logits_p), np.asarray(logits_d)

    ref_p, ref_d = run(params, create_kv_cache(cfg, cache_cfg))
    sharded_params = shard_params(params, cfg, tp4_mesh)
    sharded_cache = jax.device_put(create_kv_cache(cfg, cache_cfg),
                                   cache_shardings(cfg, tp4_mesh))
    tp_p, tp_d = run(sharded_params, sharded_cache)
    np.testing.assert_allclose(tp_p, ref_p, atol=2e-4)
    np.testing.assert_allclose(tp_d, ref_d, atol=2e-4)


def test_tp_pallas_matches_reference(cfg, tp4_mesh):
    """Pallas attention under tp=4 (head-parallel shard_map, interpret mode
    on CPU) must match the einsum reference path — round 1 silently
    downgraded to reference attention under tp>1 (VERDICT r1 #4)."""
    params = shard_params(weights.init_params(cfg), cfg, tp4_mesh)
    # float32 cache: with bf16 the pallas and einsum paths round differently
    # (~5e-3), which would mask a real partitioning bug
    cache_cfg = CacheConfig(block_size=4, num_blocks=16, max_blocks_per_seq=4,
                            dtype="float32")

    def run(attn_impl, mesh):
        cache = jax.device_put(create_kv_cache(cfg, cache_cfg),
                               cache_shardings(cfg, tp4_mesh))
        tokens = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        lens = jnp.asarray([4, 3], jnp.int32)
        slots = np.full((2, 4), PAD_SLOT, np.int32)
        for b in range(2):
            for t in range(int(lens[b])):
                slots[b, t] = (2 * b) * 4 + t
        logits_p, cache = transformer.prefill(
            params, cfg, tokens, lens, jnp.asarray(slots), cache,
            attn_impl=attn_impl, mesh=mesh)
        bt = jnp.asarray([[0, 1, 0, 0], [2, 3, 0, 0]], jnp.int32)
        logits_d, cache = transformer.decode_step(
            params, cfg, jnp.asarray([9, 9], jnp.int32),
            jnp.asarray([4, 3], jnp.int32),
            jnp.asarray([1 * 4, 2 * 4 + 3], jnp.int32), bt,
            jnp.asarray([5, 4], jnp.int32), cache,
            attn_impl=attn_impl, mesh=mesh)
        return np.asarray(logits_p), np.asarray(logits_d)

    ref_p, ref_d = run("reference", None)
    tp_p, tp_d = run("pallas", tp4_mesh)
    np.testing.assert_allclose(tp_p, ref_p, atol=2e-4)
    np.testing.assert_allclose(tp_d, ref_d, atol=2e-4)


def test_engine_tp_pallas_no_downgrade(cfg, tp4_mesh):
    """With kv_heads % tp == 0 the engine keeps attn_impl=pallas under TP
    (the round-1 downgrade warning is gone) and generates correctly."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)
    eng_cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8),
        scheduler=SchedulerConfig(min_prefill_bucket=8, min_decode_bucket=2),
        attn_impl="pallas")
    mesh = make_mesh(MeshConfig(dp=1, tp=2))
    eng = Engine(eng_cfg, model_cfg=cfg, mesh=mesh)
    assert eng.attn_impl == "pallas"
    assert eng._attn_mesh is mesh
    plain = Engine(eng_cfg, model_cfg=cfg)
    p = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    a = plain.generate(["hello"], p)[0]
    b = eng.generate(["hello"], p)[0]
    assert a.output_token_ids == b.output_token_ids


def test_engine_with_mesh(cfg, tp4_mesh):
    """Engine end-to-end with TP sharded params/cache."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)
    eng_cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8),
        scheduler=SchedulerConfig(min_prefill_bucket=8, min_decode_bucket=2))
    plain = Engine(eng_cfg)
    meshy = Engine(eng_cfg, mesh=make_mesh(MeshConfig(dp=1, tp=2)))
    p = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    a = plain.generate(["hello"], p)[0]
    b = meshy.generate(["hello"], p)[0]
    assert a.output_token_ids == b.output_token_ids


def test_train_step_sharded(cfg, tp4_mesh):
    from tpuserve.parallel.train import (TrainConfig, causal_lm_loss,
                                         init_train_state, train_step)
    params = shard_params(weights.init_params(cfg), cfg, tp4_mesh)
    tcfg = TrainConfig(learning_rate=1e-3, remat=True)
    optimizer, opt_state = init_train_state(params, tcfg)
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch_sh = NamedSharding(tp4_mesh, P("dp", None))
    tokens = jax.device_put(
        jnp.asarray(np.random.default_rng(0).integers(1, 100, (4, 8)), jnp.int32),
        batch_sh)
    mask = jax.device_put(jnp.ones((4, 8), bool), batch_sh)
    loss0 = causal_lm_loss(params, cfg, tokens, mask)
    params, opt_state, loss = train_step(params, opt_state, cfg, tcfg,
                                         optimizer, tokens, mask)
    loss1 = causal_lm_loss(params, cfg, tokens, mask)
    assert float(loss1) < float(loss0)          # one step reduces train loss
    # params keep their TP shardings through the update
    assert params["layers"][0]["q_proj"]["kernel"].sharding.spec == \
        jax.sharding.PartitionSpec(None, AXIS_TP)


def test_tp_pallas_window_matches_reference(cfg, tp4_mesh):
    """The paged window (chunked-prefill) kernel under tp=4 head-parallel
    shard_map must match the segmented einsum reference path."""
    params = shard_params(weights.init_params(cfg), cfg, tp4_mesh)
    cache_cfg = CacheConfig(block_size=4, num_blocks=16, max_blocks_per_seq=4,
                            dtype="float32")

    def run(attn_impl, mesh):
        cache = jax.device_put(create_kv_cache(cfg, cache_cfg),
                               cache_shardings(cfg, tp4_mesh))
        # first chunk: 4 tokens of sequence 0 at ctx 0
        tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        slots = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        bt = jnp.asarray([[0, 1, 0, 0]], jnp.int32)
        logits1, cache = transformer.prefill_chunk(
            params, cfg, tokens, jnp.asarray([0], jnp.int32),
            jnp.asarray([4], jnp.int32), slots, bt, cache,
            attn_impl=attn_impl, mesh=mesh)
        # second chunk: 3 more tokens against the cached context
        tokens = jnp.asarray([[5, 6, 7, 0]], jnp.int32)
        slots = jnp.asarray([[4, 5, 6, PAD_SLOT]], jnp.int32)
        logits2, cache = transformer.prefill_chunk(
            params, cfg, tokens, jnp.asarray([4], jnp.int32),
            jnp.asarray([3], jnp.int32), slots, bt, cache,
            attn_impl=attn_impl, mesh=mesh)
        return np.asarray(logits1), np.asarray(logits2)

    ref1, ref2 = run("reference", None)
    tp1, tp2 = run("pallas", tp4_mesh)
    np.testing.assert_allclose(tp1, ref1, atol=2e-4)
    np.testing.assert_allclose(tp2, ref2, atol=2e-4)


# ---------------------------------------------------------------------------
# No silent fallback: a caller that names attn_impl="pallas" gets Pallas or
# an error, never a quiet substitute; parameters are born sharded.
# ---------------------------------------------------------------------------

def _pallas_engine(cfg, mesh, tpu=False, **kw):
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SchedulerConfig)
    return Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8,
                          dtype=kw.pop("kv_dtype", "bfloat16")),
        scheduler=SchedulerConfig(min_prefill_bucket=8, min_decode_bucket=2,
                                  **kw.pop("scheduler", {})),
        attn_impl=kw.pop("attn_impl", "pallas"), multi_step=1,
        pipeline_decode=False), model_cfg=cfg, mesh=mesh)


@pytest.mark.parametrize("case", ["pp", "ragged_under_tp",
                                  "narrow_kv_rows_on_tpu"])
def test_explicit_pallas_downgrade_raises(case, cfg, monkeypatch, caplog):
    """Each place the engine used to drop Pallas for reference with a log
    line only: asked for by name it is an error; under "auto" (resolving
    to pallas, as on a TPU) the engine serves on reference and warns."""
    import dataclasses
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # ... a TPU whose memory_stats() the CPU backend cannot stand in for
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(1 << 30))
    kw = {}
    if case == "pp":
        mesh = make_mesh(MeshConfig(pp=2))
        cfg = dataclasses.replace(cfg, num_layers=4)
        kw["scheduler"] = dict(allow_chunked_prefill=False)
    elif case == "ragged_under_tp":
        mesh = make_mesh(MeshConfig(dp=1, tp=2))
        kw["scheduler"] = dict(mixed_batching=True, mixed_token_budget=512)
    else:
        mesh = None                 # int8 pages of 2 kv heads: 2 bytes a row
        cfg = dataclasses.replace(cfg, num_kv_heads=2)
        kw["kv_dtype"] = "int8"
    attr = "_ragged_attn" if case == "ragged_under_tp" else "attn_impl"
    with pytest.raises(ValueError, match="attn_impl='pallas' was requested"):
        _pallas_engine(cfg, mesh, **{k: (dict(v) if isinstance(v, dict) else v)
                                     for k, v in kw.items()})
    # (kv heads that do not divide tp have no downgrade to test: the
    # kv-head-sharded cache cannot be created for them at all)
    with caplog.at_level("WARNING", "tpuserve.engine"):
        eng = _pallas_engine(cfg, mesh, attn_impl="auto", **kw)
    assert getattr(eng, attr) == "reference"
    assert any("using reference attention" in r.message
               for r in caplog.records)


def test_init_params_is_born_sharded(cfg):
    """Random init under a mesh never lands whole on one device — a model
    that needs tp to fit could not pass through device 0 — and its values
    do not depend on the placement."""
    from tpuserve.models.weights import init_params, param_nbytes
    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    sharded = init_params(cfg, seed=3, mesh=mesh)
    plain = init_params(cfg, seed=3)
    total = param_nbytes(plain)
    held = {}
    for leaf in jax.tree.leaves(sharded):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    assert len(held) == 4
    assert max(held.values()) < 0.3 * total      # a quarter, plus norms
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_under_mesh_never_holds_params_whole(cfg, monkeypatch):
    """The engine hands its mesh to the initialiser: what init_params
    returns is already in the tensor-parallel shards."""
    from tpuserve.models import weights
    seen = {}
    real = weights.init_params

    def spy(model_cfg, seed=0, mesh=None):
        out = real(model_cfg, seed, mesh)
        seen["mesh"] = mesh
        seen["whole"] = [leaf.shape for leaf in jax.tree.leaves(out)
                         if leaf.ndim == 2 and leaf.sharding.is_fully_replicated]
        return out

    monkeypatch.setattr(weights, "init_params", spy)
    mesh = make_mesh(MeshConfig(dp=1, tp=2))
    _pallas_engine(cfg, mesh, attn_impl="reference")
    assert seen["mesh"] is mesh
    assert seen["whole"] == []      # every matrix is sharded at birth
