"""Multi-head latent attention (DeepSeek MLA) — the latent-cache serving
path: absorbed-form decode/chunk attention vs the naive decompressed form,
the k-only 1-head cache layout and its ~10x size win, and engine
integration (greedy parity across single-step / fused windows / chunked
prefill / spec verify / disaggregation, int8 weights + int8 KV).

Numeric ground truth is transformers (tests/test_golden_checkpoint.py
deepseek_v2/v3 rows); these tests pin the SERVING machinery on top.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.models import transformer
from tpuserve.models.config import get_model_config
from tpuserve.models.weights import init_params, quantize_params_int8
from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SamplingParams, SchedulerConfig)
from tpuserve.runtime.kv_cache import bytes_per_block, create_kv_cache


def _cfg(**kw):
    return dataclasses.replace(get_model_config("tiny-deepseek"),
                               dtype="float32", **kw)


# --------------------------------------------------------- cache layout

def test_latent_cache_is_k_only_one_head():
    cfg = _cfg()
    cache = create_kv_cache(cfg, CacheConfig(block_size=4, num_blocks=8,
                                             max_blocks_per_seq=4))
    assert set(cache[0]) == {"k"}
    assert cache[0]["k"].shape == (8, 4, 1, cfg.mla_latent_dim)
    q = create_kv_cache(cfg, CacheConfig(block_size=4, num_blocks=8,
                                         max_blocks_per_seq=4, dtype="int8"))
    assert set(q[0]) == {"k", "ks"}


def test_mla_block_bytes_reflect_compression():
    """The whole point: per-block bytes ~10x under the equivalent dense
    layout (1 array x 1 head x latent_dim vs 2 x Hkv x head_dim)."""
    cfg = _cfg()
    cc = CacheConfig(block_size=16, num_blocks=8, max_blocks_per_seq=4)
    mla = bytes_per_block(cfg, cc)
    dense = bytes_per_block(dataclasses.replace(cfg, mla_kv_lora_rank=None),
                            cc)
    # tiny cfg: latent 48 vs 2*4*48 = 8x; real V2-Lite: 576 vs 2*16*192=10.7x
    assert dense / mla == (2 * cfg.num_kv_heads * cfg.head_dim
                           ) / cfg.mla_latent_dim
    v2l = get_model_config("deepseek-v2-lite")
    assert (2 * v2l.num_kv_heads * v2l.head_dim) / v2l.mla_latent_dim > 10


# ----------------------------------------------- absorbed == naive form

def test_absorbed_decode_matches_naive_prefill_row():
    """Prefill runs the naive decompressed attention; decode the absorbed
    latent-space form.  Decoding the (t+1)-th token must produce the same
    logits as prefilling all t+1 tokens and reading the last row — the
    equivalence q_lat . c == q_nope . k_nope is exact, so tolerance is
    float-accumulation only."""
    cfg = _cfg()
    params = init_params(cfg)
    # float32 cache: the default bf16 pages would round the stored latents
    # and mask the equivalence being tested
    cc = CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8,
                     dtype="float32")
    toks = jnp.asarray([[7, 3, 250, 99, 14, 2]], jnp.int32)

    # full prefill of 6 tokens
    cache = create_kv_cache(cfg, cc)
    slots = jnp.asarray([[0, 1, 2, 3, 4, 5]], jnp.int32)
    full_logits, *_ = transformer.prefill(
        params, cfg, toks, jnp.asarray([6], jnp.int32), slots, cache)

    # prefill 5, then absorbed decode of token 6
    cache = create_kv_cache(cfg, cc)
    logits5, cache, *_ = transformer.prefill(
        params, cfg, toks[:, :5].at[:, :].get().reshape(1, 5),
        jnp.asarray([5], jnp.int32), slots[:, :5], cache)
    bt = jnp.asarray([[0, 1, 0, 0, 0, 0, 0, 0]], jnp.int32)
    dec_logits, *_ = transformer.decode_step(
        params, cfg, toks[:, 5], jnp.asarray([5], jnp.int32),
        jnp.asarray([5], jnp.int32), bt, jnp.asarray([6], jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits), atol=2e-4, rtol=2e-4)


# --------------------------------------------------- engine integration

def _engine(mesh=None, cache=None, **kw):
    return Engine(EngineConfig(
        model="tiny-deepseek",
        cache=cache or CacheConfig(block_size=4, num_blocks=256,
                                   max_blocks_per_seq=64),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=2,
                                  max_prefill_tokens=32), **kw), mesh=mesh)


def test_engine_decode_multistep_parity():
    p = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    base = [r.output_token_ids
            for r in _engine().generate(["hello world", "abc"], p)]
    fused = [r.output_token_ids
             for r in _engine(multi_step=4).generate(["hello world", "abc"],
                                                     p)]
    assert base == fused
    assert all(len(t) == 8 for t in base)


def test_engine_chunked_prefill_parity():
    """A 100-token prompt against max_prefill_tokens=32 runs the chunked
    path (absorbed window attention vs the latent cache)."""
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    long = "x" * 100
    (chunked,) = _engine().generate([long], p)
    big = Engine(EngineConfig(
        model="tiny-deepseek",
        cache=CacheConfig(block_size=4, num_blocks=256,
                          max_blocks_per_seq=64),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=2,
                                  max_prefill_tokens=512)))
    (full,) = big.generate([long], p)
    assert chunked.output_token_ids == full.output_token_ids


def test_engine_spec_decode_parity():
    """Speculative verify rides _chunk_trunk: its MLA branch must accept
    and emit exactly the plain decode's tokens."""
    from tpuserve.runtime.spec import SpecConfig
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    (spec,) = _engine(speculative=SpecConfig(num_draft_tokens=3)).generate(
        ["abcabcabcabc"], p)
    (plain,) = _engine().generate(["abcabcabcabc"], p)
    assert spec.output_token_ids == plain.output_token_ids


def test_engine_quantized_paths_run():
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    (w8,) = _engine(quantization="int8").generate(["hello"], p)
    assert len(w8.output_token_ids) == 6
    kv8 = Engine(EngineConfig(
        model="tiny-deepseek",
        cache=CacheConfig(block_size=4, num_blocks=256,
                          max_blocks_per_seq=64, dtype="int8"),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=2,
                                  max_prefill_tokens=32)))
    (r,) = kv8.generate(["hello"], p)
    assert len(r.output_token_ids) == 6


def test_engine_prefix_cache_and_drain():
    eng = _engine(enable_prefix_caching=True)
    p = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    (a,) = eng.generate(["shared prefix tail A"], p)
    (b,) = eng.generate(["shared prefix tail A"], p)
    assert a.output_token_ids == b.output_token_ids
    assert eng.block_manager.num_seqs() == 0


def test_disagg_matches_colocated():
    """The latent pages survive extract -> wire-format -> insert (k-only
    entries; the generic key-set machinery must not assume a "v")."""
    from tpuserve.parallel.disagg import DisaggregatedEngine
    kw = dict(model="tiny-deepseek",
              cache=CacheConfig(block_size=4, num_blocks=64,
                                max_blocks_per_seq=16),
              scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                        min_decode_bucket=2))
    p = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    (d,) = DisaggregatedEngine(EngineConfig(**kw),
                               EngineConfig(**kw)).generate(["hello world"], p)
    (c,) = _engine().generate(["hello world"], p)
    assert d.output_token_ids == c.output_token_ids


def _tp_mesh():
    from tpuserve.parallel import MeshConfig, make_mesh
    return make_mesh(MeshConfig(dp=1, tp=2))


@pytest.mark.parametrize("what,kw,why", [
    ("the model's own dtype on one device", {}, None),
    ("an int8 latent cache", {"cache_dtype": "int8"}, "slice scales"),
    ("a mesh", {"mesh": _tp_mesh}, "ONE kv head"),
])
def test_pallas_request_downgrades_to_reference(monkeypatch, caplog, what,
                                                kw, why):
    """The Pallas kernels read latent pages in the model's own dtype on
    one device: asked for by name that engine runs them and serves the
    reference path's tokens.  What they still cannot do (an int8 latent
    cache, a mesh) is an error when asked for by name, each with its
    reason, while "auto" (resolving to pallas as it does on a TPU) serves
    on the reference path and says so."""
    kw = dict(kw)
    cache = CacheConfig(block_size=4, num_blocks=256, max_blocks_per_seq=64,
                        dtype=kw.pop("cache_dtype", "bfloat16"))
    if "mesh" in kw:
        if jax.device_count() < 2:
            pytest.skip("needs the 8-virtual-device conftest mesh")
        kw["mesh"] = kw["mesh"]()
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    if why is None:
        eng = _engine(attn_impl="pallas", cache=cache, **kw)
        assert eng.attn_impl == "pallas" and eng._packed_prefill
        want = _engine(attn_impl="reference").generate(["hello world"], p)
        got = eng.generate(["hello world"], p)
        assert got[0].output_token_ids == want[0].output_token_ids
        return
    with pytest.raises(ValueError, match="attn_impl='pallas' was requested"
                                         f".*{why}"):
        _engine(attn_impl="pallas", cache=cache, **kw)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(1 << 30))
    with caplog.at_level("WARNING", "tpuserve.engine"):
        eng = _engine(attn_impl="auto", multi_step=1, pipeline_decode=False,
                      cache=cache, **kw)
    assert eng.attn_impl == "reference"
    assert any("using reference attention" in r.message and why in r.message
               for r in caplog.records)


def test_int8_covers_mla_and_shared_weights():
    cfg = _cfg()
    q = quantize_params_int8(init_params(cfg))
    lp = q["layers"][1]                       # MoE layer (layer 0 dense)
    assert lp["kv_b_proj"]["kernel"].dtype == jnp.int8
    assert lp["kv_a_proj"]["kernel"].dtype == jnp.int8
    assert lp["shared"]["gate_proj"]["kernel"].dtype == jnp.int8
    # correction bias must stay f32 and unquantized
    assert lp["router_bias"]["bias"].dtype == jnp.float32
    dense = q["layers"][0]
    assert dense["gate_proj"]["kernel"].dtype == jnp.int8


# ------------------------------------------------------- tp mesh (cpu)

def test_mla_under_tp_mesh():
    if jax.device_count() < 4:
        pytest.skip("needs the 8-virtual-device conftest mesh")
    from tpuserve.ops.attention import PAD_SLOT
    from tpuserve.parallel import (MeshConfig, cache_shardings, make_mesh,
                                   shard_params)
    mesh = make_mesh(MeshConfig(dp=1, tp=4))
    cfg = _cfg()
    params = shard_params(init_params(cfg), cfg, mesh)
    cc = CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=4)
    cache = jax.device_put(create_kv_cache(cfg, cc),
                           cache_shardings(cfg, mesh))
    B, T = 2, 8
    toks = jnp.ones((B, T), jnp.int32)
    lens = jnp.full((B,), 5, jnp.int32)
    slots = np.full((B, T), PAD_SLOT, np.int32)
    for b in range(B):
        for t in range(5):
            slots[b, t] = 2 * b * cc.block_size + t
    logits, cache, *_ = transformer.prefill(params, cfg, toks, lens,
                                            jnp.asarray(slots), cache)
    bt = np.zeros((B, 4), np.int32)
    for b in range(B):
        bt[b, 0], bt[b, 1] = 2 * b, 2 * b + 1
    logits, cache, *_ = transformer.decode_step(
        params, cfg, jnp.ones((B,), jnp.int32),
        jnp.full((B,), 5, jnp.int32),
        jnp.asarray([(2 * b + 1) * cc.block_size for b in range(B)],
                    jnp.int32),
        jnp.asarray(bt), jnp.full((B,), 6, jnp.int32), cache)
    logits.block_until_ready()
    assert logits.shape == (B, cfg.vocab_size)


def test_pp_rejected_with_clear_error():
    """DeepSeek on the pipeline engine must fail loudly at startup (the
    staged trunk can't stack MLA/mixed-dense layers), mirroring the spec
    and multi-host pp guards."""
    from tpuserve.parallel import MeshConfig, make_mesh
    if jax.device_count() < 2:
        pytest.skip("needs the multi-device conftest mesh")
    mesh = make_mesh(MeshConfig(pp=2))
    with pytest.raises(ValueError, match="pipeline parallelism"):
        Engine(EngineConfig(
            model="tiny-deepseek",
            cache=CacheConfig(block_size=4, num_blocks=32,
                              max_blocks_per_seq=8),
            scheduler=SchedulerConfig(max_num_seqs=2, min_prefill_bucket=8,
                                      min_decode_bucket=2)), mesh=mesh)


def test_tp_shards_mla_projections():
    """The b-projections hold the bulk of MLA attention weights; under tp
    they must actually shard (round-4 review: the substring patterns
    missed q_b_proj/kv_b_proj, silently replicating them everywhere)."""
    from jax.sharding import PartitionSpec as P
    from tpuserve.parallel.mesh import AXIS_TP
    from tpuserve.parallel.sharding import _spec_for
    cfg = _cfg()
    assert _spec_for("layers.q_b_proj.kernel", cfg) == P(None, AXIS_TP)
    assert _spec_for("layers.kv_b_proj.kernel", cfg) == P(None, AXIS_TP)
    # the a-projections produce the SHARED latent: replicated
    assert _spec_for("layers.kv_a_proj.kernel", cfg) == P()
    assert _spec_for("layers.q_a_proj.kernel", cfg) == P()
    assert _spec_for("layers.router_bias.bias", cfg) == P()


def test_int8_mla_per_slice_scales_survive_hot_rope_channel():
    """ADVICE r4: one absmax scale over the 576-wide (latent ⊕ rope)
    vector lets a large rope channel crush latent precision.  The cache
    stores separate latent/rope scales; dequantized latents must stay
    accurate even when a rope channel is 50x the latent magnitude, and
    quantized decode must track the fp output."""
    from tpuserve.ops import attention as attn_ops

    cfg = _cfg()
    split = cfg.mla_kv_lora_rank
    cc = CacheConfig(block_size=4, num_blocks=8, max_blocks_per_seq=4,
                     dtype="int8")
    entry = create_kv_cache(cfg, cc)[0]
    assert entry["ks"].shape == (8, 4, 2)          # latent + rope scales

    rng = np.random.default_rng(0)
    T = 8
    latent = rng.normal(size=(1, T, cfg.mla_latent_dim)).astype(np.float32)
    latent[..., split:] *= 3.0
    latent[..., -1] = 50.0                          # hot rope channel
    latent = jnp.asarray(latent)
    slots = jnp.arange(T, dtype=jnp.int32)[None, :]
    entry = attn_ops.write_mla_entry(entry, latent, slots,
                                     latent_split=split)

    sc = attn_ops.expand_slice_scales(
        entry["ks"], (split, cfg.mla_qk_rope_head_dim))
    deq = (entry["k"].astype(jnp.float32) * sc).reshape(
        -1, cfg.mla_latent_dim)[:T]
    ref = latent[0]
    # latent slice precision must NOT be set by the 50.0 rope channel:
    # absmax/127 quantization error is bounded by half a step
    lat_err = jnp.max(jnp.abs(deq[:, :split] - ref[:, :split]))
    lat_step = jnp.max(jnp.abs(ref[:, :split])) / 127.0
    assert float(lat_err) <= float(lat_step) * 0.51 + 1e-6
    rope_err = jnp.max(jnp.abs(deq[:, split:] - ref[:, split:]))
    assert float(rope_err) <= 50.0 / 127.0 * 0.51 + 1e-6

    # end-to-end: quantized decode attention tracks fp within tolerance
    q = jnp.asarray(rng.normal(size=(1, cfg.num_heads, cfg.mla_latent_dim)),
                    jnp.float32)
    bt = jnp.arange(2, dtype=jnp.int32)[None, :]
    fp_entry = {"k": jnp.zeros((8, 4, 1, cfg.mla_latent_dim), jnp.float32)}
    fp_entry = attn_ops.write_mla_entry(fp_entry, latent, slots)
    lens = jnp.array([T], jnp.int32)
    out_q = attn_ops.paged_decode_attention(
        q, entry["k"], entry["k"], bt, lens, cfg.attn_scale,
        k_scale=entry["ks"], v_scale=entry["ks"],
        scale_slices=(split, cfg.mla_qk_rope_head_dim))
    out_fp = attn_ops.paged_decode_attention(
        q, fp_entry["k"], fp_entry["k"], bt, lens, cfg.attn_scale)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_fp),
                               atol=0.15, rtol=0.1)
