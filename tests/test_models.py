"""Model family tests: config registry/HF parsing, forward/prefill/decode
parity, HF checkpoint name-mapping."""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.models import transformer, weights
from tpuserve.models.config import (
    config_from_hf_json, get_model_config, list_model_configs)
from tpuserve.ops.attention import PAD_SLOT


def test_registry_has_tracked_configs():
    # The five tracked configs from BASELINE.json.
    for name in ("qwen3-0.6b", "qwen2-72b", "llama3-8b", "phi3-mini", "opt-1.3b"):
        cfg = get_model_config(name)
        assert cfg.num_layers > 0
    assert "Qwen/Qwen3-0.6B" in list_model_configs()


def test_qwen3_preset_shape_math():
    cfg = get_model_config("qwen3-0.6b")
    assert cfg.q_size == 2048 and cfg.kv_size == 1024
    assert cfg.qk_norm and cfg.tie_word_embeddings
    # ~0.6B params (embedding-heavy model)
    assert 0.4e9 < cfg.num_params < 0.8e9


def test_hf_config_parsing_llama_family():
    hf = dict(model_type="qwen3", architectures=["Qwen3ForCausalLM"],
              vocab_size=1000, hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, rope_theta=1e6,
              rms_norm_eps=1e-6, tie_word_embeddings=True,
              max_position_embeddings=2048, eos_token_id=[7, 8])
    cfg = config_from_hf_json("x", hf)
    assert cfg.qk_norm and cfg.num_kv_heads == 2 and cfg.head_dim == 16
    assert cfg.eos_token_id == 7


def test_hf_config_parsing_opt():
    hf = dict(model_type="opt", vocab_size=100, hidden_size=32, ffn_dim=64,
              num_hidden_layers=2, num_attention_heads=4,
              max_position_embeddings=128, eos_token_id=2)
    cfg = config_from_hf_json("opt", hf)
    assert cfg.pos == "learned" and cfg.learned_pos_offset == 2
    assert cfg.mlp_style == "mlp" and cfg.act == "relu" and cfg.norm == "layernorm"


@pytest.mark.parametrize("fixture_name", ["fp32_tiny_qwen3", "fp32_tiny_llama", "fp32_tiny_opt"])
def test_prefill_decode_matches_forward(fixture_name, request):
    """Paged prefill + decode must reproduce the plain forward pass."""
    cfg = request.getfixturevalue(fixture_name)
    params = weights.init_params(cfg)
    tokens = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    B, T, bs, nb = 2, 4, 4, 8
    cache = [{"k": jnp.zeros((nb, bs, cfg.num_kv_heads, cfg.head_dim), jnp.float32),
              "v": jnp.zeros((nb, bs, cfg.num_kv_heads, cfg.head_dim), jnp.float32)}
             for _ in range(cfg.num_layers)]
    prompt_lens = jnp.asarray([4, 2])
    slots = np.full((B, T), PAD_SLOT, np.int32)
    for b in range(B):
        for t in range(int(prompt_lens[b])):
            slots[b, t] = [0, 2][b] * bs + t
    logits_p, cache = transformer.prefill(params, cfg, tokens, prompt_lens,
                                          jnp.asarray(slots), cache)
    full = transformer.forward(params, cfg, tokens, prompt_lens)
    np.testing.assert_allclose(np.asarray(logits_p[0]), np.asarray(full[0, 3]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(logits_p[1]), np.asarray(full[1, 1]), atol=1e-4)

    bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    logits_d, cache = transformer.decode_step(
        params, cfg, jnp.asarray([7, 9], jnp.int32), jnp.asarray([4, 2], jnp.int32),
        jnp.asarray([1 * bs, 2 * bs + 2], jnp.int32), bt, jnp.asarray([5, 3], jnp.int32),
        cache)
    full2 = transformer.forward(
        params, cfg, jnp.asarray([[1, 2, 3, 4, 7, 0], [5, 6, 9, 0, 0, 0]], jnp.int32),
        jnp.asarray([5, 3]))
    np.testing.assert_allclose(np.asarray(logits_d[0]), np.asarray(full2[0, 4]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(logits_d[1]), np.asarray(full2[1, 2]), atol=1e-4)


def _save_safetensors(path, tensors):
    from safetensors.numpy import save_file
    save_file(tensors, path)


def test_hf_checkpoint_loading_llama_names(tmp_path, fp32_tiny_llama):
    """Round-trip: write an HF-named checkpoint, load, compare vs direct params."""
    cfg = fp32_tiny_llama
    rng = np.random.default_rng(0)
    raw = {"model.embed_tokens.weight": rng.standard_normal(
        (cfg.vocab_size, cfg.hidden_size)).astype(np.float32),
        "model.norm.weight": np.ones(cfg.hidden_size, np.float32),
        "lm_head.weight": rng.standard_normal(
            (cfg.vocab_size, cfg.hidden_size)).astype(np.float32)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        raw[p + "input_layernorm.weight"] = np.ones(cfg.hidden_size, np.float32)
        raw[p + "post_attention_layernorm.weight"] = np.ones(cfg.hidden_size, np.float32)
        raw[p + "self_attn.q_proj.weight"] = rng.standard_normal(
            (cfg.q_size, cfg.hidden_size)).astype(np.float32)
        raw[p + "self_attn.k_proj.weight"] = rng.standard_normal(
            (cfg.kv_size, cfg.hidden_size)).astype(np.float32)
        raw[p + "self_attn.v_proj.weight"] = rng.standard_normal(
            (cfg.kv_size, cfg.hidden_size)).astype(np.float32)
        raw[p + "self_attn.o_proj.weight"] = rng.standard_normal(
            (cfg.hidden_size, cfg.q_size)).astype(np.float32)
        raw[p + "mlp.gate_proj.weight"] = rng.standard_normal(
            (cfg.intermediate_size, cfg.hidden_size)).astype(np.float32)
        raw[p + "mlp.up_proj.weight"] = rng.standard_normal(
            (cfg.intermediate_size, cfg.hidden_size)).astype(np.float32)
        raw[p + "mlp.down_proj.weight"] = rng.standard_normal(
            (cfg.hidden_size, cfg.intermediate_size)).astype(np.float32)
    _save_safetensors(str(tmp_path / "model.safetensors"), raw)
    params = weights.load_hf_checkpoint(cfg, str(tmp_path))
    # kernels are transposed HF weights
    np.testing.assert_array_equal(
        np.asarray(params["layers"][0]["q_proj"]["kernel"]),
        raw["model.layers.0.self_attn.q_proj.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"]["kernel"]),
        raw["lm_head.weight"].T)
    logits = transformer.forward(params, cfg, jnp.asarray([[1, 2, 3]], jnp.int32))
    assert logits.shape == (1, 3, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_hf_checkpoint_loading_phi3_fused(tmp_path):
    """Phi-3 stores fused qkv_proj / gate_up_proj — loader must split them."""
    from tpuserve.models.config import ModelConfig
    cfg = ModelConfig(name="tiny-phi", vocab_size=64, hidden_size=32,
                      intermediate_size=48, num_layers=1, num_heads=4,
                      num_kv_heads=4, head_dim=8, tie_word_embeddings=False,
                      dtype="float32")
    rng = np.random.default_rng(1)
    qkv = rng.standard_normal((cfg.q_size + 2 * cfg.kv_size, cfg.hidden_size)).astype(np.float32)
    gu = rng.standard_normal((2 * cfg.intermediate_size, cfg.hidden_size)).astype(np.float32)
    raw = {
        "model.embed_tokens.weight": rng.standard_normal((64, 32)).astype(np.float32),
        "model.norm.weight": np.ones(32, np.float32),
        "lm_head.weight": rng.standard_normal((64, 32)).astype(np.float32),
        "model.layers.0.input_layernorm.weight": np.ones(32, np.float32),
        "model.layers.0.post_attention_layernorm.weight": np.ones(32, np.float32),
        "model.layers.0.self_attn.qkv_proj.weight": qkv,
        "model.layers.0.self_attn.o_proj.weight": rng.standard_normal(
            (32, cfg.q_size)).astype(np.float32),
        "model.layers.0.mlp.gate_up_proj.weight": gu,
        "model.layers.0.mlp.down_proj.weight": rng.standard_normal(
            (32, 48)).astype(np.float32),
    }
    _save_safetensors(str(tmp_path / "model.safetensors"), raw)
    params = weights.load_hf_checkpoint(cfg, str(tmp_path))
    np.testing.assert_array_equal(
        np.asarray(params["layers"][0]["q_proj"]["kernel"]), qkv[:cfg.q_size].T)
    np.testing.assert_array_equal(
        np.asarray(params["layers"][0]["k_proj"]["kernel"]),
        qkv[cfg.q_size:cfg.q_size + cfg.kv_size].T)
    np.testing.assert_array_equal(
        np.asarray(params["layers"][0]["gate_proj"]["kernel"]),
        gu[:cfg.intermediate_size].T)
    np.testing.assert_array_equal(
        np.asarray(params["layers"][0]["up_proj"]["kernel"]),
        gu[cfg.intermediate_size:].T)


def test_hf_checkpoint_loading_opt_names(tmp_path, fp32_tiny_opt):
    cfg = fp32_tiny_opt
    rng = np.random.default_rng(2)
    h, q = cfg.hidden_size, cfg.q_size
    raw = {
        "model.decoder.embed_tokens.weight": rng.standard_normal(
            (cfg.vocab_size, h)).astype(np.float32),
        "model.decoder.embed_positions.weight": rng.standard_normal(
            (cfg.max_position_embeddings + 2, h)).astype(np.float32),
        "model.decoder.final_layer_norm.weight": np.ones(h, np.float32),
        "model.decoder.final_layer_norm.bias": np.zeros(h, np.float32),
    }
    for i in range(cfg.num_layers):
        p = f"model.decoder.layers.{i}."
        for nm in ("self_attn_layer_norm", "final_layer_norm"):
            raw[p + nm + ".weight"] = np.ones(h, np.float32)
            raw[p + nm + ".bias"] = np.zeros(h, np.float32)
        for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
            raw[p + f"self_attn.{proj}.weight"] = rng.standard_normal((q, h) if proj != "out_proj" else (h, q)).astype(np.float32)
            raw[p + f"self_attn.{proj}.bias"] = np.zeros(q if proj != "out_proj" else h, np.float32)
        raw[p + "fc1.weight"] = rng.standard_normal((cfg.intermediate_size, h)).astype(np.float32)
        raw[p + "fc1.bias"] = np.zeros(cfg.intermediate_size, np.float32)
        raw[p + "fc2.weight"] = rng.standard_normal((h, cfg.intermediate_size)).astype(np.float32)
        raw[p + "fc2.bias"] = np.zeros(h, np.float32)
    _save_safetensors(str(tmp_path / "model.safetensors"), raw)
    params = weights.load_hf_checkpoint(cfg, str(tmp_path))
    assert "pos_embed" in params and "lm_head" not in params  # OPT ties embeddings
    logits = transformer.forward(params, cfg, jnp.asarray([[1, 2, 3]], jnp.int32))
    assert bool(jnp.isfinite(logits).all())


def test_get_model_config_from_checkpoint_dir(tmp_path):
    cfg_json = dict(model_type="llama", vocab_size=128, hidden_size=32,
                    intermediate_size=64, num_hidden_layers=1,
                    num_attention_heads=4, num_key_value_heads=4,
                    rms_norm_eps=1e-5, max_position_embeddings=256)
    (tmp_path / "config.json").write_text(json.dumps(cfg_json))
    cfg = get_model_config(str(tmp_path))
    assert cfg.hidden_size == 32 and cfg.head_dim == 8


def test_orbax_roundtrip(tmp_path):
    """Weight persistence (the reference parks weights on PVCs,
    llm-d-deploy.yaml:195-215; here orbax is the cache format)."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from tpuserve.models import weights
    from tpuserve.models.config import get_model_config
    cfg = dataclasses.replace(get_model_config("tiny-qwen3"), dtype="float32")
    params = weights.init_params(cfg, seed=3)
    path = str(tmp_path / "ckpt")
    weights.save_orbax(params, path)
    restored = weights.restore_orbax(cfg, path)
    a = np.asarray(params["layers"][0]["q_proj"]["kernel"])
    b = np.asarray(restored["layers"][0]["q_proj"]["kernel"])
    np.testing.assert_array_equal(a, b)
    # quantized pytrees (int8 + scales) survive the same path
    qp = weights.quantize_params_int8(params)
    qpath = str(tmp_path / "ckpt-int8")
    weights.save_orbax(qp, qpath)
    qr = weights.restore_orbax(cfg, qpath, target_params=qp)
    assert qr["layers"][0]["q_proj"]["kernel"].dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(qp["embed"]["scale"]), np.asarray(qr["embed"]["scale"]))


def test_tiny_gemma_serves():
    """Gemma family traits (RMSNorm(1+w), sqrt(hidden) embed scale,
    tanh-GELU, head_dim independent of hidden/heads) through the full
    engine path."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)
    eng = Engine(EngineConfig(
        model="tiny-gemma",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=2)))
    out = eng.generate(["hello gemma"],
                       SamplingParams(max_tokens=6, temperature=0.0,
                                      ignore_eos=True))[0]
    assert len(out.output_token_ids) == 6
    a = eng.generate(["hello gemma"],
                     SamplingParams(max_tokens=6, temperature=0.0,
                                    ignore_eos=True))[0]
    assert a.output_token_ids == out.output_token_ids


def test_tiny_mistral_sliding_window_serves():
    """Sliding-window family end to end: prompts longer than the window
    route through batched AND chunked prefill, and decode crosses the
    window boundary; pallas (interpret) and reference impls agree."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)

    def mk(attn, chunk=64):
        return Engine(EngineConfig(
            model="tiny-mistral", attn_impl=attn,
            cache=CacheConfig(block_size=4, num_blocks=128,
                              max_blocks_per_seq=32),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=2,
                                      prefill_chunk_size=chunk)))
    prompts = [list(range(2, 32)), [5, 6, 7]]    # 30 tokens >> window 8
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    ref = mk("reference").generate(prompts, p)
    pal = mk("pallas").generate(prompts, p)
    for a, b in zip(ref, pal):
        assert len(a.output_token_ids) == 10
        assert a.output_token_ids == b.output_token_ids
    # chunked prefill route (chunk 16 < prompt 30) agrees too
    chunked = mk("reference", chunk=16).generate(prompts, p)
    for a, b in zip(ref, chunked):
        assert a.output_token_ids == b.output_token_ids


def test_qwen_style_sliding_window_gating():
    """Qwen2-style configs: the window applies only when use_sliding_window
    is on; HF's max_window_layers (the FIRST that-many layers use full
    attention) maps onto full_attention_first_layers."""
    from tpuserve.models.config import _sliding_window

    base = {"sliding_window": 4096, "num_hidden_layers": 28}
    # qwen default: field present but disabled
    assert _sliding_window({**base, "use_sliding_window": False},
                           "qwen2") == {}
    # enabled but every layer full-attention (mwl == num_layers): no window
    assert _sliding_window({**base, "use_sliding_window": True,
                            "max_window_layers": 28}, "qwen2") == {}
    # uniform SWA (mwl == 0)
    assert _sliding_window({**base, "use_sliding_window": True,
                            "max_window_layers": 0}, "qwen2") == {
        "sliding_window": 4096, "full_attention_first_layers": 0}
    # mixed per-layer: first 14 layers full attention, rest windowed
    assert _sliding_window({**base, "use_sliding_window": True,
                            "max_window_layers": 14}, "qwen2") == {
        "sliding_window": 4096, "full_attention_first_layers": 14}
    # mistral applies whenever set
    assert _sliding_window({"sliding_window": 4096}, "mistral") == {
        "sliding_window": 4096, "full_attention_first_layers": 0}
    assert _sliding_window({"sliding_window": None}, "mistral") == {}


def test_sliding_window_rolling_buffer_capacity():
    """The rolling buffer returns out-of-window blocks to the pool, so
    windowed sequences fit a cache their full contexts would blow: four
    32-token sequences (9 blocks each unreleased) serve concurrently from
    a 24-block pool without a single preemption, and emit the same tokens
    as an uncontended engine."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)

    def mk(num_blocks):
        return Engine(EngineConfig(
            model="tiny-mistral",
            cache=CacheConfig(block_size=4, num_blocks=num_blocks,
                              max_blocks_per_seq=16),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=2),
            enable_prefix_caching=False))
    prompts = [[i + 2, i + 3, i + 4] * 4 for i in range(4)]   # 12 tokens
    p = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
    tight = mk(24)
    outs = tight.generate(prompts, p)
    assert all(len(r.output_token_ids) == 20 for r in outs)
    assert tight.stats.preemptions == 0, (
        "rolling buffer failed to hold 4 windowed seqs in 24 blocks")
    assert tight.block_manager.num_seqs() == 0
    assert tight.block_manager.num_free_blocks == 24
    roomy = mk(64).generate(prompts, p)
    for a, b in zip(outs, roomy):
        assert a.output_token_ids == b.output_token_ids


def test_tiny_gemma2_serves_all_impls():
    """Gemma2's full trait set through the serving engine: sandwich norms,
    attention/final softcaps, qpas scale, alternating sliding/full layers.
    reference and pallas (interpret) agree token for token, and the
    chunked-prefill route matches — covering softcap + alternation in
    every kernel."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)

    def mk(attn, chunk=64):
        return Engine(EngineConfig(
            model="tiny-gemma2", attn_impl=attn,
            cache=CacheConfig(block_size=4, num_blocks=128,
                              max_blocks_per_seq=32),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=2,
                                      prefill_chunk_size=chunk)))
    prompts = [list(range(2, 30)), [5, 6, 7] * 4]   # 28 tokens >> window 8
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    ref = mk("reference").generate(prompts, p)
    pal = mk("pallas").generate(prompts, p)
    for a, b in zip(ref, pal):
        assert len(a.output_token_ids) == 10
        assert a.output_token_ids == b.output_token_ids
    for impl in ("reference", "pallas"):   # pallas = the WINDOW KERNEL's
        chunked = mk(impl, chunk=16).generate(prompts, p)   # softcap path
        for a, b in zip(ref, chunked):
            assert a.output_token_ids == b.output_token_ids
    # mixed layers: the rolling buffer must NOT release (odd layers are
    # full attention and need all KV) — fail loudly if any release fires
    eng = mk("reference")

    def _boom(*a, **kw):
        raise AssertionError("release_out_of_window fired on a "
                             "mixed-layer (non-uniform-window) model")
    eng.block_manager.release_out_of_window = _boom
    eng.generate(prompts, p)
    assert not eng.model_cfg.uniform_window


def test_tiny_gemma3_serves_all_impls():
    """Gemma3 text end to end: 5-local:1-global layers with PER-LAYER rope
    (local 10k unscaled / global 1M with linear scaling), qk norms,
    sandwich norms; reference == pallas == chunked token equality."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)

    def mk(attn, chunk=64):
        return Engine(EngineConfig(
            model="tiny-gemma3", attn_impl=attn,
            cache=CacheConfig(block_size=4, num_blocks=192,
                              max_blocks_per_seq=32),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=2,
                                      prefill_chunk_size=chunk)))
    prompts = [list(range(2, 30)), [5, 6, 7] * 4]   # 28 tokens >> window 8
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    ref = mk("reference").generate(prompts, p)
    for impl, chunk in (("pallas", 64), ("reference", 16), ("pallas", 16)):
        outs = mk(impl, chunk).generate(prompts, p)
        for a, b in zip(ref, outs):
            assert len(a.output_token_ids) == 10
            assert a.output_token_ids == b.output_token_ids


def test_gemma3_sliding_window_pattern_fallback():
    """Original-release gemma3 configs carry sliding_window_pattern
    instead of layer_types — both must parse to the same layer map."""
    base = dict(model_type="gemma3_text", vocab_size=256, hidden_size=64,
                intermediate_size=128, num_hidden_layers=6,
                num_attention_heads=4, num_key_value_heads=2, head_dim=24,
                max_position_embeddings=512, sliding_window=8,
                query_pre_attn_scalar=24, eos_token_id=1)
    via_types = config_from_hf_json("a", {
        **base, "layer_types": ["sliding_attention"] * 5
        + ["full_attention"]})
    via_pattern = config_from_hf_json("b", {
        **base, "sliding_window_pattern": 6})
    assert via_types.window_layers == via_pattern.window_layers
    assert via_pattern.layer_window(4) == 8
    assert via_pattern.layer_window(5) is None


def test_every_registered_config_is_structurally_sound():
    """Hand-entered registry entries (gemma3-4b, llama31-8b, ...) must be
    internally consistent — a typo here serves garbage at checkpoint-load
    time, far from its cause."""
    from tpuserve.models.config import ModelConfig
    for name in list_model_configs():
        if name.startswith("bench/"):
            # a cell's CUT of a registered model, which a test of the
            # benchmark's registered earlier in this process (a cut keeps
            # the published per-layer lists whole): not hand-entered
            continue
        cfg = get_model_config(name)
        assert cfg.num_heads % cfg.num_kv_heads == 0, name
        # (a latent layer beside another mixer states its own q/k width:
        # ModelConfig.mla_qk_head_dim; everywhere else it IS head_dim)
        assert cfg.q_size == cfg.num_heads * cfg.qk_head_dim, name
        assert cfg.qk_head_dim == (cfg.mla_qk_head_dim or cfg.head_dim), name
        if cfg.mla_qk_head_dim is not None:
            assert cfg.is_mla and cfg.linear_layers is not None, name
            assert cfg.mla_qk_nope_head_dim > 0, name
        if cfg.window_layers is not None:
            assert len(cfg.window_layers) == cfg.num_layers, name
            assert cfg.sliding_window, name
        if cfg.full_attention_first_layers:
            assert cfg.sliding_window, name
            assert cfg.full_attention_first_layers < cfg.num_layers, name
        if cfg.rope_llama3_scaling is not None:
            assert len(cfg.rope_llama3_scaling) == 4, name
        # every layer resolves a window + rope without raising
        for li in range(cfg.num_layers):
            cfg.layer_window(li)
            cfg.layer_rope(li)
        assert cfg.num_params > 0, name
