"""CPU rehearsal of the openPangu-Ultra-MoE cell: control flow, and what
the probes can tell apart.

What ``test_benchmark_k_exaone_rehearsal.py`` does for its family, for a
configuration whose file names ``"reference": "openpangu_moe"``, HOLDS A
SHARE and asks for the Pallas kernels: ``tiny-pangu`` (latent attention
with a query latent under sandwich norms, a cached vector of 136 + 12
stored as 256 lanes, two dense layers and then 16 experts, 4 a token by
sigmoid scores scaled 2.5 with no groups and no selection bias, beside a
shared one) told through ``session.register_configuration`` that it holds
8 of its 16 experts and half of its vocabulary; the real server with
``--attn-impl pallas`` (the paged kernels' latent entry in interpret mode),
the warm-up of the traffic's shapes, the probes against the family's plain
reference, the child load generator, the window.  Then the faults: one
expert's weights altered, the scaling ignored, the share shifted by one
expert, another rotary base, and on the SERVED side the score scale taken
from the cached vector's width, each must read over a limit.  And the
published cut's parameter count from the shapes.  Nothing here is a chip
run, and nothing it prints is a device number."""

import dataclasses
import json
import os
import types

import pytest

from benchmark.harness import plan, session, stats
from benchmark.harness.meter import CompileMeter

CONFIG = {"model": "tiny-pangu", "chips": 1,
          "reduced": ["n_routed_experts", "vocab_size"],
          "n_routed_experts": 8, "vocab_size": 128,
          "published": {"n_routed_experts": 16, "vocab_size": 256},
          "deployment": "one of 2 chips that share each expert layer, "
                        "experts 0-7 of 16; one of 2 that share the "
                        "vocabulary",
          "num_experts_per_tok": 4, "moe_intermediate_size": 32,
          "n_shared_experts": 1, "routed_scaling_factor": 2.5,
          "norm_topk_prob": True, "first_k_dense_replace": 2,
          "sandwich_norm": True, "kv_lora_rank": 136, "q_lora_rank": 40,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 12, "v_head_dim": 16,
          "rope_theta": 25600000, "num_nextn_predict_layers": 1,
          "expect": {"attn_impl": "pallas",
                     "block_manager": "NativeBlockManager"},
          "reference": "openpangu_moe",
          "server_args": ["--num-blocks", "256", "--block-size", "8",
                          "--max-blocks-per-seq", "24",
                          "--max-num-seqs", "8", "--multi-step", "4",
                          "--kv-cache-dtype", "float32",
                          "--attn-impl", "pallas"]}
SEED = 2**31 + 50
HELD, EXPERT_LAYERS = 8, 3
CELL_CONFIG = os.path.join(plan.BENCH_ROOT, "configs",
                           "openpangu-ultra-718b-ep16-l7.json")


def tiny_cell(tmp_path):
    mix = {"loop": "closed", "pool": 32, "pool_seed": 1, "preroll_s": 1.0,
           "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 60},
           "output": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
           "end_to_end": ["out_tok_s"]}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    return plan.Cell(name="tiny-pangu", chips=1,
                     config_name="tiny-pangu-ep2",
                     reference=plan.load_reference(CONFIG),
                     config=CONFIG, traffic_name="mix", traffic=mix,
                     traffic_path=str(path), params={"clients": 5},
                     end_to_end=("out_tok_s", "setup_s"), per_layer=(),
                     units={"out_tok_s": "tokens/s", "setup_s": "s"})


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server for the module: the window's run, the page of /metrics
    after it, and the probes as served (scored again by each test)."""
    tmp = tmp_path_factory.mktemp("openpangu")
    cell, meter = tiny_cell(tmp), CompileMeter()
    assert plan.share_faults(cell.config) == []
    assert plan.unchecked_keys(cell.config, cell.reference) == []
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        assert engine._packed_prefill and engine.model_cfg.is_mla
        verdict = session.probe(url, model, engine, SEED, cell.reference)
        run = session.run_window(cell, server, url, model, SEED, 2.0,
                                 False, str(tmp / "out"), meter)
        page = session.scrape(url)

        def rescore(reference=None, params=None, **fields):
            """The same served path scored by another reference, on other
            weights, or for another architecture than the one that runs."""
            other = types.SimpleNamespace(
                model_cfg=dataclasses.replace(engine.model_cfg, **fields),
                params=params or engine.params)
            return session.probe(url, model, other, SEED,
                                 reference or plan.load_reference(CONFIG))

        yield types.SimpleNamespace(cell=cell, engine=engine, run=run,
                                    verdict=verdict, page=page,
                                    rescore=rescore, url=url, model=model)
    finally:
        server.shutdown()


def test_the_share_reaches_the_program_as_data(served):
    """What the file lists under ``reduced`` is what the registered model
    gained: the experts held (the router keeps its 16), the vocabulary
    slice, and nothing else; the cache is ONE latent array a layer."""
    from tpuserve.models.config import get_model_config
    cfg = served.engine.model_cfg
    assert cfg == dataclasses.replace(
        get_model_config("tiny-pangu"), name="bench/tiny-pangu-ep2",
        moe_experts_held=HELD, vocab_size=128)
    assert (cfg.num_experts, cfg.moe_first_expert) == (16, 0)
    lp = served.engine.params["layers"][2]
    assert lp["experts"]["gate_proj"]["kernel"].shape == (HELD, 64, 32)
    assert lp["router"]["kernel"].shape == (64, 16)
    assert "router_bias" not in lp and "post_mlp_norm" in lp
    assert served.engine.params["lm_head"]["kernel"].shape == (64, 128)
    assert served.engine.attn_impl == "pallas"
    assert all(set(entry) == {"k"} and entry["k"].shape[2:] == (1, 256)
               for entry in served.engine.kv_cache)


def test_a_tiny_openpangu_cell_runs_end_to_end(served):
    verdict, run, page = served.verdict, served.run, served.page
    assert verdict["ok"], verdict
    assert verdict["positions"] == 48
    assert verdict["logprob_diff_max"] < 1e-3       # float32 on both sides
    assert verdict["tie_gap_max"] < 1e-3
    assert run["compiles_in_window"] == 0, run["new_executables"]
    s = stats.summarize(run["records"], "closed", run["t_window"],
                        run["t_end"])
    assert s["attempted"] > 0 and s["failed"] == 0, s["errors"]
    assert stats.end_to_end("out_tok_s", s) > 0
    assert {"prefill", "window"} <= {step["kind"] for step in run["steps"]}
    # the counter: context tokens the dispatches that are no prefill
    # attended against latent pages (the page counts from the server's
    # start, so it holds at least the window's)
    attended = sum(step["ctx_tokens"] for step in run["steps"]
                   if step["kind"] in ("window", "decode"))
    assert 0 < attended <= page["tpuserve_kv_latent_tokens_attended_total"]
    routed = [step for step in run["steps"] if step.get("moe_rows")]
    assert routed and all(
        step["moe_rows"] == step["padded_tokens"] * EXPERT_LAYERS
        * served.engine.model_cfg.num_experts_per_tok for step in routed)
    assert page["tpuserve_moe_experts_held"] == HELD
    # no trace, so the readers of the device's time find nothing to read
    # and say so, without raising
    run["trace"] = None
    readers = plan.discover_layer_metrics()
    names = [n for n in readers if n.startswith("mla.")]
    assert len(names) == 5
    for name in names:
        assert readers[name].compute(dict(
            run, config=dict(CONFIG, num_attention_heads=8))) is None


def test_the_probes_request_names_the_picks_of_the_expert_layers(served):
    """The logprobs object names the experts of every EXPERT layer (the
    two dense layers have none) for every position of the prompt and for
    every served token, over all 16 experts whether held or not."""
    cfg = served.engine.model_cfg
    ids = session.traffic_mod.prompt_ids(SEED, "unseen", 0, 64,
                                         cfg.vocab_size)
    body = session.http_json(served.url + "/v1/completions", {
        "model": served.model, "prompt": ids, "max_tokens": 16,
        "temperature": 0, "ignore_eos": True, "logprobs": 5})
    lp = body["choices"][0]["logprobs"]
    assert len(lp["routed_experts"]) == 16 \
        and len(lp["prompt_routed_experts"]) == 64
    assert all(len(tok) == EXPERT_LAYERS and all(
        len(layer) == cfg.num_experts_per_tok for layer in tok)
        for tok in lp["routed_experts"] + lp["prompt_routed_experts"])
    picks = [e for pos in lp["prompt_routed_experts"] for layer in pos
             for e in layer]
    assert min(picks) >= 0 and max(picks) >= HELD      # absent experts too


def _one_experts_weights_altered(served):
    import jax.numpy as jnp
    layers = list(served.engine.params["layers"])
    ek = layers[2]["experts"]
    up = ek["up_proj"]["kernel"]
    layers[2] = dict(layers[2], experts=dict(ek, up_proj={
        "kernel": up.at[3].set(jnp.flip(up[3], axis=0))}))
    return {"params": dict(served.engine.params, layers=layers)}


FAULTS = {
    "one expert's weights altered": _one_experts_weights_altered,
    "the scaling 2.5 ignored":
        lambda served: {"moe_routed_scaling": 1.0},
    "the share shifted by one expert":
        lambda served: {"moe_first_expert": 1},
    "a rotary base of 10,000":
        lambda served: {"rope_theta": 1e4},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_reads_over_a_limit(served, fault):
    """The reference handed one thing other than what runs (a held
    expert's kernel, the scaling, which experts are held, the rotary
    base) disagrees with what the sound server produced by more than a
    limit of the harness."""
    bad = served.rescore(**FAULTS[fault](served))
    assert not bad["ok"] and "disagree" in bad["why"], (fault, bad)
    assert max(bad["logprob_diff_max"], bad["tie_gap_max"]) \
        > 1.2 * session.LOGPROB_ATOL, (fault, bad)
    good = served.rescore()
    assert good["ok"] and good["logprob_diff_max"] < 1e-3


def test_a_score_scaled_by_the_latents_width_reads_over_a_limit(served):
    """The fault on the SERVED side: the same weights through a program
    that scales its scores by the cached vector's width (148^-0.5, as
    576^-0.5 would be at the published sizes) and not by the key's
    (28^-0.5), scored by the sound reference."""
    import numpy as np

    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams)
    cfg = served.engine.model_cfg
    wrong = dataclasses.replace(cfg, name="scaled-by-the-latent",
                                query_pre_attn_scalar=cfg.mla_latent_dim)
    eng = Engine(EngineConfig(model="tiny-pangu", multi_step=4,
                              attn_impl="pallas",
                              cache=CacheConfig(block_size=8, num_blocks=64,
                                                max_blocks_per_seq=24,
                                                dtype="float32")),
                 params=served.engine.params, model_cfg=wrong)
    ids = session.traffic_mod.prompt_ids(SEED, "probe", 0, 64,
                                         cfg.vocab_size)
    (out,) = eng.generate([ids], SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True, logprobs=1))
    toks = out.output_token_ids
    rows = np.asarray(plan.load_reference(CONFIG).score_probes(
        served.engine.params, cfg, [(ids, toks, {})]))
    off = max(abs(float(rows[j, e["token_id"]]) - e["logprob"])
              for j, e in enumerate(out.logprobs))
    assert off > 1.2 * session.LOGPROB_ATOL, off


def test_the_published_cut_counts_6161_m_parameters_from_its_shapes():
    """The configuration file through ``register_configuration``: 7 of 61
    layers, 16 of 256 experts held, 19,200 of 153,600 vocabulary rows, at
    published widths; the parameter tree's shapes count 6,161 M (12.32 GB
    in bf16), an expert layer 1,000.7 M and a dense layer 621.3 M, and the
    latent cache 2 B x 640 lanes x 7 layers a token."""
    import jax

    from tpuserve.models.config import get_model_config
    from tpuserve.models.weights import init_params
    from tpuserve.runtime.kv_cache import CacheConfig, bytes_per_block
    config = plan.read_json(CELL_CONFIG)
    cell = types.SimpleNamespace(
        config=config, config_name="openpangu-ultra-718b-ep16-l7",
        reference=plan.load_reference(config))
    assert plan.share_faults(config) == []
    assert plan.unchecked_keys(config, cell.reference) == []
    cfg = get_model_config(session.register_configuration(cell))
    assert (cfg.num_layers, cfg.moe_experts_held, cfg.num_experts,
            cfg.vocab_size, cfg.moe_first_k_dense) == (7, 16, 256, 19200, 3)
    assert (cfg.hidden_size, cfg.num_heads, cfg.mla_q_lora_rank,
            cfg.mla_kv_lora_rank, cfg.head_dim, cfg.mla_v_head_dim,
            cfg.expert_intermediate_size, cfg.num_experts_per_tok,
            cfg.intermediate_size) == (7680, 128, 1536, 512, 192, 128, 2048,
                                       8, 18432)
    shapes = jax.eval_shape(lambda: init_params(cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))
    # (the matrices are 6,161.4 M; the norms' vectors bring 6,161.7 M)
    assert count(shapes) // 10**6 == 6161
    assert round(count(shapes["layers"][0]) / 1e6) == 621       # dense
    assert round(count(shapes["layers"][3]) / 1e6) == 1001      # experts
    assert "experts" not in shapes["layers"][2]
    assert shapes["layers"][3]["experts"]["up_proj"]["kernel"].shape \
        == (16, 7680, 2048)
    assert shapes["layers"][3]["router"]["kernel"].shape == (7680, 256)
    assert bytes_per_block(cfg, CacheConfig(block_size=32)) \
        == 32 * 2 * 640 * 7
    argv = config["server_args"]
    assert argv[argv.index("--max-num-seqs") + 1] == "128"
    assert argv[argv.index("--attn-impl") + 1] == "pallas"


def test_the_parent_would_have_refused_this_file(monkeypatch):
    """A program without the registered model (the parent commit) answers
    the file before any server is built: ``get_model_config`` raises on
    the name, at once."""
    import tpuserve.models.config as models
    config = plan.read_json(CELL_CONFIG)
    monkeypatch.setattr(models, "_REGISTRY", {
        k: v for k, v in models._REGISTRY.items() if "pangu" not in k})
    cell = types.SimpleNamespace(config=config, config_name="x",
                                 reference=None)
    with pytest.raises(KeyError, match="Unknown model"):
        session.register_configuration(cell)
