"""CPU rehearsal of the K-EXAONE cell: control flow, and what the probes
can tell apart.

What ``test_benchmark_mellum2_rehearsal.py`` does for its family, for a
configuration whose file names ``"reference": "k_exaone"`` and HOLDS A
SHARE: ``tiny-k-exaone`` (two periods of L L L G, window 8, no rotation
on the G layers, a dense layer and then 32 experts, 4 a token by sigmoid
scores scaled 2.5, beside a shared one) told through
``session.register_configuration`` that it holds 8 of its 32 experts and
half of its vocabulary; the real server, the warm-up of the traffic's
shapes, the probes against the family's plain reference, the child load
generator, the window.  The probes' prompts (64 to 128 tokens) are 8 to 16
windows long here.  Then the faults: one expert's weights altered, the
scaling ignored, a full layer rotated, the share shifted by one expert,
each scored against what the sound server produced, must read over a
limit.  Nothing here is a chip run, and nothing it prints is a device
number."""

import dataclasses
import json
import types

import pytest

from benchmark.harness import plan, session, stats
from benchmark.harness.meter import CompileMeter

CONFIG = {"model": "tiny-k-exaone", "chips": 1,
          "reduced": ["num_experts", "vocab_size"],
          "num_experts": 8, "vocab_size": 128,
          "published": {"num_experts": 32, "vocab_size": 256},
          "deployment": "one of 4 chips that share each expert layer, "
                        "experts 0-7 of 32; one of 2 that share the "
                        "vocabulary",
          "num_experts_per_tok": 4, "moe_intermediate_size": 32,
          "num_shared_experts": 1, "routed_scaling_factor": 2.5,
          "scoring_func": "sigmoid", "first_k_dense_replace": 1,
          "sliding_window": 8, "sliding_window_pattern": "LLLG",
          "expect": {"block_manager": "NativeBlockManager"},
          "reference": "k_exaone",
          "server_args": ["--num-blocks", "256", "--block-size", "8",
                          "--max-blocks-per-seq", "24",
                          "--max-num-seqs", "8", "--multi-step", "4",
                          "--kv-cache-dtype", "float32"]}
SEED = 2**31 + 41
HELD, EXPERT_LAYERS = 8, 7


def tiny_cell(tmp_path):
    mix = {"loop": "closed", "pool": 32, "pool_seed": 1, "preroll_s": 1.0,
           "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 60},
           "output": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
           "end_to_end": ["out_tok_s"]}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    return plan.Cell(name="tiny-k-exaone", chips=1,
                     config_name="tiny-k-exaone-ep4",
                     reference=plan.load_reference(CONFIG),
                     config=CONFIG, traffic_name="mix", traffic=mix,
                     traffic_path=str(path), params={"clients": 5},
                     end_to_end=("out_tok_s", "setup_s"), per_layer=(),
                     units={"out_tok_s": "tokens/s", "setup_s": "s"})


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server for the module: the window's run, the page of /metrics
    after it, and the probes as served (scored again by each test)."""
    tmp = tmp_path_factory.mktemp("k_exaone")
    cell, meter = tiny_cell(tmp), CompileMeter()
    assert plan.share_faults(cell.config) == []
    assert plan.unchecked_keys(cell.config, cell.reference) == []
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        assert engine._packed_prefill and engine.model_cfg.routes_experts
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
    gained: the experts held (the router keeps its 32), the vocabulary
    slice, and nothing else."""
    from tpuserve.models.config import get_model_config
    cfg = served.engine.model_cfg
    assert cfg == dataclasses.replace(
        get_model_config("tiny-k-exaone"), name="bench/tiny-k-exaone-ep4",
        moe_experts_held=HELD, vocab_size=128)
    assert (cfg.num_experts, cfg.moe_first_expert) == (32, 0)
    lp = served.engine.params["layers"][1]
    assert lp["experts"]["gate_proj"]["kernel"].shape == (HELD, 64, 32)
    assert lp["router"]["kernel"].shape == (64, 32)
    assert served.engine.params["lm_head"]["kernel"].shape == (64, 128)


def test_a_tiny_k_exaone_cell_runs_end_to_end(served):
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
    cfg = served.engine.model_cfg
    # what landed here came back with the tokens, into the step records
    # and the page
    routed = [step for step in run["steps"] if step.get("moe_rows")]
    assert routed and all(
        step["moe_rows"] == step["padded_tokens"] * EXPERT_LAYERS
        * cfg.num_experts_per_tok for step in routed)
    assert all(0 <= step["moe_held_rows"] <= step["moe_buffer_rows"]
               and step["moe_held_hits"] <= step["moe_expert_hits"]
               for step in routed)
    held = sum(step["moe_held_rows"] for step in routed)
    assert 0 < held < sum(step["moe_rows"] for step in routed) / 2
    assert page["tpuserve_moe_held_rows_total"] >= held
    assert page["tpuserve_moe_held_rows_total"] \
        < page["tpuserve_moe_routed_rows_total"]
    assert page["tpuserve_moe_buffer_rows_total"] \
        >= page["tpuserve_moe_held_rows_total"]
    assert 0 < page["tpuserve_moe_held_hits_total"]
    assert page["tpuserve_moe_experts_held"] == HELD
    assert page["tpuserve_moe_expert_rows_total"] \
        == page["tpuserve_moe_routed_rows_total"]
    # no trace, so the readers of the device's time find nothing to read
    # and say so; the counters' reader has the page
    run["trace"] = None
    readers = plan.discover_layer_metrics()
    for name in ("moe.held_gmm_roofline", "moe.held_gmm_device_share",
                 "moe.held_gmm_ns_per_row", "moe.shared_device_share"):
        assert readers[name].compute(run) is None
    zero = {k: 0.0 for k in page}
    away = readers["moe.away_rows_share"].compute(
        dict(run, metrics_start=zero, metrics_end=page))
    assert away == pytest.approx(100 * (
        1 - page["tpuserve_moe_held_rows_total"]
        / page["tpuserve_moe_buffer_rows_total"]))
    assert 0.0 <= away < 87.5


def test_the_probes_request_names_the_picks_of_the_expert_layers(served):
    """The logprobs object names the experts of every EXPERT layer (the
    dense layer has none) for every position of the prompt and for every
    served token, over all 32 experts whether held or not; they are the
    reference router's own at this size, so handing it none of them reads
    the same."""
    cfg = served.engine.model_cfg
    # (a prompt the server has not seen: the probes' own would hit the
    # prefix cache, and a position no prefill computed names no expert)
    ids = session.traffic_mod.prompt_ids(SEED, "unseen", 0, 64,
                                         cfg.vocab_size)
    assert max(ids) < 128
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
    ref = plan.load_reference(CONFIG)
    toks = [int(t) for t in lp["tokens"]]
    with_picks = ref.score_probes(served.engine.params, cfg,
                                  [(ids, toks, lp)])
    without = ref.score_probes(served.engine.params, cfg,
                               [(ids, toks, {})])
    assert with_picks.shape == (16, 128)
    assert float(abs(with_picks - without).max()) < 1e-4


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
    "a windowed layer run as full past the window":
        lambda served: {"window_layers": (False,) + (True, True, False) * 2
                        + (True,)},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_reads_over_a_limit(served, fault):
    """The reference handed one thing other than what runs (a held
    expert's kernel, the scaling, which experts are held, a layer's kind)
    disagrees with what the sound server produced by more than a limit of
    the harness."""
    bad = served.rescore(**FAULTS[fault](served))
    assert not bad["ok"] and "disagree" in bad["why"], (fault, bad)
    assert max(bad["logprob_diff_max"], bad["tie_gap_max"]) \
        > 1.2 * session.LOGPROB_ATOL, (fault, bad)
    good = served.rescore()
    assert good["ok"] and good["logprob_diff_max"] < 1e-3


def test_a_full_layer_that_rotates_reads_over_a_limit(served):
    """The fault on the SERVED side: the same weights through a program
    that rotates q and k on the full layers too, scored by the sound
    reference."""
    import numpy as np

    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams)
    cfg = served.engine.model_cfg
    rotates = dataclasses.replace(cfg, name="rotates-everywhere",
                                  rope_windowed_only=False)
    eng = Engine(EngineConfig(model="tiny-k-exaone", multi_step=4,
                              cache=CacheConfig(block_size=8, num_blocks=64,
                                                max_blocks_per_seq=24,
                                                dtype="float32")),
                 params=served.engine.params, model_cfg=rotates)
    ids = session.traffic_mod.prompt_ids(SEED, "probe", 0, 64,
                                         cfg.vocab_size)
    (out,) = eng.generate([ids], SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True, logprobs=1))
    toks = out.output_token_ids
    rows = np.asarray(plan.load_reference(CONFIG).score_probes(
        served.engine.params, cfg, [(ids, toks, {})]))
    off = max(abs(float(rows[j, e["token_id"]]) - e["logprob"])
              for j, e in enumerate(out.logprobs))
    assert [e["token_id"] for e in out.logprobs] == toks
    assert off > 1.2 * session.LOGPROB_ATOL, off


def test_the_parent_would_have_refused_this_file(monkeypatch, capsys):
    """A ModelConfig without the field (the program before the share)
    answers such a file with one sentence, before any server is built."""
    import tpuserve.models.config as models

    @dataclasses.dataclass(frozen=True)
    class Before:
        name: str = "tiny-k-exaone"
        num_experts: int = 32
        vocab_size: int = 256

    monkeypatch.setattr(models, "get_model_config", lambda name: Before())
    cell = types.SimpleNamespace(config=CONFIG, config_name="x",
                                 reference=None)
    with pytest.raises(session.Refused):
        session.register_configuration(cell)
    assert "'moe_experts_held'" in capsys.readouterr().out
