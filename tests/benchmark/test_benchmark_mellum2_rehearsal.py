"""CPU rehearsal of a Mellum 2 cell: control flow, and what the probes can
tell apart.

What ``test_benchmark_falcon_h1_rehearsal.py`` does for its family, for a
configuration whose file names ``"reference": "mellum2"``: the real server
on ``tiny-mellum2`` (two periods of three windowed layers to one full one,
window 16, a YaRN table on the full layers, 8 experts and 2 a token), the
warm-up of the traffic's shapes, the probes against the family's plain
reference, the child load generator, the window.  The probes' prompts (64
to 128 tokens) are four to eight windows long here, which they are not at
the published window of 1,024.  Then four faults, each one line of the
mathematics changed in a fresh copy of the reference: the sound served
tokens scored by it must read over a limit.  Nothing here is a chip run,
and nothing it prints is a device number."""

import dataclasses
import json
import types

import pytest

from benchmark.harness import plan, session, stats
from benchmark.harness.meter import CompileMeter

CONFIG = {"model": "tiny-mellum2", "reduced": [], "chips": 1,
          "expect": {"block_manager": "NativeBlockManager"},
          "reference": "mellum2",
          "server_args": ["--num-blocks", "256", "--block-size", "8",
                          "--max-blocks-per-seq", "24",
                          "--max-num-seqs", "8", "--multi-step", "4",
                          "--kv-cache-dtype", "float32"]}
SEED = 2**31 + 35


def tiny_cell(tmp_path):
    mix = {"loop": "closed", "pool": 32, "pool_seed": 1, "preroll_s": 1.0,
           "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 60},
           "output": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
           "end_to_end": ["out_tok_s"]}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    return plan.Cell(name="tiny-mellum2", chips=1, config_name="tiny-mellum2",
                     reference=plan.load_reference(CONFIG),
                     config=CONFIG, traffic_name="mix", traffic=mix,
                     traffic_path=str(path), params={"clients": 5},
                     end_to_end=("out_tok_s", "setup_s"), per_layer=(),
                     units={"out_tok_s": "tokens/s", "setup_s": "s"})


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server for the module: the window's run, the page of /metrics
    after it, and the probes as served (scored again by each test)."""
    tmp = tmp_path_factory.mktemp("mellum2")
    cell, meter = tiny_cell(tmp), CompileMeter()
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        assert engine._packed_prefill and engine.model_cfg.routes_experts
        verdict = session.probe(url, model, engine, SEED, cell.reference)
        run = session.run_window(cell, server, url, model, SEED, 2.0,
                                 False, str(tmp / "out"), meter)
        page = session.scrape(url)

        def rescore(reference, model_cfg=None):
            """The same served path scored by another reference, or for
            another architecture than the one that runs."""
            other = types.SimpleNamespace(
                model_cfg=model_cfg or engine.model_cfg,
                params=engine.params)
            return session.probe(url, model, other, SEED, reference)

        yield types.SimpleNamespace(cell=cell, engine=engine, run=run,
                                    verdict=verdict, page=page,
                                    rescore=rescore, url=url, model=model)
    finally:
        server.shutdown()


def test_a_tiny_mellum2_cell_runs_end_to_end(served):
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
    assert run["kv_bytes_per_token"] == 2 * cfg.num_layers \
        * cfg.num_kv_heads * cfg.head_dim * 4
    # the routing counts came back with the tokens, into the step records
    # and the page
    routed = [step for step in run["steps"] if step.get("moe_rows")]
    assert routed and all(
        step["moe_rows"] == step["padded_tokens"] * cfg.num_layers
        * cfg.num_experts_per_tok for step in routed)
    assert page["tpuserve_moe_routed_rows_total"] >= sum(
        step["moe_rows"] for step in routed)
    assert page["tpuserve_moe_expert_rows_total"] \
        == page["tpuserve_moe_routed_rows_total"]
    assert page["tpuserve_moe_expert_load_max_over_mean"] >= 1.0
    assert 0.0 <= page["tpuserve_kv_window_dead_tokens"] \
        < page["tpuserve_kv_pool_tokens"] * cfg.num_layers
    assert page["tpuserve_kv_pool_tokens"] == 256 * 8
    # no trace, so the moe.* readers find nothing to read and say so; an
    # untraced run scrapes no page either, which the dead share reads
    run["trace"] = None
    readers = plan.discover_layer_metrics()
    for name in ("moe.gmm_device_share", "moe.gmm_ns_per_row",
                 "moe.gmm_roofline", "kv.window_dead_share"):
        assert readers[name].compute(run) is None
    traced = dict(run, metrics_start=page, metrics_end=page, config={
        **run["config"], "num_hidden_layers": cfg.num_layers})
    assert 0.0 <= readers["kv.window_dead_share"].compute(traced) < 100.0


def test_the_probes_request_is_answered_with_every_pick(served):
    """The request ``session.probe`` sends, as it sends it: the logprobs
    object names the experts of every layer for every position of the
    prompt and for every served token, which is what the reference
    replays; they are the reference router's own at this size (float32 on
    both sides), so handing it none of them reads the same."""
    cfg = served.engine.model_cfg
    ids = session.traffic_mod.prompt_ids(SEED, "probe", 0, 64,
                                         cfg.vocab_size)
    body = session.http_json(served.url + "/v1/completions", {
        "model": served.model, "prompt": ids, "max_tokens": 16,
        "temperature": 0, "ignore_eos": True, "logprobs": 5})
    lp = body["choices"][0]["logprobs"]
    shape = [cfg.num_layers, cfg.num_experts_per_tok]
    assert [len(lp["routed_experts"]), *shape] == [16, *shape] \
        and all(len(layer) == shape[1] for tok in lp["routed_experts"]
                for layer in tok)
    assert len(lp["prompt_routed_experts"]) == 64
    assert lp["prompt_routed_experts"][-1] == lp["routed_experts"][0]
    assert min(e for pos in lp["prompt_routed_experts"] for layer in pos
               for e in layer) >= 0
    ref = plan.load_reference(CONFIG)
    toks = [int(t) for t in lp["tokens"]]
    with_picks = ref.score_probes(served.engine.params, cfg,
                                  [(ids, toks, lp)])
    without = ref.score_probes(served.engine.params, cfg,
                               [(ids, toks, {})])
    assert float(abs(with_picks - without).max()) < 1e-4


def _skip_one_chosen_expert(ref):
    sound = ref.route

    def route(lp, h, cfg, served):
        w = sound(lp, h, cfg, served)
        return w.at[:, 3].set(0.0)      # expert 3 adds nothing where chosen
    ref.route = route


def _ignore_the_attention_factor(ref):
    sound = ref.rotary_table
    ref.rotary_table = lambda cfg, windowed: (sound(cfg, windowed)[0], 1.0)


FAULTS = {
    "one chosen expert skipped": (_skip_one_chosen_expert, {}),
    "the attention factor ignored on full layers":
        (_ignore_the_attention_factor, {}),
    "a windowed layer run as full past the window":
        (None, {"window_layers": (False,) + (True, True, False) * 2
                + (True,)}),
    "renormalisation dropped": (None, {"norm_topk_prob": False}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_reads_over_a_limit(served, fault):
    """The reference with one line of the mathematics changed (a fresh
    copy of the module: nothing of a sound trace is reused) disagrees with
    what the sound server produced by more than a limit of the harness."""
    patch, fields = FAULTS[fault]
    ref = plan.load_reference(CONFIG)
    if patch is not None:
        patch(ref)
    cfg = dataclasses.replace(served.engine.model_cfg, **fields)
    bad = served.rescore(ref, cfg)
    assert not bad["ok"] and "disagree" in bad["why"], (fault, bad)
    # (with the served picks replayed a fault outside the router no longer
    # grows by moving picks: the attention factor reads 0.127 here)
    assert max(bad["logprob_diff_max"], bad["tie_gap_max"]) \
        > 1.2 * session.LOGPROB_ATOL, (fault, bad)
    # and the sound reference, loaded the same way, still agrees
    good = served.rescore(plan.load_reference(CONFIG))
    assert good["ok"] and good["logprob_diff_max"] < 1e-3
