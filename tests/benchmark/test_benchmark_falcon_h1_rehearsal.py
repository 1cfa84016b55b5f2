"""CPU rehearsal of a Falcon-H1 cell: control flow only.

What ``test_benchmark_rehearsal.py`` does for the dense family, for a
configuration whose file names ``"reference": "falcon_h1"``: the real
server on ``tiny-falcon-h1`` (state-space heads beside attention, a
recurrent state a seat beside the paged cache), the warm-up of the
traffic's shapes, the probes against the family's plain reference, the
child load generator, the window.  Nothing here is a chip run, and
nothing it prints is a device number."""

import json
import types

import pytest

from benchmark.harness import plan, session, stats
from benchmark.harness.meter import CompileMeter


def tiny_cell(tmp_path):
    mix = {"loop": "closed", "pool": 32, "pool_seed": 1, "preroll_s": 1.0,
           "prompt": {"median": 16, "sigma": 0.6, "min": 8, "max": 60},
           "output": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
           "end_to_end": ["out_tok_s"]}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    config = {"model": "tiny-falcon-h1", "reduced": [], "chips": 1,
              "expect": {"block_manager": "NativeBlockManager"},
              "reference": "falcon_h1",
              "server_args": ["--num-blocks", "256", "--block-size", "8",
                              "--max-blocks-per-seq", "24",
                              "--max-num-seqs", "8", "--multi-step", "4",
                              "--kv-cache-dtype", "float32"]}
    return plan.Cell(name="tiny-h1", chips=1, config_name="tiny-h1",
                     reference=plan.load_reference(config),
                     config=config, traffic_name="mix", traffic=mix,
                     traffic_path=str(path), params={"clients": 5},
                     end_to_end=("out_tok_s", "setup_s"), per_layer=(),
                     units={"out_tok_s": "tokens/s", "setup_s": "s"})


@pytest.fixture(scope="module")
def meter():
    return CompileMeter()


def test_a_tiny_falcon_h1_cell_runs_end_to_end(tmp_path, meter):
    cell = tiny_cell(tmp_path)
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        assert engine.ssm_state is not None and engine._packed_prefill
        verdict = session.probe(url, model, engine, 2**31 + 5,
                                cell.reference)
        run = session.run_window(cell, server, url, model, 2**31 + 5, 2.0,
                                 False, str(tmp_path / "out"), meter)
        page = session.scrape(url)
        seats_left = engine.block_manager.seats.in_use
    finally:
        server.shutdown()
    assert verdict["ok"], verdict
    assert verdict["positions"] == 48
    assert verdict["logprob_diff_max"] < 1e-3       # float32 on both sides
    assert run["compiles_in_window"] == 0, run["new_executables"]
    s = stats.summarize(run["records"], "closed", run["t_window"],
                        run["t_end"])
    assert s["attempted"] > 0 and s["failed"] == 0, s["errors"]
    assert stats.end_to_end("out_tok_s", s) > 0
    assert {"prefill", "window"} <= {step["kind"] for step in run["steps"]}
    # the pool is not a leaf of the KV cache: bytes a token stay KV bytes
    cfg = engine.model_cfg
    assert run["kv_bytes_per_token"] == 2 * cfg.num_layers \
        * cfg.num_kv_heads * cfg.head_dim * 4
    assert page["tpuserve_ssm_state_resets_total"] >= s["attempted"]
    assert page["tpuserve_ssm_state_slots"] == seats_left
    # no trace, so the ssm.* readers find nothing to read and say so
    run["trace"] = None
    readers = plan.discover_layer_metrics()
    for name in ("ssm.state_update_ns_per_row", "ssm.state_update_roofline",
                 "ssm.device_share"):
        assert readers[name].compute(run) is None


def test_the_probe_fails_on_other_weights(tmp_path, meter):
    """Scored against weights the server does not run — here only the
    mixer's ``D`` of one layer differs — the probes must fail."""
    import jax
    cell = tiny_cell(tmp_path)
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        good = session.probe(url, model, engine, 11, cell.reference)
        params = jax.tree.map(lambda x: x, engine.params)
        params["layers"][0]["ssm"]["D"] = -params["layers"][0]["ssm"]["D"]
        other = types.SimpleNamespace(model_cfg=engine.model_cfg,
                                      params=params)
        bad = session.probe(url, model, other, 11, cell.reference)
    finally:
        server.shutdown()
    assert good["ok"] and good["positions"] == 48
    assert not bad["ok"] and "disagree" in bad["why"]
