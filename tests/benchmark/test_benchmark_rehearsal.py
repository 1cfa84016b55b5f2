"""CPU rehearsal of the benchmark's command: control flow only.

``benchmark/run.py`` refuses to run off the TPU and has no option to make
it; these tests enter below its platform check with a tiny plan (a tiny
registered model, a tiny traffic file) and drive the same functions: the
real server, the warm-up of the traffic's shapes, the probes against the
plain reference, the child load generator, the window, the arithmetic.
Nothing here is a chip run, and nothing it prints is a device number."""

import dataclasses
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import plan, session, stats
from benchmark.harness.meter import CompileMeter

BENCH = plan.load_benchmark()


def tiny_cell(tmp_path, loop, model="tiny-qwen3"):
    mix = {"loop": loop, "pool": 32, "pool_seed": 1, "preroll_s": 1.0,
           "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 100},
           "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
           "end_to_end": ["out_tok_s"]}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    config = {"model": model, "reduced": [], "chips": 1, "expect": {
        "block_manager": "NativeBlockManager"}, "reference": "dense_gqa",
        "server_args": ["--num-blocks", "256", "--block-size", "8",
                        "--max-blocks-per-seq", "24", "--max-num-seqs", "8",
                        "--multi-step", "4"]}
    return plan.Cell(name="tiny", chips=1, config_name="tiny-" + loop,
                     reference=plan.load_reference(config),
                     config=config, traffic_name="mix", traffic=mix,
                     traffic_path=str(path),
                     params={"clients": 5, "rate": 6.0},
                     end_to_end=("out_tok_s", "setup_s"), per_layer=(),
                     units={"out_tok_s": "tokens/s", "setup_s": "s"})


@pytest.fixture(scope="module")
def meter():
    return CompileMeter()


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_a_tiny_cell_runs_end_to_end(tmp_path, capsys, meter, loop):
    cell = tiny_cell(tmp_path, loop)
    server, url, model = session.build(cell, meter, 2.0)
    try:
        verdict = session.probe(url, model, server.engine, 2**31 + 3,
                                cell.reference)
        run = session.run_window(cell, server, url, model, 2**31 + 3, 2.0,
                                 False, str(tmp_path / "out"), meter)
    finally:
        server.shutdown()
    assert not server.runner._thread.is_alive()
    assert verdict["ok"], verdict
    assert verdict["positions"] == 48
    assert run["compiles_in_window"] == 0, run["new_executables"]
    s = stats.summarize(run["records"], loop, run["t_window"], run["t_end"])
    assert s["attempted"] > 0 and s["failed"] == 0, s["errors"]
    assert s["tokens_in_window"] > 0
    assert stats.end_to_end("out_tok_s", s) > 0
    if loop == "open":
        assert s["attempted"] == 12                       # 6/s for 2 s
        assert stats.percentile(s["ttft_ms"], 95) > 0
    kinds = {step["kind"] for step in run["steps"]}
    assert {"prefill", "window"} <= kinds
    # what a reader needs for a roofline: the file, the cell, the cache
    assert run["config"] is cell.config
    assert run["cell"] == {"name": "tiny", "chips": 1,
                           "params": cell.params}
    import jax
    cfg = server.engine.model_cfg       # K and V of every layer
    itemsize = jax.tree.leaves(server.engine.kv_cache)[0].dtype.itemsize
    assert run["kv_bytes_per_token"] == 2 * cfg.num_layers \
        * cfg.num_kv_heads * cfg.head_dim * itemsize
    readers = plan.discover_layer_metrics()
    run["trace"] = None
    for name, reader in readers.items():
        value = reader.compute(run)
        if reader.SOURCE == "device_trace":
            assert value is None, name      # no trace, so nothing to read
    printed = capsys.readouterr().out
    device_names = [m["name"] for m in BENCH["per_layer"]
                    if m["source"] == "device_trace"] + ["busy_s", "tok_s"]
    for name in device_names:
        assert name not in printed


def test_the_probe_fails_on_other_weights(tmp_path, meter):
    """Scored against weights the server does not run, the probes must
    fail: the tolerance separates right from wrong."""
    from tpuserve.models.weights import init_params
    cell = tiny_cell(tmp_path, "closed", model="tiny-mistral")
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        good = session.probe(url, model, engine, 11, cell.reference)
        other = types.SimpleNamespace(
            model_cfg=engine.model_cfg,
            params=init_params(engine.model_cfg, seed=1234))
        bad = session.probe(url, model, other, 11, cell.reference)
        # a reference that scores fewer rows than tokens were served
        short = types.SimpleNamespace(score_probes=lambda p, c, probes: (
            cell.reference.score_probes(p, c, probes)[:-1]))
        cut = session.probe(url, model, engine, 11, short)
    finally:
        server.shutdown()
    assert good["ok"] and good["logprob_diff_max"] < 0.05
    assert good["positions"] == 48
    assert not bad["ok"] and "disagree" in bad["why"]
    assert not cut["ok"] and "scored (47," in cut["why"]


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "broken"])
def test_a_whole_run_says_correct_only_of_a_sound_served_path(
        tmp_path, monkeypatch, capsys, broken):
    """Below the look for a chip, the rest of a run as the command drives
    it: sound, ``correct`` is true; with every sampled token altered where
    the engine produces it, false, and the sentence says which number
    passed its limit."""
    import time

    import jax

    from tpuserve.runtime.engine import Engine
    from tpuserve.utils import compile_cache
    monkeypatch.setattr(session, "device_info", lambda peaks, chips: {
        "platform": "rehearsal", "kind": "none", "count": chips})
    monkeypatch.setattr(compile_cache, "configure",
                        lambda: str(tmp_path / "cache"))
    if broken:
        sample = Engine._sample_modes

        def altered(self, logits, *args, **kwargs):
            toks = sample(self, logits, *args, **kwargs)
            return (toks + 1) % self.model_cfg.vocab_size
        monkeypatch.setattr(Engine, "_sample_modes", altered)
    kept = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        result = session.measure(
            tiny_cell(tmp_path, "closed"), 2**31 + 5, 2.0, False,
            time.monotonic(), str(tmp_path / "out"), "unused")
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
    assert result["correct"] is (not broken), result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    compared = result["compared"]
    assert "logprob_diff_max" in compared and "(limit 0.1)" in compared
    assert compared.startswith(f"correct {not broken}: ")
    assert compared in capsys.readouterr().out
    if broken:
        gap = float(compared.split("tie_gap_max ")[1].split(" ")[0])
        assert gap > 3 * session.TIE_ATOL


def test_a_configuration_that_misdescribes_what_runs_is_refused(tmp_path):
    cell = tiny_cell(tmp_path, "closed")
    cell.config["hidden_size"] = 4096
    with pytest.raises(SystemExit):
        session.register_configuration(cell)


def no_server(monkeypatch):
    import tpuserve.server.openai_api as api

    def build_server(argv):
        raise AssertionError("the server was built")
    monkeypatch.setattr(api, "build_server", build_server)


def test_a_family_the_reference_does_not_describe_is_refused_before_the_build(
        tmp_path, meter, monkeypatch, capsys):
    no_server(monkeypatch)
    cell = tiny_cell(tmp_path, "closed", model="tiny-moe")
    with pytest.raises(session.Refused):
        session.build(cell, meter, 2.0)
    out = capsys.readouterr().out
    assert "REFUSED" in out and "not the dense-GQA family (mlp" in out


def test_a_moe_size_that_differs_from_what_runs_is_refused_before_the_build(
        tmp_path, meter, monkeypatch, capsys):
    """Under a reference that takes the family, the file's own sizes are
    still held to what runs."""
    no_server(monkeypatch)
    cell = tiny_cell(tmp_path, "closed", model="tiny-moe")
    cell.config.update(num_experts=128, num_experts_per_tok=2)
    cell = dataclasses.replace(cell, reference=types.SimpleNamespace(
        check_family=lambda cfg: None))
    with pytest.raises(session.Refused):
        session.build(cell, meter, 2.0)
    assert "num_experts: file 128, runs 4" in capsys.readouterr().out


def test_a_depth_cut_is_applied_as_data(tmp_path):
    from tpuserve.models.config import get_model_config
    cell = tiny_cell(tmp_path, "closed")
    cell.config.update(reduced=["num_hidden_layers"], num_hidden_layers=1)
    name = session.register_configuration(cell)
    cfg = get_model_config(name)
    assert cfg.num_layers == 1
    assert cfg.hidden_size == get_model_config("tiny-qwen3").hidden_size


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(plan.BENCH_ROOT, "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=plan.REPO_ROOT)
    assert done.returncode != 0
    assert "REFUSED" in done.stdout
    for line in done.stdout.splitlines():
        assert not line.startswith("{"), line


def test_an_unknown_workload_is_an_error():
    done = subprocess.run(
        [sys.executable, os.path.join(plan.BENCH_ROOT, "run.py"),
         "--workload", "no-such-cell", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=plan.REPO_ROOT)
    assert done.returncode != 0 and "no-such-cell" in done.stderr
