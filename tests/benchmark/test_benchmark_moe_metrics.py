"""The three ``moe.*`` readers and ``kv.window_dead_share``
(``benchmark/layer_metrics/``) on a built trace: hand-made device events,
step records and polls, so every number below can be worked out on paper.
No chip, and no number here is a measurement."""

import os
import types

import pytest

from benchmark.harness import host_spans, plan
from benchmark.layer_metrics import _moe_trace

V5E = plan.read_json(os.path.join(plan.BENCH_ROOT, "peaks.json"))[
    "devices"]["TPU v5 lite"]
CONFIG = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                     "mellum2-12b-l12.json"))
NAMES = ("moe.gmm_device_share", "moe.gmm_ns_per_row", "moe.gmm_roofline")
KERNEL = ("%_moe_grouped_matmul.11 = bf16[512,896] custom-call(...), "
          "custom_call_target=\"tpu_custom_call\"")
WEIGHTS = 3 * 2304 * 896 * 2            # one expert's three kernels, bytes


def event(name, start, duration):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=duration)


def built_trace(kernel_events):
    """One chip: a ``while`` of 100 ms that holds the kernel's events
    and a fusion, as a fused decode window's loop does."""
    ops = [event("%while.3 = while(...)", 0, 100_000_000),
           event("%fusion.12 = bf16[64,2304] fusion(...)", 1_000_000,
                 20_000_000)]
    ops += [event(KERNEL, s, d) for s, d in kernel_events]
    line = types.SimpleNamespace(name="XLA Ops", events=ops)
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[line])
    host = types.SimpleNamespace(name="/host:CPU", lines=[])
    return types.SimpleNamespace(planes=[device, host])


def run_with(monkeypatch, kernel_events, steps, polls=()):
    monkeypatch.setattr(_moe_trace.tr, "load",
                        lambda path: built_trace(kernel_events))
    monkeypatch.setattr(host_spans, "analyse",
                        lambda run: {"steps_joined": steps})
    import benchmark.harness.session as session
    monkeypatch.setattr(session, "find_xplane", lambda d: "built.xplane.pb")
    return {"trace": {"busy_s": 0.1}, "trace_dir": "x", "config": CONFIG,
            "peaks": V5E, "steps": steps, "polls": list(polls)}


def window(rows, steps, hits_a_step_layer):
    """The step record of a fused decode window over ``rows`` rows."""
    return {"kind": "window", "rows": rows, "actual_tokens": rows * steps,
            "moe_rows": rows * 8 * 12 * steps,
            "moe_expert_hits": hits_a_step_layer * 12 * steps}


def test_the_readers_on_a_built_trace(monkeypatch):
    """Two decode windows of 2 fused steps over 64 rows, every expert hit
    in every layer: 2 x 2 x 12 x 512 = 24,576 routed rows and 2 x 2 x 12 x
    64 = 3,072 expert-layers, in 2 x 2 x 12 x 3 = 144 calls of 400 us:
    57.6 ms of self time."""
    steps = [window(64, 2, 64), {"kind": "idle", "rows": 0},
             window(64, 2, 64)]
    calls = [(30_000_000 + 450_000 * i, 400_000) for i in range(144)]
    run = run_with(monkeypatch, calls, steps)
    readers = plan.discover_layer_metrics()
    got = {n: readers[n].compute(run) for n in NAMES}
    assert got["moe.gmm_device_share"] == pytest.approx(57.6)  # of 100 ms
    assert got["moe.gmm_ns_per_row"] == pytest.approx(57_600_000 / 24_576)
    flops, nbytes = readers["moe.gmm_roofline"].work(CONFIG, 24_576, 3_072)
    assert flops == 2 * 3 * 2304 * 896 * 24_576
    assert nbytes == 3_072 * WEIGHTS + 24_576 * (2 * 2304 + 3 * 896) * 2
    # memory-bound: 38.4 GB at 819 GB/s is 46.9 ms of the 57.6
    assert nbytes / 819e9 > flops / 197e12
    assert got["moe.gmm_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.0576)
    assert 81 < got["moe.gmm_roofline"] < 82


def test_few_rows_on_few_experts_stay_under_the_roofline(monkeypatch):
    """A window of 2 rows touches at most 16 experts a layer.  A kernel
    that reads just those takes 16 x 12.4 MB / 819 GB/s = 0.24 ms a layer
    step; counted from the HITS the share is 100 % there, where a count
    from all 64 experts would read 400 %."""
    steps = [window(2, 1, 16)]
    least_s = (16 * 12 * WEIGHTS + 2 * 8 * 12 * (2 * 2304 + 3 * 896) * 2) \
        / 819e9
    calls = [(30_000_000, int(least_s * 1e9) + 1)]   # whole nanoseconds
    run = run_with(monkeypatch, calls, steps)
    share = plan.discover_layer_metrics()["moe.gmm_roofline"].compute(run)
    assert share == pytest.approx(100.0, rel=1e-5)
    assert share <= 100.0


def test_a_prefill_reads_as_compute_bound(monkeypatch):
    """4,096 packed prompt tokens: 393,216 routed rows over 768
    expert-layers are 4.87 TFLOP (24.7 ms of MXU) against 11.1 GB (13.6 ms
    of HBM): the larger of the two is the least time."""
    steps = [{"kind": "prefill", "rows": 6, "actual_tokens": 3900,
              "moe_rows": 4096 * 8 * 12, "moe_expert_hits": 64 * 12}]
    run = run_with(monkeypatch, [(10_000_000, 40_000_000)], steps)
    reader = plan.discover_layer_metrics()["moe.gmm_roofline"]
    flops, nbytes = reader.work(CONFIG, 4096 * 8 * 12, 64 * 12)
    assert flops / 197e12 > nbytes / 819e9
    assert reader.compute(run) == pytest.approx(
        100 * (flops / 197e12) / 0.040)


@pytest.mark.parametrize("case", ["no trace", "no such kernel",
                                  "no routing counts"])
def test_nothing_to_read_reads_none(monkeypatch, case):
    """A run without a trace, a program without the kernel (the parent, a
    dense model) and step records without routing counts: None, not a
    raise, and the result line leaves the metric out."""
    steps = [window(8, 2, 40)]
    calls = [(40_000_000, 2_000_000)]
    if case == "no such kernel":
        calls = []
    if case == "no routing counts":
        steps = [{"kind": "window", "rows": 8, "actual_tokens": 16}]
    run = run_with(monkeypatch, calls, steps)
    if case == "no trace":
        monkeypatch.setattr(host_spans, "analyse", lambda run: None)
        run["trace"] = None
    readers = plan.discover_layer_metrics()
    for name in NAMES:
        assert readers[name].compute(run) is None, name


def test_the_dead_share_is_the_larger_of_the_windows_two_ends():
    reader = plan.discover_layer_metrics()["kv.window_dead_share"]
    config = {"num_hidden_layers": 12}
    start = {"tpuserve_kv_window_dead_tokens": 48_000.0,
             "tpuserve_kv_pool_tokens": 100_000.0}
    end = {"tpuserve_kv_window_dead_tokens": 108_000.0,
           "tpuserve_kv_pool_tokens": 100_000.0}
    run = {"metrics_start": start, "metrics_end": end, "config": config}
    assert reader.compute(run) == pytest.approx(9.0)
    run = {"metrics_start": end, "metrics_end": start, "config": config}
    assert reader.compute(run) == pytest.approx(9.0)
    # a program from before the gauges: nothing to read
    old = {"vllm_kv_cache_usage_perc": 0.3}
    assert reader.compute({"metrics_start": old, "metrics_end": old,
                           "config": config}) is None


def test_the_entries_name_the_cell_and_the_kernel():
    bench = plan.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == ["mellum2-12b-l12.batch"]
        assert entries[name]["layer"] == "kernels"
        assert entries[name]["source"] == "device_trace"
    assert entries["kv.window_dead_share"]["workloads"] \
        == ["mellum2-12b-l12.batch"]
    assert entries["kv.window_dead_share"]["source"] == "program_counter"
    # appended: the accepted entries come first and are as they were
    assert [m["name"] for m in bench["per_layer"]][-4:] \
        == [*NAMES, "kv.window_dead_share"]
    assert bench["workloads"][-1]["name"] == "mellum2-12b-l12.batch"
    assert bench["configs"][-1]["name"] == "mellum2-12b-l12"
    from tpuserve.ops.pallas_moe_gmm import KERNEL_NAME
    assert _moe_trace.KERNEL == KERNEL_NAME
    assert plan.lint(bench) == []


def test_the_configuration_file_states_the_catalogs_config():
    """Every width as published, depth the one cut, the lists cut with
    it."""
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    want = {"num_hidden_layers": 12, "num_experts": 64,
            "num_experts_per_tok": 8, "moe_intermediate_size": 896,
            "sliding_window": 1024, "vocab_size": 98304,
            "hidden_size": 2304, "intermediate_size": 7168,
            "num_attention_heads": 32, "num_key_value_heads": 4,
            "head_dim": 128, "max_position_embeddings": 131072,
            "norm_topk_prob": True, "tie_word_embeddings": False,
            "max_window_layers": 0, "model_type": "mellum"}
    for key, value in want.items():
        assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 3
    assert CONFIG["mlp_layer_types"] == ["sparse"] * 12
    full = CONFIG["rope_parameters"]["full_attention"]
    assert (full["factor"], full["original_max_position_embeddings"],
            full["beta_fast"], full["beta_slow"]) == (16, 8192, 32, 1)
    assert full["attention_factor"] == 1.2772588722239782
    cell = plan.load_cell("mellum2-12b-l12.batch", plan.load_benchmark())
    assert cell.params["clients"] == 72 and cell.params["ramp_s"] == 6
    assert set(cell.per_layer) >= set(NAMES) | {"kv.window_dead_share",
                                                "kernel.attn_device_share"}
    assert "ssm.device_share" not in cell.per_layer
    assert plan.unchecked_keys(cell.config, cell.reference) == []
