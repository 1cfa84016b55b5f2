"""The three ``ssm.*`` readers (``benchmark/layer_metrics/ssm.*.py`` and
their shared ``_ssm_trace.py``) on a built trace: hand-made device events
and step records, so every number below can be worked out on paper.  No
chip, and no number here is a measurement."""

import os
import types

import pytest

from benchmark.harness import host_spans, plan
from benchmark.layer_metrics import _ssm_trace

V5E = plan.read_json(os.path.join(plan.BENCH_ROOT, "peaks.json"))[
    "devices"]["TPU v5 lite"]
CONFIG = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                     "falcon-h1-34b-l6.json"))
NAMES = ("ssm.state_update_ns_per_row", "ssm.state_update_roofline",
         "ssm.device_share")
KERNEL = ("%_ssm_state_update.7 = (f32[64,4,128,8], f32[65,32,128,256]) "
          "custom-call(...), custom_call_target=\"tpu_custom_call\"")


def event(name, start, duration):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=duration)


def built_trace(kernel_events):
    """One chip: a ``while`` of 100 ms that holds the kernel's events
    and a fusion, as a fused decode window's loop does."""
    ops = [event("%while.3 = while(...)", 0, 100_000_000),
           event("%fusion.12 = bf16[64,5120] fusion(...)", 1_000_000,
                 30_000_000)]
    ops += [event(KERNEL, s, d) for s, d in kernel_events]
    line = types.SimpleNamespace(name="XLA Ops", events=ops)
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[line])
    host = types.SimpleNamespace(name="/host:CPU", lines=[])
    return types.SimpleNamespace(planes=[device, host])


def run_with(monkeypatch, kernel_events, steps):
    monkeypatch.setattr(_ssm_trace.tr, "load",
                        lambda path: built_trace(kernel_events))
    monkeypatch.setattr(host_spans, "analyse",
                        lambda run: {"steps_joined": steps})
    import benchmark.harness.session as session
    monkeypatch.setattr(session, "find_xplane", lambda d: "built.xplane.pb")
    return {"trace": {"busy_s": 0.1}, "trace_dir": "x", "config": CONFIG,
            "peaks": V5E, "steps": steps}


def test_the_readers_on_a_built_trace(monkeypatch):
    """Two decode windows of 2 fused steps over 64 and 32 rows and one
    prefill; the kernel ran 6 layers x (128 + 64) rows = 1,152 row-layers
    in 2 x 6 calls of 2 ms: 24 ms of self time."""
    steps = [{"kind": "window", "rows": 64, "actual_tokens": 128},
             {"kind": "prefill", "rows": 3, "actual_tokens": 700},
             {"kind": "window", "rows": 32, "actual_tokens": 64}]
    calls = [(40_000_000 + 2_500_000 * i, 2_000_000) for i in range(12)]
    run = run_with(monkeypatch, calls, steps)
    readers = plan.discover_layer_metrics()
    got = {n: readers[n].compute(run) for n in NAMES}
    assert got["ssm.state_update_ns_per_row"] == pytest.approx(
        24_000_000 / 1152)                                  # 20.8 us
    assert got["ssm.device_share"] == pytest.approx(24.0)   # of 100 ms
    # a row-layer moves 2 x 32 x 128 x 256 x 4 B of state and 50,176 B of
    # inputs and output; 8,438,784 B / 819e9 B/s = 10.3 us against 20.8 us
    roof = readers["ssm.state_update_roofline"]
    flops, nbytes = roof.work_per_row_layer(CONFIG)
    assert nbytes == 2 * 32 * 128 * 256 * 4 + (3 * 32 * 128 + 2 * 2 * 256) * 4
    assert flops == 5 * 32 * 128 * 256
    assert got["ssm.state_update_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / (24_000_000e-9 / 1152))
    assert 49 < got["ssm.state_update_roofline"] < 50


@pytest.mark.parametrize("case", ["no trace", "no such kernel",
                                  "no decode step"])
def test_nothing_to_read_reads_none(monkeypatch, case):
    """A run without a trace, a program without the kernel (the parent, a
    dense model) and a span with no decode dispatch: None, not a raise,
    and the result line leaves the metric out."""
    steps = [{"kind": "window", "rows": 8, "actual_tokens": 16}]
    calls = [(40_000_000, 2_000_000)]
    if case == "no such kernel":
        calls = []
    if case == "no decode step":
        steps = [{"kind": "prefill", "rows": 3, "actual_tokens": 700}]
    run = run_with(monkeypatch, calls, steps)
    if case == "no trace":
        monkeypatch.setattr(host_spans, "analyse", lambda run: None)
        run["trace"] = None
    readers = plan.discover_layer_metrics()
    for name in NAMES:
        assert readers[name].compute(run) is None, name


def test_the_entries_name_the_cell_and_the_kernel():
    bench = plan.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == ["falcon-h1-34b-l6.reason"]
        assert entries[name]["layer"] == "kernels"
        assert entries[name]["source"] == "device_trace"
    from tpuserve.ops.pallas_ssm_update import KERNEL_NAME
    assert _ssm_trace.KERNEL == KERNEL_NAME
    assert plan.lint(bench) == []
