"""Idle time by the engine loop's span (``benchmark/harness/host_spans.py``):
the partition's arithmetic on hand-made intervals, the readers where
there is nothing to read, the entries in ``BENCHMARK.json``, and the whole
of it on a small trace recorded on the chip (``benchmark/fixtures``;
recorded numbers, not measurements of this machine)."""

import json
import os

import pytest

from benchmark.harness import host_spans as hs
from benchmark.harness import plan, roofline, session

FIXTURES = os.path.join(plan.BENCH_ROOT, "fixtures")
NEW = ("idle.kv_demote_share", "idle.host_loop_share", "idle.sync_share",
       "idle.unattributed_share", "kernel.decode_attn_ns_per_ctx_tok")
ROOFLINE = "kernel.decode_attn_roofline"
V5E = plan.read_json(os.path.join(plan.BENCH_ROOT, "peaks.json"))[
    "devices"]["TPU v5 lite"]
STEP = ("engine.step", 0, 1000)


def span(name, start, end):
    return (start, end, name)


@pytest.mark.parametrize("case,gaps,spans,by_span,classes", [
    ("wholly inside one span", [(100, 50)],
     [span(*STEP), span("schedule", 90, 200)],
     {"schedule": 50}, {"host_loop": 50}),
    ("across two spans and the cycle's own time between them", [(100, 100)],
     [span(*STEP), span("schedule", 50, 130), span("dispatch", 160, 400)],
     {"schedule": 30, "engine.step": 30, "dispatch": 40},
     {"host_loop": 70, "unattributed": 30}),
    ("nested spans: the innermost wins", [(100, 100)],
     [span(*STEP), span("kv.restore", 90, 300), span("kv.demote", 110, 250),
      span("sync.demote", 120, 180)],
     {"kv.restore": 10, "kv.demote": 30, "sync.demote": 60},
     {"kv_demote": 100}),
    ("a dispatch's enqueue inside its dispatch span", [(0, 100)],
     [span(*STEP), span("dispatch", 0, 100),
      span("dispatch.decode_multi", 40, 60)],
     {"dispatch": 80, "dispatch.decode_multi": 20}, {"host_loop": 100}),
    ("a sync that is not the demotion's", [(10, 10)],
     [span(*STEP), span("sync.window", 0, 500)],
     {"sync.window": 10}, {"sync": 10}),
    ("outside every span", [(2000, 70)], [span(*STEP)],
     {"": 70}, {"unattributed": 70}),
    ("between two cycles, in the runner", [(990, 40)],
     [span(*STEP), span("runner.route", 1000, 1010),
      span("runner.gauges", 1012, 1020), span("engine.step", 1025, 2000)],
     {"engine.step": 15, "runner.route": 10, "": 7, "runner.gauges": 8},
     {"host_loop": 18, "unattributed": 22}),
    ("a child that outlasts its parent is held inside it", [(0, 300)],
     [span("engine.step", 0, 200), span("detokenize", 150, 260)],
     {"engine.step": 150, "detokenize": 50, "": 100},
     {"host_loop": 50, "unattributed": 250}),
    ("no gap", [], [span(*STEP), span("schedule", 1, 2)], {}, {}),
    ("empty trace", [], [], {}, {}),
])
def test_the_partition(case, gaps, spans, by_span, classes):
    got = hs.partition(gaps, spans)
    assert got["by_span"] == by_span, case
    want = dict.fromkeys(hs.CLASSES, 0) | classes
    assert got["classes"] == want, case
    assert sum(got["classes"].values()) == got["idle_ns"] \
        == sum(n for _, n in gaps)
    if got["idle_ns"]:
        shares = [100.0 * v / got["idle_ns"] for v in got["classes"].values()]
        assert sum(shares) == pytest.approx(100.0, abs=1e-9)


def test_the_longest_gaps_come_first_with_their_spans():
    got = hs.partition([(0, 5), (100, 50), (300, 20)],
                       [span(*STEP), span("block", 90, 400)])
    assert [g[:2] for g in got["longest"]] == [[100, 50], [300, 20], [0, 5]]
    assert got["longest"][0][2] == {"block": 50}
    assert got["longest"][2][2] == {"engine.step": 5}


@pytest.mark.parametrize("name,cls", [
    ("kv.demote", "kv_demote"), ("kv.restore", "kv_demote"),
    ("sync.demote", "kv_demote"), ("sync.window", "sync"),
    ("sync.sample", "sync"), ("runner.intake", "host_loop"),
    ("dispatch", "host_loop"), ("dispatch.prefill", "host_loop"),
    ("step.close", "host_loop"), ("slo.admission", "host_loop"),
    ("engine.step", "unattributed"), ("PjitFunction(decode_multi)", None),
    ("ThreadpoolListener::Record", None), ("flush", None),
])
def test_span_classes(name, cls):
    assert hs.span_class(name) == cls


@pytest.mark.parametrize("step,tokens", [
    ({"kind": "window", "rows": 2, "actual_tokens": 8, "ctx_tokens": 39},
     4 * 39 + 2 * 6),                        # S = 4: 39+41+43+45
    ({"kind": "decode", "rows": 3, "actual_tokens": 3, "ctx_tokens": 30}, 30),
    ({"kind": "prefill", "rows": 2, "actual_tokens": 37, "ctx_tokens": 37}, 0),
    ({"kind": "idle", "rows": 0, "actual_tokens": 0, "ctx_tokens": 0}, 0),
])
def test_context_tokens_a_dispatch_attends(step, tokens):
    assert hs.attended(step) == tokens


@pytest.mark.parametrize("run", [
    {"trace": None, "trace_dir": None, "steps": []},
    {"trace": {"busy_s": 1.0}, "trace_dir": None, "steps": []},
    {"trace": None, "trace_dir": "/nonexistent", "steps": []},
    {"trace": {"busy_s": 1.0}, "trace_dir": "/nonexistent", "steps": []},
], ids=["untraced", "no-trace-dir", "no-reduction", "no-xplane"])
def test_every_reader_returns_none_without_a_trace(run, capsys):
    readers = plan.discover_layer_metrics()
    for name in NEW + (ROOFLINE,):
        assert readers[name].compute(dict(run)) is None, name
    assert capsys.readouterr().out == ""


def test_the_new_entries_are_appended_and_lint_clean():
    bench = plan.load_benchmark()
    assert plan.lint(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    assert tuple(names[first:first + 5]) == NEW
    readers = plan.discover_layer_metrics()
    keys = set(bench["per_layer"][1])
    for m in bench["per_layer"][first:first + 5]:
        assert set(m) - {"workloads"} == keys
        assert m["source"] == readers[m["name"]].SOURCE == "device_trace"
        assert m["moves"] == "out_tok_s"


def unpacked(tmp_path, fixture, steps):
    """A ``run`` as the session hands it to a reader, over a recorded
    trace (unpacked: the session finds an ``.xplane.pb``)."""
    import gzip
    trace_dir = tmp_path / fixture / "trace"
    trace_dir.mkdir(parents=True)
    with gzip.open(os.path.join(FIXTURES, fixture + ".xplane.pb.gz")) as f:
        (trace_dir / "fixture.xplane.pb").write_bytes(f.read())
    return {"trace": {"busy_s": 1.0}, "trace_dir": str(trace_dir),
            "steps": steps}


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(FIXTURES,
                           "qwen3_batch_spans_v5e.expected.json")) as f:
        return json.load(f)["host_spans"]


def test_a_recorded_trace_reads_as_it_did(tmp_path, capsys, expected):
    run = unpacked(tmp_path, "qwen3_batch_spans_v5e", expected["steps"])
    readers = plan.discover_layer_metrics()
    got = {name: readers[name].compute(run) for name in NEW}
    for name in NEW:
        assert got[name] == pytest.approx(expected["metrics"][name],
                                          rel=1e-9), name
    assert sum(got[n] for n in NEW[:4]) == pytest.approx(100.0, abs=1e-6)
    result = hs.analyse(run)
    assert result["idle_ns"] == expected["idle_ns"]
    assert result["by_span"] == expected["by_span"]
    assert {"kv.demote", "sync.demote", "dispatch", "engine.step"} \
        <= set(result["by_span"])
    out = capsys.readouterr().out
    assert out.count("idle time of the busiest chip") == 1   # opened once
    assert "sync.demote" in out and "gap" in out
    assert all(name not in out for name in NEW)
    with open(tmp_path / "qwen3_batch_spans_v5e"
              / "host_spans.steps.json") as f:
        assert json.load(f) == result["steps_joined"]


def test_a_trace_without_spans_or_seq_reads_nothing(tmp_path, expected):
    """The parent commit: its trace has no host spans, its step records
    no ``seq``; each reader then finds nothing, and says nothing."""
    old_steps = [{k: v for k, v in s.items()
                  if k not in ("seq", "ctx_tokens")}
                 for s in expected["steps"]]
    readers = plan.discover_layer_metrics()
    run = unpacked(tmp_path, "qwen3_batch_v5e", old_steps)
    assert all(readers[name].compute(run) is None for name in NEW)
    # spans in the trace, but records from before ``seq``: the shares
    # read, the kernel's cost per token does not
    run = unpacked(tmp_path, "qwen3_batch_spans_v5e", old_steps)
    assert readers[NEW[0]].compute(run) is not None
    assert readers[NEW[4]].compute(run) is None


@pytest.mark.parametrize("seconds,flops,nbytes,want", [
    (1.0, 0.0, 819e9, 1.0),                  # the memory rate bounds it
    (2.0, 197e12, 0.0, 0.5),                 # the operation rate does
    (1.0, 197e12, 2 * 819e9, 2.0),           # over 1 is never clipped
    (140.0e-9 / 0.69, 229376.0, 114688.0, 0.69),
    (0.0, 1.0, 1.0, None), (1.0, 0.0, 0.0, None),
])
def test_a_share_of_the_roofline(seconds, flops, nbytes, want):
    got = roofline.share(seconds, flops, nbytes, V5E)
    assert got == (want if want is None else pytest.approx(want, rel=1e-3))


def qwen3_run(tmp_path, expected):
    """The recorded trace as the session hands it over since PR 26: with
    the configuration file, the cache's bytes a token and the peaks."""
    run = unpacked(tmp_path, "qwen3_batch_spans_v5e", expected["steps"])
    config = plan.load_cell("qwen3-0.6b.batch", plan.load_benchmark()).config
    run.update(config=config, peaks=V5E, chips=1, kv_bytes_per_token=float(
        2 * config["num_hidden_layers"] * config["num_key_value_heads"]
        * config["head_dim"] * 2))
    return run


def test_the_decode_kernels_share_of_its_roofline(tmp_path, expected):
    """Recorded on the chip (not a measurement of this machine): the
    cache's bytes a context token over the HBM rate, over the kernel's
    time a context token."""
    run = qwen3_run(tmp_path, expected)
    assert run["kv_bytes_per_token"] == 114688
    readers = plan.discover_layer_metrics()
    ns = readers[NEW[4]].compute(run)
    got = readers[ROOFLINE].compute(run)
    assert got == pytest.approx(100 * 114688 / 819e9 / (ns * 1e-9), rel=1e-9)
    assert 55 < got < 80
    flops, nbytes = readers[ROOFLINE].work_per_ctx_token(run)
    assert (flops, nbytes) == (4.0 * 16 * 128 * 28, 114688.0)
    # a cache sharded over four chips: a chip reads its part
    assert readers[ROOFLINE].work_per_ctx_token(dict(run, chips=4)) == \
        (flops / 4, nbytes / 4)
    for missing in ("kv_bytes_per_token", "peaks"):
        assert readers[ROOFLINE].compute(
            {k: v for k, v in run.items() if k != missing}) is None


def test_the_breakdowns_idle_gaps_are_by_span(tmp_path, expected):
    run = qwen3_run(tmp_path, expected)
    run["trace"]["gaps"] = [["jit_a -> jit_b", 0.5]]
    gaps = session.idle_gaps(run)
    assert len(gaps) == 10 and gaps == sorted(gaps, key=lambda g: -g[1])
    by_span = {k or session.NO_SPAN: v * 1e-9
               for k, v in expected["by_span"].items()}
    assert dict(gaps) == {k: by_span[k] for k, _ in gaps}
    assert {"kv.demote", "dispatch"} <= set(dict(gaps))
    assert all(plan.NAME.match(name) for name, _ in gaps)
    # a trace from before the spans: by the programs around each gap
    old = unpacked(tmp_path, "qwen3_batch_v5e", [])
    old["trace"]["gaps"] = [["jit_a -> jit_b", 0.5]] * 12
    assert session.idle_gaps(old) == [["jit_a -> jit_b", 0.5]] * 10


def test_the_counters_that_moved_over_the_window():
    start = {"kv_blocks_demoted_total": 10.0, "same_total": 3.0,
             "latency_bucket": 5.0, "latency_sum": 1.5, "latency_count": 2.0,
             "running": 2.0}
    end = {"kv_blocks_demoted_total": 74.0, "same_total": 3.0,
           "latency_bucket": 9.0, "latency_sum": 4.0, "latency_count": 6.0,
           "running": 1.0, "new_total": 4.0}
    assert session.moved_counters(start, end) == {
        "kv_blocks_demoted_total": 64.0, "latency_sum": 2.5,
        "latency_count": 4.0, "new_total": 4.0}
