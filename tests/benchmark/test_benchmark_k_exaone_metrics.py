"""The five readers of the K-EXAONE cell (``benchmark/layer_metrics/
moe.held_*``, ``moe.away_rows_share``, ``moe.shared_device_share``) on a
built trace: hand-made device events, step records and pages, so every
number below can be worked out on paper; the configuration file against
the catalog; and what PR 41 appended to ``BENCHMARK.json``, found by name
(``accepted.py`` is the accepted benchmark's file and gains no block from
a PR that may only add: this PR's block is ``pr41`` below).  No chip, and
no number here is a measurement."""

import json
import os

import pytest

from benchmark.harness import host_spans, plan
from benchmark.layer_metrics import _moe_held_trace
from tests.benchmark import accepted

V5E = plan.read_json(os.path.join(plan.BENCH_ROOT, "peaks.json"))[
    "devices"]["TPU v5 lite"]
CONFIG = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                     "k-exaone-236b-ep8-l8.json"))
CELL = "k-exaone-236b-ep8-l8.reason"
HELD_NAMES = ("moe.held_gmm_roofline", "moe.held_gmm_device_share",
              "moe.held_gmm_ns_per_row")
NAMES = HELD_NAMES + ("moe.away_rows_share", "moe.shared_device_share")
KERNEL = ("%_moe_grouped_matmul.11 = bf16[96,2048] custom-call(...), "
          "custom_call_target=\"tpu_custom_call\"")
WEIGHTS = 3 * 6144 * 2048 * 2           # one expert's three kernels, bytes
LAYERS = 7                              # expert layers of the cell's 8


def built_ops(kernel_events, phase="decode"):
    """One chip's operations as ``_scope_trace.read_ops`` gives them: a
    ``while`` of 100 ms that holds a fusion and the kernel's calls, each
    ``(start, duration)`` or ``(start, duration, phase)``."""
    pre = "jit(x)/{}/while/body/mlp/moe.experts/"
    ops = [(0, 100_000_000, "%while.3 = while(...)",
            f"jit(x)/{phase}/while", "7"),
           (1_000_000, 21_000_000, "%fusion.12 = bf16[64,6144] fusion(...)",
            pre.format(phase) + "mul", "7")]
    for s, d, *ph in kernel_events:
        ops.append((s, s + d, KERNEL,
                    pre.format(ph[0] if ph else phase) + "pallas_call", "7"))
    return [ops]


def run_with(monkeypatch, kernel_events, steps):
    monkeypatch.setattr(_moe_held_trace.st, "read_ops",
                        lambda path: built_ops(kernel_events))
    monkeypatch.setattr(host_spans, "analyse",
                        lambda run: {"steps_joined": steps})
    import benchmark.harness.session as session
    monkeypatch.setattr(session, "find_xplane", lambda d: "built.xplane.pb")
    return {"trace": {"busy_s": 0.1}, "trace_dir": "x", "config": CONFIG,
            "peaks": V5E, "steps": steps}


def window(rows, steps, held_rows_a_layer, held_hits_a_layer):
    """The step record of a fused decode window over ``rows`` rows of a
    model that holds 16 of 128 experts."""
    return {"kind": "window", "rows": rows, "actual_tokens": rows * steps,
            "moe_rows": rows * 8 * LAYERS * steps,
            "moe_expert_hits": 120 * LAYERS * steps,
            "moe_held_rows": held_rows_a_layer * LAYERS * steps,
            "moe_held_hits": held_hits_a_layer * LAYERS * steps,
            "moe_buffer_rows": 96 * LAYERS * steps,
            "moe_held_pieces": LAYERS * steps}


def test_the_held_readers_on_a_built_trace(monkeypatch):
    """Two decode windows of 2 fused steps over 64 rows, 64 rows landing
    on the 16 held experts a layer, every held expert hit: 2 x 2 x 7 x 64
    = 1,792 held rows and 2 x 2 x 7 x 16 = 448 held expert-layers, in 2 x
    2 x 7 x 3 = 84 calls of 600 us: 50.4 ms of self time.  The work is the
    HELD rows': the 3,584 x 4 routed rows play no part."""
    steps = [window(64, 2, 64, 16), {"kind": "idle", "rows": 0},
             window(64, 2, 64, 16)]
    calls = [(30_000_000 + 650_000 * i, 600_000) for i in range(84)]
    run = run_with(monkeypatch, calls, steps)
    readers = plan.discover_layer_metrics()
    got = {n: readers[n].compute(run) for n in HELD_NAMES}
    assert got["moe.held_gmm_device_share"] == pytest.approx(50.4)
    assert got["moe.held_gmm_ns_per_row"] == pytest.approx(50_400_000 / 1_792)
    flops, nbytes = readers["moe.held_gmm_roofline"].work(CONFIG, 1_792, 448)
    assert flops == 2 * 3 * 6144 * 2048 * 1_792
    assert nbytes == 448 * WEIGHTS + 1_792 * (2 * 6144 + 3 * 2048) * 2
    # memory-bound: 33.9 GB at 819 GB/s is 41.4 ms of the 50.4
    assert nbytes / 819e9 > flops / 197e12
    assert got["moe.held_gmm_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 0.0504)
    assert 82 < got["moe.held_gmm_roofline"] < 83


def test_time_and_work_come_from_the_same_calls(monkeypatch):
    """The capture holds THREE windows' calls (126) and two packed
    prefills' (6 calls of 5 ms), the ``seq`` join two windows and no
    prefill: the work is the joined windows' a call times the calls the
    trace has under ``decode/``, and the prefill's time is left out with
    its rows, so the readings are those of the whole join."""
    steps = [window(64, 2, 64, 16), window(64, 2, 64, 16)]
    calls = [(30_000_000 + 400_000 * i, 300_000) for i in range(126)]
    calls += [(85_000_000 + 1_100_000 * i, 1_000_000, "prefill")
              for i in range(6)]
    run = run_with(monkeypatch, calls, steps)
    m = _moe_held_trace.measure(run)
    assert m["kernel_ns"] == 126 * 300_000
    assert m["rows"] == pytest.approx(1_792 * 1.5)
    assert m["hits"] == pytest.approx(448 * 1.5)
    readers = plan.discover_layer_metrics()
    assert readers["moe.held_gmm_ns_per_row"].compute(run) \
        == pytest.approx(300_000 * 3 * LAYERS * 2 * 2 / 1_792)
    # the same windows summed against the whole trace's kernel time, as
    # the join alone would have it, read a third low
    assert (126 * 300_000 + 6 * 1_000_000) / 1_792 \
        > 1.5 * readers["moe.held_gmm_ns_per_row"].compute(run)
    # a layer-step that took two pieces is six calls
    two = dict(steps[0], moe_held_pieces=2 * LAYERS * 2)
    run = run_with(monkeypatch, calls[:126], [two])
    assert _moe_held_trace.measure(run)["rows"] == pytest.approx(
        896 * 126 / (3 * 28))


def test_an_untouched_held_expert_is_not_counted(monkeypatch):
    """4 rows a layer on 3 held experts: a kernel that reads just those
    three takes 3 x 75.5 MB / 819 GB/s a layer step; counted from the held
    HITS the share is 100 % there and never over."""
    steps = [window(8, 1, 4, 3)]
    least_s = (3 * LAYERS * WEIGHTS
               + 4 * LAYERS * (2 * 6144 + 3 * 2048) * 2) / 819e9
    each = int(least_s * 1e9 / (3 * LAYERS)) + 1     # whole nanoseconds
    run = run_with(monkeypatch, [(30_000_000 + 2 * each * i, each)
                                 for i in range(3 * LAYERS)], steps)
    share = plan.discover_layer_metrics()["moe.held_gmm_roofline"].compute(
        run)
    assert share == pytest.approx(100.0, rel=1e-4) and share <= 100.0


@pytest.mark.parametrize("case", ["no trace", "no such kernel",
                                  "every expert held", "nothing landed"])
def test_nothing_to_read_reads_none(monkeypatch, case):
    """A run without a trace, a program without the kernel, a model that
    holds every expert (Mellum 2's records: no held counts) and a span in
    which no row landed here: None, not a raise."""
    steps = [window(8, 2, 9, 7)]
    calls = [(40_000_000, 2_000_000)]
    if case == "no such kernel":
        calls = []
    if case == "every expert held":
        steps = [{"kind": "window", "rows": 8, "actual_tokens": 16,
                  "moe_rows": 8 * 8 * 12 * 2, "moe_expert_hits": 300}]
    if case == "nothing landed":
        steps = [window(8, 2, 0, 0)]
    run = run_with(monkeypatch, calls, steps)
    if case == "no trace":
        monkeypatch.setattr(host_spans, "analyse", lambda run: None)
        run["trace"] = None
    readers = plan.discover_layer_metrics()
    for name in HELD_NAMES:
        assert readers[name].compute(run) is None, name


def test_the_away_share_is_the_buffers_slack():
    reader = plan.discover_layer_metrics()["moe.away_rows_share"]
    start = {"tpuserve_moe_held_rows_total": 1_000.0,
             "tpuserve_moe_buffer_rows_total": 2_000.0,
             "tpuserve_moe_routed_rows_total": 8_000.0}
    end = {"tpuserve_moe_held_rows_total": 65_000.0,
           "tpuserve_moe_buffer_rows_total": 98_000.0,
           "tpuserve_moe_routed_rows_total": 520_000.0}
    run = {"metrics_start": start, "metrics_end": end}
    assert reader.compute(run) == pytest.approx(100 * (1 - 64 / 96))
    # a layer that moved every pick of an eighth-share: seven in eight
    end["tpuserve_moe_buffer_rows_total"] = 2_000.0 + 512_000.0
    assert reader.compute(run) == pytest.approx(87.5)
    # a program without the counters (the parent; a model that holds
    # every expert, whose counters stay at zero): nothing to read
    old = {"tpuserve_moe_routed_rows_total": 5.0}
    assert reader.compute({"metrics_start": old, "metrics_end": old}) is None
    zero = dict(start, tpuserve_moe_held_rows_total=0.0,
                tpuserve_moe_buffer_rows_total=0.0)
    assert reader.compute({"metrics_start": zero, "metrics_end": zero}) \
        is None


def test_the_shared_experts_share_on_built_operations(monkeypatch):
    """One chip, 100 us busy: a fusion of 30 us under ``moe.shared``, the
    wait for its prefetched weight slice (no ``op_name``: the compiler's)
    of 5 us right before it, 40 us of the dense MLP and 25 us under
    ``moe.experts``.  The accepted reader files the first two under
    ``mlp``; this one reads them as 35 %."""
    reader = plan.discover_layer_metrics()["moe.shared_device_share"]
    pre = "jit(decode_multi)/decode/while/body/mlp/"
    ops = [(0, 40_000, "%fusion.1", pre + "dot_general", "7"),
           (40_000, 45_000, "%slice-done.4", "", "7"),
           (45_000, 75_000, "%fusion.2", pre + "moe.shared/dot_general",
            "7"),
           (75_000, 100_000, "%_moe_grouped_matmul.3",
            pre + "moe.experts/pallas_call", "7")]
    assert reader.part_ns(ops) == (35_000, 100_000)
    from benchmark.layer_metrics import _scope_trace as st
    assert st.scope_of(ops[2][3]) == ("decode", "mlp")
    import benchmark.harness.session as session
    monkeypatch.setattr(session, "find_xplane", lambda d: "built.xplane.pb")
    monkeypatch.setattr(st, "read_ops", lambda path: [ops])
    assert reader.compute({"trace_dir": "x"}) == pytest.approx(35.0)
    # a trace that names no such scope (Mellum 2: no shared expert), and
    # an untraced run
    monkeypatch.setattr(st, "read_ops", lambda path: [ops[:1] + ops[3:]])
    assert reader.compute({"trace_dir": "x"}) is None
    assert reader.compute({"trace_dir": None}) is None


def test_a_cropped_chip_trace_names_no_shared_expert():
    """The recorded Qwen3 trace (``benchmark/fixtures``): a dense model
    from before the scope: the reader reads it and finds nothing."""
    from benchmark.layer_metrics import _scope_trace as st
    reader = plan.discover_layer_metrics()["moe.shared_device_share"]
    path = os.path.join(plan.BENCH_ROOT, "fixtures",
                        "qwen3_batch_scopes_v5e.xplane.pb.gz")
    chips = st.read_ops(path)
    assert chips and all(reader.part_ns(ops)[0] == 0 and
                         reader.part_ns(ops)[1] > 0 for ops in chips)


# ---- the file, the entries ------------------------------------------------

def pr41(bench: dict) -> None:
    """One configuration, one cell and five per-layer entries, after
    everything accepted before them, each entry in the new cell alone."""
    order = accepted.names(bench)
    at = [order.index(name) for name in NAMES]
    assert at == list(range(at[0], at[0] + 5))
    assert at[0] > order.index(accepted.KV_WRITE)
    for name in NAMES:
        assert accepted.entry(bench, name)["workloads"] == [CELL]
        assert accepted.entry(bench, name)["moves"] == "out_tok_s"
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index(CELL) == 4 and configs.index(CELL[:-7]) == 4
    accepted.pr39(bench)
    accepted.pr38(bench)


def test_what_pr41_appended_stands_and_what_was_accepted_with_it():
    from tests.benchmark.test_benchmark_accepted import with_a_fifth
    bench = plan.load_benchmark()
    pr41(bench)
    pr41(with_a_fifth(bench))
    assert plan.lint(bench) == []
    cell = plan.load_cell(CELL, bench)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert set(cell.per_layer) == unlisted | set(NAMES)
    assert cell.end_to_end == ("out_tok_s", "setup_s")
    assert cell.chips == 1 and cell.traffic_name == "reason-closed"
    assert cell.params["clients"] == 72 and cell.params["ramp_s"] == 6
    # the expert kernel's accepted readers list Mellum 2's cell by name
    for name in accepted.MOE + ("kv.window_dead_share",):
        assert name not in cell.per_layer


def test_the_accepted_files_hold_no_share():
    """What ``test_benchmark_share_cut.py::test_the_accepted_files_cut_
    depth_alone_and_state_whole_sizes`` means to hold, of the four accepted
    files BY NAME (that test loops over every configuration, so the fifth,
    which holds a share, fails it: ``tests/conftest.py`` marks it)."""
    bench = plan.load_benchmark()
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for name in accepted.CONFIGS:
        data = plan.read_json(os.path.join(plan.REPO_ROOT, files[name]))
        assert plan.share_cuts(data) == [] and "published" not in data
        assert plan.share_faults(data) == []
        assert set(plan.architecture_overrides(data)) <= {"num_layers"}
    assert plan.share_cuts(CONFIG) == ["num_experts", "vocab_size"]


def test_the_configuration_file_states_the_catalogs_config():
    """Every key of the catalog's ``config`` under the same key: every
    number as published but the depth, the experts held and the vocabulary
    slice; the lists cut with the depth; the published sizes, the
    deployment and what was assumed beside them."""
    from tests.test_k_exaone import catalog_config
    published = catalog_config()
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (8, 16, 19200)
    assert CONFIG["published"] == {key: published[key]
                                   for key in CONFIG["reduced"]}
    cut_lists = ("layer_types", "mlp_layer_types", "sliding_windows")
    for key, value in published.items():
        if key in CONFIG["reduced"]:
            continue
        assert CONFIG[key] == (value[:8] if key in cut_lists else value), key
    assert set(CONFIG["assumed"]) >= {
        "qk_norm", "rotation", "norm_placement", "selection_bias", "mtp",
        "weights", "routing_replay"}
    assert "8 chips" in CONFIG["deployment"]
    assert CONFIG["source"].endswith("K-EXAONE-236B-A23B/blob/main/"
                                     "config.json")
    assert plan.share_faults(CONFIG) == []
    cell = plan.load_cell(CELL, plan.load_benchmark())
    assert plan.unchecked_keys(cell.config, cell.reference) == []


def test_the_file_registers_the_share_and_describes_what_runs():
    """Through ``register_configuration`` as a run makes it: the preset
    with depth, experts held and vocabulary replaced, nothing else; the
    published sizes are the preset's; the reference describes it."""
    import dataclasses

    from benchmark.harness import session
    from tpuserve.models.config import get_model_config
    cell = plan.load_cell(CELL, plan.load_benchmark())
    name = session.register_configuration(cell)
    cfg = get_model_config(name)
    assert cfg == dataclasses.replace(
        get_model_config("LGAI-EXAONE/K-EXAONE-236B-A23B"), name=name,
        num_layers=8, moe_experts_held=16, vocab_size=19200)
    assert cfg.num_experts == 128 and cfg.moe_first_expert == 0
    assert plan.architecture_mismatches(cell.config, cfg,
                                        cell.reference) == []
    # what the file's deployment reckons, counted from the shapes that
    # init_params would draw: 5.98 B parameters, 11.96 GB at 2 bytes
    import jax

    from tpuserve.models.weights import init_params
    shapes = jax.eval_shape(lambda: init_params(cfg, 0))
    sparse = sum(x.size for x in jax.tree.leaves(shapes["layers"][1]))
    assert sparse == 113_246_208 + 37_748_736 + 786_432 + 603_979_776 \
        + 2 * 6144 + 2 * 128 + 128      # norms, q/k norms, selection bias
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 11.95e9 < nbytes < 11.97e9
    text = json.dumps(cell.config["deployment"])
    assert "11.96 GB" in text and "32,768 B a token" in text
