"""The four readers of the Olmo-Hybrid cell (``benchmark/layer_metrics/
lin.*``) on a built trace: hand-made device events and step records, so
every number below can be worked out on paper; the configuration file
against the catalog; the parameter and byte counts the file's deployment
reckons, from the shapes; and what PR 43 appended to ``BENCHMARK.json``,
found by name (``accepted.py`` is the accepted benchmark's file and gains
no block from a PR that may only add: this PR's block is ``pr43`` below).
No chip, and no number here is a measurement."""

import dataclasses
import json
import os

import pytest

from benchmark.harness import host_spans, plan
from benchmark.layer_metrics import _lin_trace
from tests.benchmark import accepted

V5E = plan.read_json(os.path.join(plan.BENCH_ROOT, "peaks.json"))[
    "devices"]["TPU v5 lite"]
CONFIG = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                     "olmo-hybrid-7b-l16.json"))
CELL = "olmo-hybrid-7b-l16.reason"
KERNEL_NAMES = ("lin.state_update_roofline", "lin.state_update_device_share",
                "lin.state_update_ns_per_row")
NAMES = KERNEL_NAMES + ("lin.prefill_scan_device_share",)
KERNEL = ("%_gdn_state_update.5 = (f32[64,15,384], f32[65,15,96,384]) "
          "custom-call(...), custom_call_target=\"tpu_custom_call\"")
LINEAR = 12                             # linear layers of the cell's 16
ROW_LAYER_BYTES = 2 * 30 * 96 * 192 * 4 + (2 * 30 * 96 + 2 * 30 * 192
                                           + 2 * 30) * 4


def built_ops(kernel_events, phase="decode"):
    """One chip's operations as ``_scope_trace.read_ops`` gives them: a
    ``while`` of 100 ms that holds a fusion and the kernel's calls, each
    ``(start, duration)`` or ``(start, duration, phase)``."""
    pre = "jit(decode_multi)/{}/while/body/"
    ops = [(0, 100_000_000, "%while.3 = while(...)",
            f"jit(decode_multi)/{phase}/while", "7"),
           (1_000_000, 21_000_000, "%fusion.12 = bf16[64,3840] fusion(...)",
            pre.format(phase) + "ssm.in_proj/dot_general", "7")]
    for s, d, *ph in kernel_events:
        ops.append((s, s + d, KERNEL, pre.format(ph[0] if ph else phase)
                    + "ssm.scan/pallas_call", "7"))
    return [ops]


def run_with(monkeypatch, kernel_events, steps, config=CONFIG):
    monkeypatch.setattr(_lin_trace.st, "read_ops",
                        lambda path: built_ops(kernel_events))
    monkeypatch.setattr(host_spans, "analyse",
                        lambda run: {"steps_joined": steps})
    import benchmark.harness.session as session
    monkeypatch.setattr(session, "find_xplane", lambda d: "built.xplane.pb")
    return {"trace": {"busy_s": 0.1}, "trace_dir": "x", "config": config,
            "peaks": V5E, "steps": steps}


def window(rows, steps):
    return {"kind": "window", "rows": rows, "actual_tokens": rows * steps}


def test_the_work_of_a_row_layer_is_the_published_sizes():
    """30 heads of 96 x 192 in float32, read and written once, beside the
    row's q, k, v, o and two scalars a head: 4.49 MB, 7 operations a state
    element; memory-bound by two orders, least time 5.5 us."""
    reader = plan.discover_layer_metrics()["lin.state_update_roofline"]
    flops, nbytes = reader.work_per_row_layer(CONFIG)
    assert nbytes == ROW_LAYER_BYTES == 4_423_680 + 69_360
    assert flops == 7 * 30 * 96 * 192
    assert nbytes / 819e9 > 100 * flops / 197e12
    assert 5.4e-6 < nbytes / 819e9 < 5.5e-6


def test_the_kernel_readers_on_a_built_trace(monkeypatch):
    """Two decode windows of 2 fused steps over 60 real rows: 2 x 2 x 12 =
    48 calls of 400 us, each serving 60 row-layers: 2,880 row-layers in
    19.2 ms of self time of 100 ms busy."""
    steps = [window(60, 2), {"kind": "idle", "rows": 0}, window(60, 2)]
    calls = [(30_000_000 + 450_000 * i, 400_000) for i in range(48)]
    run = run_with(monkeypatch, calls, steps)
    readers = plan.discover_layer_metrics()
    got = {n: readers[n].compute(run) for n in KERNEL_NAMES}
    assert got["lin.state_update_device_share"] == pytest.approx(19.2)
    assert got["lin.state_update_ns_per_row"] == pytest.approx(
        19_200_000 / 2_880)
    assert got["lin.state_update_roofline"] == pytest.approx(
        100 * (2_880 * ROW_LAYER_BYTES / 819e9) / 0.0192)
    assert 82 < got["lin.state_update_roofline"] < 83
    # counted from layer_types, never from num_hidden_layers: the log's
    # cross-check divides the calls by the 12 layers that make them
    assert _lin_trace.linear_layers(CONFIG) == LINEAR
    assert CONFIG["num_hidden_layers"] == 16


def test_time_and_work_come_from_the_same_calls(monkeypatch):
    """The capture holds THREE windows' calls (72), the ``seq`` join two
    windows: the work is the joined windows' rows a call times the calls
    the trace has under ``decode/``, so a row reads what it read over the
    whole join.  Windows of unequal rows weigh by their steps."""
    steps = [window(60, 2), window(60, 2)]
    calls = [(30_000_000 + 450_000 * i, 400_000) for i in range(72)]
    run = run_with(monkeypatch, calls, steps)
    m = _lin_trace.measure(run)
    assert m["kernel_ns"] == 72 * 400_000 and m["calls"] == 72
    assert m["row_layers"] == pytest.approx(72 * 60)
    reader = plan.discover_layer_metrics()["lin.state_update_ns_per_row"]
    assert reader.compute(run) == pytest.approx(400_000 / 60)
    # the joined records' own rows x layers against the whole trace's
    # kernel time would have read half again as much
    assert 72 * 400_000 / (2 * 2 * 60 * LINEAR) \
        == pytest.approx(1.5 * reader.compute(run))
    uneven = [window(64, 3), window(32, 1)]
    assert _lin_trace.rows_a_call(uneven) == pytest.approx(
        (64 * 3 + 32) / 4)


def test_the_share_cannot_pass_100_for_a_kernel_at_the_hbm_rate(monkeypatch):
    """A kernel that moved exactly a row-layer's bytes at 819 GB/s reads
    100 %; one that also moved a third more (a state padded to 256 lanes)
    reads 75 %: padding is lost share, never work."""
    steps = [window(64, 1)]
    each = int(64 * ROW_LAYER_BYTES / 819e9 * 1e9) + 1      # whole ns
    run = run_with(monkeypatch, [(30_000_000 + 2 * each * i, each)
                                 for i in range(LINEAR)], steps)
    reader = plan.discover_layer_metrics()["lin.state_update_roofline"]
    share = reader.compute(run)
    assert share == pytest.approx(100.0, rel=1e-4) and share <= 100.0
    padded = int(each * 4 / 3)
    run = run_with(monkeypatch, [(30_000_000 + 2 * padded * i, padded)
                                 for i in range(LINEAR)], steps)
    assert reader.compute(run) == pytest.approx(75.0, rel=1e-3)


@pytest.mark.parametrize("case", ["no trace", "no such kernel",
                                  "no decode recorded",
                                  "prefill calls only"])
def test_nothing_to_read_reads_none(monkeypatch, case):
    """A run without a trace, a program without the kernel (the parent;
    Falcon-H1, whose kernel has another name), a run that recorded no
    decode step at all, a kernel met under another phase alone: None, not
    a raise."""
    steps = [window(8, 2)]
    calls = [(40_000_000, 2_000_000)]
    if case == "no such kernel":
        calls = []
    if case == "no decode recorded":
        steps = [{"kind": "prefill", "rows": 4, "actual_tokens": 900}]
    if case == "prefill calls only":
        calls = [(40_000_000, 2_000_000, "prefill")]
    run = run_with(monkeypatch, calls, steps)
    if case == "no trace":
        monkeypatch.setattr(host_spans, "analyse", lambda run: None)
        run["trace"] = run["trace_dir"] = None
    readers = plan.discover_layer_metrics()
    for name in KERNEL_NAMES:
        assert readers[name].compute(run) is None, name


def test_a_short_capture_reads_rows_from_the_nearest_decode_records(
        monkeypatch):
    """The driver's first check of PR 43 captured 0.58 s of the 2 s it
    asked for.  Where the ``seq`` join of such a span holds no decode
    dispatch, the rows a call come from the decode records stamped inside
    the span, and failing those from the window's: the kernel's calls are
    in the trace, so the three readers read."""
    calls = [(30_000_000 + 450_000 * i, 400_000) for i in range(24)]
    inside = dict(window(60, 2), t=10.5)
    before = dict(window(40, 2), t=3.0)
    run = run_with(monkeypatch, calls, [])
    run.update(steps=[before, inside], trace_span=(10.0, 12.0))
    assert _lin_trace.measure(run)["row_layers"] == pytest.approx(24 * 60)
    run = run_with(monkeypatch, calls, [])
    run.update(steps=[before], trace_span=(10.0, 12.0))
    assert _lin_trace.measure(run)["row_layers"] == pytest.approx(24 * 40)
    readers = plan.discover_layer_metrics()
    for name in KERNEL_NAMES:
        assert readers[name].compute(run) is not None, name
    # a capture inside ONE fused window holds no engine.step span at all
    # (0.36 s on the chip: ``host_spans.analyse`` gives None)
    run = run_with(monkeypatch, calls, [])
    monkeypatch.setattr(host_spans, "analyse", lambda run: None)
    run.update(steps=[before], trace_span=(10.0, 12.0))
    assert _lin_trace.measure(run)["row_layers"] == pytest.approx(24 * 40)
    # the join's own records come first wherever it holds a decode step
    run = run_with(monkeypatch, calls, [window(64, 1)])
    run.update(steps=[before, inside], trace_span=(10.0, 12.0))
    assert _lin_trace.measure(run)["row_layers"] == pytest.approx(24 * 64)


def test_the_prefill_scan_share_on_the_recorded_trace():
    """``lin.prefill_scan_device_share`` reads what ``ssm.prefill_scan_
    device_share`` reads wherever a span holds a prefill: on a reduced
    trace with 3 ms under ``prefill/ssm.scan``, 1 ms under
    ``chunk/ssm.conv`` and 2 ms of ``decode/ssm.scan`` in 100 ms busy,
    4 %.  A span of this cell with NO prefill in it reads 0.0 and not
    None (a reading left out of the result's line refuses the run: the
    driver's first check of PR 43 met such a span); a model without
    linear layers (the recorded Qwen3 trace) and a run without a trace
    read None."""
    from benchmark.layer_metrics import _scope_trace as st
    readers = plan.discover_layer_metrics()
    mine, falcon = (readers["lin.prefill_scan_device_share"],
                    readers["ssm.prefill_scan_device_share"])
    built = {"busy_s": 0.1, "decode_steps": 4, "scopes": {
        ("prefill", "ssm.scan"): 0.003, ("chunk", "ssm.conv"): 0.001,
        ("decode", "ssm.scan"): 0.002, ("prefill", "mlp"): 0.05}}
    run = {st._KEY: built, "config": CONFIG}
    assert mine.compute(run) == falcon.compute(run) == pytest.approx(4.0)
    decode_only = {"busy_s": 0.1, "decode_steps": 4, "scopes": {
        ("decode", "ssm.scan"): 0.02, ("decode", "mlp"): 0.05}}
    run = {st._KEY: decode_only, "config": CONFIG}
    assert mine.compute(run) == 0.0 and falcon.compute(run) is None
    path = os.path.join(plan.BENCH_ROOT, "fixtures",
                        "qwen3_batch_scopes_v5e.xplane.pb.gz")
    dense = {st._KEY: st.reduce(path, 28)}
    assert dense[st._KEY]["busy_s"] > 0
    assert mine.compute(dense) is None
    dense["config"] = plan.read_json(os.path.join(
        plan.BENCH_ROOT, "configs", "qwen3-0.6b.json"))
    assert mine.compute(dense) is None
    assert mine.compute({st._KEY: None, "config": CONFIG}) is None


# ---- the file, the entries ------------------------------------------------

def pr43(bench: dict) -> None:
    """One configuration, one cell and four per-layer entries, after
    everything accepted before them, each entry in the new cell alone."""
    order = accepted.names(bench)
    at = [order.index(name) for name in NAMES]
    assert at == list(range(at[0], at[0] + 4))
    assert at[0] > order.index("moe.shared_device_share")
    for name in NAMES:
        entry = accepted.entry(bench, name)
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["source"], entry["layer"]) == (
            "out_tok_s", "device_trace", "kernels")
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index(CELL) == 5 and configs.index(CELL[:-7]) == 5
    assert cells[4] == "k-exaone-236b-ep8-l8.reason"
    accepted.pr39(bench)
    accepted.pr38(bench)
    accepted.pr35(bench)
    accepted.the_first_four_stand(bench)


def test_what_pr43_appended_stands_and_what_was_accepted_with_it():
    from tests.benchmark.test_benchmark_accepted import with_a_fifth
    from tests.benchmark.test_benchmark_k_exaone_metrics import pr41
    bench = plan.load_benchmark()
    for held in (pr43, pr41):
        held(bench)
        held(with_a_fifth(bench))
    assert plan.lint(bench) == []
    cell = plan.load_cell(CELL, bench)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert set(cell.per_layer) == unlisted | set(NAMES)
    assert cell.end_to_end == ("out_tok_s", "setup_s")
    assert cell.chips == 1 and cell.traffic_name == "reason-closed"
    falcon = plan.load_cell("falcon-h1-34b-l6.reason", bench)
    assert {k: cell.params[k] for k in ("clients", "ramp_s")} \
        == {k: falcon.params[k] for k in ("clients", "ramp_s")} \
        == {"clients": 72, "ramp_s": 6}
    # Falcon-H1's readers list its cell by name, and the other way round
    for name in ("ssm.state_update_roofline", "ssm.device_share",
                 "ssm.prefill_scan_device_share"):
        assert name not in cell.per_layer
    assert not set(NAMES) & set(falcon.per_layer)
    for reader in NAMES:
        mod = plan.discover_layer_metrics()[reader]
        assert (mod.LAYER, mod.MOVES, mod.SOURCE) == (
            "kernels", "out_tok_s", "device_trace")


def catalog_config() -> dict:
    """The catalog's ``config`` of the model (model-configs guide,
    architectures.jsonl), rebuilt from its period: 32 layers of three
    linear-attention layers to one full-attention layer."""
    period = ["linear_attention"] * 3 + ["full_attention"]
    return {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }


def test_the_configuration_file_states_the_catalogs_config():
    """Every key of the catalog's ``config`` under the same key: every
    number as published but the depth; ``layer_types`` cut with the depth
    (four whole periods); the published depth, the deployment and what was
    assumed beside them; no width under ``reduced``."""
    published = catalog_config()
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == 16
    assert CONFIG["published"] == {"num_hidden_layers": 32}
    for key, value in published.items():
        if key == "num_hidden_layers":
            continue
        assert CONFIG[key] == (value[:16] if key == "layer_types"
                               else value), key
    assert CONFIG["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 4
    assert set(CONFIG["assumed"]) >= {
        "head_dim", "rotation", "norm_placement", "qk_norm", "linear_layer",
        "tensor_names", "weights", "state", "kv_cache", "mtp"}
    assert CONFIG["source"].endswith("Olmo-Hybrid-7B/blob/main/config.json")
    assert CONFIG["server_args"] == plan.read_json(os.path.join(
        plan.BENCH_ROOT, "configs", "falcon-h1-34b-l6.json"))["server_args"]
    assert plan.share_faults(CONFIG) == []
    cell = plan.load_cell(CELL, plan.load_benchmark())
    assert plan.unchecked_keys(cell.config, cell.reference) == []
    # the catalog's own row, where the guide is installed
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(rows):
        with open(rows) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["config"] == published
        assert row["source_url"] == CONFIG["source"]


def test_the_file_registers_the_cut_and_the_counts_come_from_the_shapes():
    """Through ``register_configuration`` as a run makes it: the preset
    with its depth replaced, nothing else; the reference describes it.
    Then what the file's deployment reckons, counted from the shapes
    ``init_params`` would draw and the pools ``create_*`` would make."""
    import jax

    from benchmark.harness import session
    from tpuserve.models.config import get_model_config
    from tpuserve.models.weights import init_params
    from tpuserve.runtime.kv_cache import (CacheConfig, bytes_per_block,
                                           ssm_state_bytes)
    cell = plan.load_cell(CELL, plan.load_benchmark())
    name = session.register_configuration(cell)
    cfg = get_model_config(name)
    assert cfg == dataclasses.replace(
        get_model_config("allenai/Olmo-Hybrid-7B"), name=name, num_layers=16)
    assert plan.architecture_mismatches(cell.config, cfg,
                                        cell.reference) == []
    shapes = jax.eval_shape(lambda: init_params(cfg, 0))
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    mlp = 3 * 3840 * 11008
    norms = 2 * 3840
    linear, full = shapes["layers"][0], shapes["layers"][3]
    # q, k 11.06 M each; v, gate, out 22.12 M each; w_a, w_b 0.23 M; the
    # convolution 0.05 M; A_log, dt_bias and the heads' norm weight
    mixer = (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
             + 4 * 11520 + 2 * 30 + 192)
    assert mixer == 88_750_332
    assert size(linear) == mixer + mlp + norms
    assert size(full) == 4 * 3840 * 3840 + 2 * 3840 + mlp + norms
    assert size(linear) == pytest.approx(215.6e6, rel=1e-3)
    assert size(full) == pytest.approx(185.8e6, rel=1e-3)
    head = size({k: v for k, v in shapes.items() if k != "layers"})
    assert head == 2 * 100352 * 3840 + 3840
    assert 3 * size(linear) + size(full) == pytest.approx(832.5e6, rel=1e-3)
    total = size(shapes)
    assert total == 12 * size(linear) + 4 * size(full) + head
    assert total == pytest.approx(4.10e9, rel=2e-3)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 8.19e9 < nbytes < 8.21e9
    assert cfg.num_params == pytest.approx(total, rel=1e-4)
    # the state: 2,211,840 B a seat a layer, 65 seats, 12 layers (and the
    # convolution's memory, 3 x 11,520 float32 a seat a layer): 1.83 GB
    state = ssm_state_bytes(cfg, 64)
    assert state == 12 * 65 * (30 * 96 * 192 * 4 + 3 * 11520 * 4)
    assert 1.82e9 < state < 1.84e9
    # pages: 4 layers, K and V, 32 head rows for the 30 heads, 128, bf16
    cc = CacheConfig(block_size=32, num_blocks=16, max_blocks_per_seq=128)
    assert bytes_per_block(cfg, cc) // 32 == 65_536
    text = json.dumps(cell.config)
    assert "8.20 GB" in text and "65,536 B a token" in text \
        and "1.83 GB" in text
