"""The six readers of the Ling-3.0-flash cell (``benchmark/layer_metrics/
kda.*``, ``moe.group_rows_share``) on a built trace: hand-made device
events and step records, so every number below can be worked out on paper;
the parameter and byte counts the configuration file's deployment reckons,
from the shapes, through ``session.register_configuration``; and what PR 55
appended to ``BENCHMARK.json``, found by name.  No chip, and no number here
is a measurement."""

import os
import types

import pytest

from benchmark.harness import host_spans, plan, session
from benchmark.layer_metrics import _kda_trace, _scope_trace

V5E = plan.read_json(os.path.join(plan.BENCH_ROOT, "peaks.json"))[
    "devices"]["TPU v5 lite"]
CONFIG = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                     "ling-3.0-flash-vl-ep8-l12.json"))
CELL = "ling-3.0-flash-vl-ep8-l12.reason"
KERNEL_NAMES = ("kda.state_update_roofline", "kda.state_update_device_share",
                "kda.state_update_ns_per_row")
SCOPE_NAMES = ("kda.prefill_scan_device_share", "kda.proj_device_share")
NAMES = KERNEL_NAMES + SCOPE_NAMES + ("moe.group_rows_share",)
KERNEL = ("%_kda_state_update.5 = (f32[128,32,128], f32[129,32,128,128]) "
          "custom-call(...), custom_call_target=\"tpu_custom_call\"")
LINEAR = 10                             # Kimi-delta layers of the cell's 12
ROW_LAYER_BYTES = 2 * 32 * 128 * 128 * 4 + (5 * 32 * 128 + 32) * 4


def built_ops(kernel_events, phase="decode"):
    """One chip's operations as ``_scope_trace.read_ops`` gives them: a
    ``while`` of 100 ms that holds the projections' fusion (21 ms under
    ``ssm.in_proj``, 2 ms of it the decay gate's under ``ssm.gate``
    inside), a convolution step, a prefill's scan, and the kernel's calls,
    each ``(start, duration)`` or ``(start, duration, phase)``."""
    pre = "jit(decode_multi)/{}/while/body/jit(_decode_layer)/{}/"
    d = pre.format(phase, phase)
    ops = [(0, 100_000_000, "%while.3 = while(...)",
            f"jit(decode_multi)/{phase}/while", "7"),
           (1_000_000, 20_000_000, "%fusion.12 = f32[128,12288] fusion(...)",
            d + "ssm.in_proj/dot_general", "7"),
           (20_000_000, 22_000_000, "%fusion.13 = f32[128,4096] fusion(...)",
            d + "ssm.in_proj/ssm.gate/dot_general", "7"),
           (22_000_000, 25_000_000, "%fusion.14 = f32[128,4096] fusion(...)",
            d + "ssm.conv/ssm.gate/logistic", "7"),
           (25_000_000, 29_000_000, "%fusion.15 = bf16[128,2560] fusion(...)",
            d + "ssm.out/dot_general", "7"),
           (90_000_000, 93_000_000, "%fusion.16 = f32[8,32,64,64] fusion()",
            "jit(forward_ragged)/prefill/jit(_ragged_layer)/prefill/"
            "ssm.scan/dot_general", "8")]
    for s, dur, *ph in kernel_events:
        ops.append((s, s + dur, KERNEL, pre.format(
            ph[0] if ph else phase, ph[0] if ph else phase)
            + "ssm.scan/pallas_call", "7"))
    return [ops]


def run_with(monkeypatch, kernel_events, steps, config=CONFIG):
    monkeypatch.setattr(_scope_trace, "read_ops",
                        lambda path: built_ops(kernel_events))
    monkeypatch.setattr(host_spans, "analyse",
                        lambda run: {"steps_joined": steps})
    monkeypatch.setattr(session, "find_xplane", lambda d: "built.xplane.pb")
    return {"trace": {"busy_s": 0.1}, "trace_dir": "x", "config": config,
            "peaks": V5E, "steps": steps}


def window(rows, steps):
    return {"kind": "window", "rows": rows, "actual_tokens": rows * steps}


def test_the_work_of_a_row_layer_is_the_published_sizes():
    """32 heads of 128 x 128 in float32, read and written once, beside the
    row's q, k, decay column, v and o and a step size a head: 4.28 MB, 7
    operations a state element; memory-bound by two orders, least time
    5.2 us (5.12 us for the state alone)."""
    flops, nbytes = _kda_trace.work_per_row_layer(CONFIG)
    assert nbytes == ROW_LAYER_BYTES == 2 * 2_097_152 + 82_048
    assert flops == 7 * 32 * 128 * 128
    assert nbytes / 819e9 > 100 * flops / 197e12
    assert 5.2e-6 < nbytes / 819e9 < 5.25e-6
    assert 2 * 2_097_152 / 819e9 == pytest.approx(5.12e-6, rel=1e-3)
    # counted from layer_group_size over the layers that run, never from
    # num_hidden_layers alone: 10 of 12; none for a family without the key
    assert _kda_trace.linear_layers(CONFIG) == LINEAR
    assert CONFIG["num_hidden_layers"] == 12
    olmo = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                       "olmo-hybrid-7b-l16.json"))
    assert _kda_trace.linear_layers(olmo) == 0


def test_the_readers_on_a_built_trace(monkeypatch):
    """Two decode windows of 2 fused steps over 120 real rows: 2 x 2 x 10
    = 40 calls of 800 us, each serving 120 row-layers: 4,800 row-layers in
    32 ms of self time of 100 ms busy; the projections' 28 ms under
    ``decode/`` (the decay gate's parts filed under the parts that enclose
    them); a prefill's 3 ms of scan."""
    steps = [window(120, 2), {"kind": "idle", "rows": 0}, window(120, 2)]
    calls = [(30_000_000 + 850_000 * i, 800_000) for i in range(40)]
    run = run_with(monkeypatch, calls, steps)
    readers = plan.discover_layer_metrics()
    got = {n: readers[n].compute(run) for n in KERNEL_NAMES + SCOPE_NAMES}
    assert got["kda.state_update_device_share"] == pytest.approx(32.0)
    assert got["kda.state_update_ns_per_row"] == pytest.approx(
        32_000_000 / 4_800)
    assert got["kda.state_update_roofline"] == pytest.approx(
        100 * (4_800 * ROW_LAYER_BYTES / 819e9) / 0.032)
    assert 78 < got["kda.state_update_roofline"] < 79
    assert got["kda.proj_device_share"] == pytest.approx(28.0)
    assert got["kda.prefill_scan_device_share"] == pytest.approx(3.0)
    m = _kda_trace.measure(run)
    assert m["calls"] == 40 and m["row_layers"] == pytest.approx(40 * 120)


def test_the_share_cannot_pass_100_for_a_kernel_at_the_hbm_rate(monkeypatch):
    steps = [window(128, 1)]
    each = int(128 * ROW_LAYER_BYTES / 819e9 * 1e9) + 1     # whole ns
    run = run_with(monkeypatch, [(30_000_000 + 2 * each * i, each)
                                 for i in range(LINEAR)], steps)
    reader = plan.discover_layer_metrics()["kda.state_update_roofline"]
    share = reader.compute(run)
    assert share == pytest.approx(100.0, rel=1e-4) and share <= 100.0


@pytest.mark.parametrize("case", ["no trace", "no such kernel",
                                  "no decode recorded", "another family"])
def test_nothing_to_read_reads_none(monkeypatch, case):
    """A run without a trace, a program without the kernel (the parent;
    Olmo-Hybrid, whose kernel has another name), a run that recorded no
    decode step at all, a configuration without Kimi-delta layers: None,
    not a raise."""
    steps = [window(8, 2)]
    calls = [(40_000_000, 2_000_000)]
    config = CONFIG
    if case == "no such kernel":
        calls = []
    if case == "no decode recorded":
        steps = [{"kind": "prefill", "rows": 4, "actual_tokens": 900}]
    if case == "another family":
        config = plan.read_json(os.path.join(
            plan.BENCH_ROOT, "configs", "olmo-hybrid-7b-l16.json"))
    run = run_with(monkeypatch, calls, steps, config)
    if case == "no trace":
        monkeypatch.setattr(host_spans, "analyse", lambda run: None)
        run["trace"] = run["trace_dir"] = None
    readers = plan.discover_layer_metrics()
    for name in KERNEL_NAMES:
        assert readers[name].compute(run) is None, name
    if case in ("no trace", "another family"):
        for name in SCOPE_NAMES:
            assert readers[name].compute(run) is None, name


def test_a_cropped_span_reads_every_reader_as_a_number(monkeypatch):
    """A span cut short: the ``seq`` join holds no decode dispatch and no
    prefill ran in it; the rows a call come from the records stamped
    inside the span, then from the window's; the scan's share reads 0.0,
    not nothing; the counter's reader reads the page."""
    calls = [(30_000_000 + 850_000 * i, 800_000) for i in range(20)]
    inside = dict(window(126, 2), t=10.5)
    before = dict(window(100, 2), t=3.0)
    run = run_with(monkeypatch, calls, [])
    run.update(steps=[before, inside], trace_span=(10.0, 12.0))
    assert _kda_trace.measure(run)["row_layers"] == pytest.approx(20 * 126)
    run = run_with(monkeypatch, calls, [])
    monkeypatch.setattr(host_spans, "analyse", lambda run: None)
    monkeypatch.setattr(
        _scope_trace, "read_ops",
        lambda path: [[op for op in built_ops(calls)[0]
                       if "prefill" not in op[3]]])
    run.update(steps=[before], trace_span=(10.0, 12.0),
               metrics_start={"tpuserve_moe_group_rows_total": 100.0,
                              "tpuserve_moe_routed_rows_total": 1600.0},
               metrics_end={"tpuserve_moe_group_rows_total": 5_100.0,
                            "tpuserve_moe_routed_rows_total": 81_600.0})
    assert _kda_trace.measure(run)["row_layers"] == pytest.approx(20 * 100)
    readers = plan.discover_layer_metrics()
    got = {name: readers[name].compute(run) for name in NAMES}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["kda.prefill_scan_device_share"] == 0.0
    # 5,000 rows whose group survived here of 80,000 / 8 routed: a half
    assert got["moe.group_rows_share"] == pytest.approx(50.0)
    # a program without the counter (the parent, a router without groups)
    run["metrics_end"] = dict(run["metrics_start"])
    assert readers["moe.group_rows_share"].compute(run) is None


def test_the_published_cut_counts_4736_m_parameters_from_its_shapes():
    """The configuration file through ``register_configuration``: 12 of 42
    layers, routing group 0 (64 of 512 experts) held, 19,648 of 157,184
    vocabulary rows, at published widths; the parameter tree's shapes count
    4,736 M (9.47 GB in bf16), the pool 2.90 GB at 128 + 1 seats, the
    latent cache 2 B x 640 lanes x 2 layers a token."""
    import jax

    from tpuserve.models.config import get_model_config
    from tpuserve.models.weights import init_params
    from tpuserve.runtime.kv_cache import (CacheConfig, bytes_per_block,
                                           ssm_state_bytes)
    cell = types.SimpleNamespace(
        config=CONFIG, config_name="ling-3.0-flash-vl-ep8-l12",
        reference=plan.load_reference(CONFIG))
    assert plan.share_faults(CONFIG) == []
    assert plan.unchecked_keys(CONFIG, cell.reference) == []
    cfg = get_model_config(session.register_configuration(cell))
    assert (cfg.num_layers, cfg.moe_experts_held, cfg.num_experts,
            cfg.vocab_size, cfg.moe_first_k_dense, cfg.moe_n_group,
            cfg.moe_topk_group) == (12, 64, 512, 19648, 2, 8, 4)
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.qk_head_dim,
            cfg.mla_kv_lora_rank, cfg.mla_v_head_dim,
            cfg.expert_intermediate_size, cfg.num_experts_per_tok,
            cfg.intermediate_size, cfg.lin_key_head_dim) == (
                2560, 32, 128, 192, 512, 128, 768, 8, 6144, 128)
    # the held experts ARE routing group 0
    assert cfg.moe_experts_held == cfg.num_experts // cfg.moe_n_group
    assert cfg.kv_layers == (5, 11) and len(cfg.state_layers) == LINEAR
    shapes = jax.eval_shape(lambda: init_params(cfg))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))
    assert count(shapes) == 4_736_432_704
    assert round(count(shapes["layers"][0]) / 1e6, 2) == 110.24  # KDA, dense
    assert round(count(shapes["layers"][2]) / 1e6, 2) == 447.75  # KDA, experts
    assert round(count(shapes["layers"][5]) / 1e6, 2) == 416.67  # latent
    assert shapes["layers"][2]["experts"]["up_proj"]["kernel"].shape \
        == (64, 2560, 768)
    assert shapes["layers"][2]["router"]["kernel"].shape == (2560, 512)
    assert shapes["layers"][2]["lin"]["f_proj"]["kernel"].shape \
        == (2560, 4096)
    assert shapes["layers"][5]["attn_gate_proj"]["kernel"].shape \
        == (2560, 32)
    assert shapes["lm_head"]["kernel"].shape == (2560, 19648)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert 9.47e9 < nbytes < 9.48e9
    assert ssm_state_bytes(cfg, 128) == 10 * 129 * (2_097_152 + 147_456) \
        == 2_895_544_320
    assert bytes_per_block(cfg, CacheConfig(block_size=32)) \
        == 32 * 2 * 640 * 2
    argv = CONFIG["server_args"]
    assert argv[argv.index("--max-num-seqs") + 1] == "128"
    assert argv[argv.index("--attn-impl") + 1] == "pallas"
    # fused windows of 16 steps: a burst of 128 x 32 tokens is 1.8 % of a
    # 45 s window's count (``assumed.fused_window``)
    assert argv[argv.index("--multi-step") + 1] == "16"


def test_what_pr_55_appended_is_found_by_name():
    """One configuration, one cell, six per-layer entries, each listing the
    new cell alone, after everything accepted before them; the cell file
    is openPangu's but for its own ``why_clients``."""
    bench = plan.load_benchmark()
    assert plan.lint(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in (
        "kda.state_update_roofline", "kda.state_update_ns_per_row",
        "kda.state_update_device_share", "kda.prefill_scan_device_share",
        "kda.proj_device_share", "moe.group_rows_share")]
    assert at == list(range(at[0], at[0] + 6))
    assert at[0] > names.index("mla.prefill_attn_device_share")
    for i in at:
        assert bench["per_layer"][i]["workloads"] == [CELL]
        assert bench["per_layer"][i]["moves"] == "out_tok_s"
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index(
        "openpangu-ultra-718b-ep16-l7.reason")
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["chips"], entry["traffic"]) == (1, "reason-closed")
    cell = plan.load_cell(CELL, bench)
    pangu = plan.load_cell("openpangu-ultra-718b-ep16-l7.reason", bench)
    assert {k: v for k, v in cell.params.items()
            if k not in ("config", "why_clients")} \
        == {k: v for k, v in pangu.params.items()
            if k not in ("config", "why_clients")}
    assert set(cell.end_to_end) == {"out_tok_s", "setup_s"}
    due = set(cell.per_layer)
    assert set(NAMES) <= due
    # and no reader of another family's kernel is asked of this cell
    assert not {n for n in due if n.startswith(("lin.", "mla.", "ssm."))}
