"""The five readers of the openPangu-Ultra-MoE cell (``benchmark/
layer_metrics/mla.*``) on a built trace: hand-made device events and step
records, so every number below can be worked out on paper; the
configuration file against the catalog; and what PR 50 appended to
``BENCHMARK.json``, found by name (``accepted.py`` is the accepted
benchmark's file and gains no block from a PR that may only add: this PR's
block is ``pr50`` below).  No chip, and no number here is a measurement."""

import os

import pytest

from benchmark.harness import host_spans, plan
from benchmark.layer_metrics import _mla_trace
from tests.benchmark import accepted

V5E = plan.read_json(os.path.join(plan.BENCH_ROOT, "peaks.json"))[
    "devices"]["TPU v5 lite"]
CONFIG = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                     "openpangu-ultra-718b-ep16-l7.json"))
CELL = "openpangu-ultra-718b-ep16-l7.reason"
NAMES = ("mla.decode_attn_ns_per_ctx_tok", "mla.decode_attn_roofline",
         "mla.decode_attn_device_share", "mla.proj_device_share",
         "mla.prefill_attn_device_share")
KERNEL = ("%_paged_decode_attention.5 = bf16[128,128,512] custom-call(...), "
          "custom_call_target=\"tpu_custom_call\"")
RAGGED = ("%_ragged_paged_attention.2 = bf16[4096,128,512] custom-call(...)"
          ", custom_call_target=\"tpu_custom_call\"")
LAYERS = 7
OPS, BYTES = 278_528, 1_152             # a cached token a layer


def built_ops(decode_calls, prefill_calls=(), proj_ns=0):
    """One chip's operations as ``_scope_trace.read_ops`` gives them: a
    ``while`` of 100 ms that holds the decode kernel's calls (``(start,
    duration)`` each), a projection under ``decode/attn.qkv`` and one
    under ``decode/attn.out`` of ``proj_ns`` each; the ragged kernel's
    calls under ``prefill/attn.kernel`` after it."""
    ops = [(0, 100_000_000, "%while.3 = while(...)",
            "jit(x)/decode/while", "7")]
    for s, d in decode_calls:
        ops.append((s, s + d, KERNEL, "jit(x)/decode/while/body/closed_call/"
                    "attn.kernel/_paged_decode_attention/pallas_call", "7"))
    if proj_ns:
        ops.append((90_000_000, 90_000_000 + proj_ns,
                    "%fusion.1 = bf16[128,24576] fusion(...)",
                    "jit(x)/decode/while/body/closed_call/attn.qkv/dot", "7"))
        ops.append((95_000_000, 95_000_000 + proj_ns,
                    "%fusion.2 = bf16[128,7680] fusion(...)",
                    "jit(x)/decode/while/body/closed_call/attn.out/dot", "7"))
    for s, d in prefill_calls:
        ops.append((s, s + d, RAGGED, "jit(y)/prefill/attn.kernel/"
                    "_ragged_paged_attention/pallas_call", "8"))
    return [ops]


def run_with(monkeypatch, ops, steps, joined=None, span=None):
    monkeypatch.setattr(_mla_trace.st, "read_ops", lambda path: ops)
    monkeypatch.setattr(host_spans, "analyse", lambda run: {
        "steps_joined": steps if joined is None else joined})
    import benchmark.harness.session as session
    monkeypatch.setattr(session, "find_xplane", lambda d: "built.xplane.pb")
    return {"trace": {"busy_s": 0.1}, "trace_dir": "x", "config": CONFIG,
            "peaks": V5E, "steps": steps, "trace_span": span}


def window(rows, steps, ctx_tokens, t=0.0):
    return {"kind": "window", "rows": rows, "actual_tokens": rows * steps,
            "ctx_tokens": ctx_tokens, "t": t}


def compute(run):
    readers = plan.discover_layer_metrics()
    return {n: readers[n].compute(run) for n in NAMES}


def test_the_work_of_a_cached_token_is_the_published_sizes():
    """2 x 128 x ((512 + 64) + 512) operations and 2 x (512 + 64) bytes a
    cached token a layer, from the configuration's keys: 242 operations a
    byte where the chip's ridge is 197e12 / 819e9 = 240.5, so the larger
    least time is the operations', by under one per cent.  Not the
    layout's 1,280 B (``kv_bytes_per_token`` / 7)."""
    assert _mla_trace.latent_work(CONFIG) == (OPS, BYTES)
    assert OPS / 197e12 > BYTES / 819e9 > 0.99 * OPS / 197e12
    assert _mla_trace.latent_work({"num_attention_heads": 16,
                                   "head_dim": 128}) is None
    assert _mla_trace.latent_work({"kv_lora_rank": 512,
                                   "qk_rope_head_dim": 64}) is None


def test_the_decode_readers_on_a_built_trace(monkeypatch):
    """Two windows of 2 fused steps over 128 rows whose first step attends
    108,800 context tokens: a window attends 2 x 108,800 + 128 x 1 =
    217,728 token-steps, a fused step 108,864 a layer.  28 calls of the
    kernel (4 steps x 7 layers) of 600 us: 16.8 ms of the 100 busy, over
    28 x 108,864 = 3,048,192 token-layers: 5.51 ns each, against 1.414 by
    operations: 25.7 %."""
    steps = [window(128, 2, 108_800), {"kind": "idle", "rows": 0},
             window(128, 2, 108_800)]
    calls = [(10_000_000 + 650_000 * i, 600_000) for i in range(28)]
    run = run_with(monkeypatch, built_ops(calls, proj_ns=1_500_000), steps)
    got = compute(run)
    token_layers = 28 * (2 * 108_800 + 128) / 2
    assert got["mla.decode_attn_device_share"] == pytest.approx(16.8)
    assert got["mla.decode_attn_ns_per_ctx_tok"] == pytest.approx(
        16_800_000 / token_layers)
    assert got["mla.decode_attn_roofline"] == pytest.approx(
        100 * (OPS * token_layers / 197e12) / 0.0168)
    assert 25 < got["mla.decode_attn_roofline"] < 26
    assert got["mla.proj_device_share"] == pytest.approx(3.0)
    assert got["mla.prefill_attn_device_share"] == 0.0


def test_a_kernel_on_the_ridge_cannot_pass_100(monkeypatch):
    """A call that takes exactly the operations' least time reads 100 %,
    and the share is never clipped: half that time reads 200 %, which the
    driver would refuse as a count too high."""
    steps = [window(128, 1, 100_000)]
    least_ns = OPS * 100_000 / 197e12 * 1e9
    for ns, want in ((least_ns, 100.0), (least_ns / 2, 200.0)):
        calls = [(10_000_000 + 2_000_000 * i, ns) for i in range(7)]
        run = run_with(monkeypatch, built_ops(calls), steps)
        assert compute(run)["mla.decode_attn_roofline"] == pytest.approx(
            want, rel=1e-3)


def test_time_and_work_come_from_the_same_calls(monkeypatch):
    """The capture holds THREE windows' calls (42) and a packed prefill's
    7 calls of 2 ms, the ``seq`` join two windows and no prefill: the work
    is a joined step's context times the calls the trace has under
    ``decode/``, the prefill's time is its own reader's."""
    steps = [window(128, 2, 100_000), window(128, 2, 100_000)]
    calls = [(1_000_000 + 650_000 * i, 600_000) for i in range(42)]
    prefill = [(101_000_000 + 2_100_000 * i, 2_000_000) for i in range(7)]
    run = run_with(monkeypatch, built_ops(calls, prefill), steps)
    d = _mla_trace.decode_attention(run)
    assert d["calls"] == 42 and d["ns"] == 42 * 600_000
    assert d["token_layers"] == pytest.approx(42 * (200_000 + 128) / 2)
    got = compute(run)
    # busy: the while's 100 ms and the prefill's 14
    assert got["mla.prefill_attn_device_share"] == pytest.approx(
        100 * 14 / 114)
    assert got["mla.decode_attn_device_share"] == pytest.approx(
        100 * 25.2 / 114)


def test_a_short_capture_reads_context_from_the_nearest_decode_records(
        monkeypatch):
    """A capture whose ``seq`` join holds no decode dispatch takes the
    context of a call from the records stamped inside the span, failing
    those from the window's; a span with no decode call at all reads 0.0,
    not nothing."""
    steps = [window(128, 2, 90_000, t=1.0), window(128, 2, 110_000, t=5.0)]
    calls = [(1_000_000 + 650_000 * i, 600_000) for i in range(14)]
    run = run_with(monkeypatch, built_ops(calls), steps, joined=[],
                   span=(4.0, 6.0))
    assert _mla_trace.decode_attention(run)["token_layers"] \
        == pytest.approx(14 * (220_000 + 128) / 2)
    run = run_with(monkeypatch, built_ops(calls), steps, joined=[],
                   span=(7.0, 8.0))
    assert _mla_trace.decode_attention(run)["token_layers"] \
        == pytest.approx(14 * (400_000 + 256) / 4)
    none = run_with(monkeypatch, built_ops([], [(1_000_000, 2_000_000)]),
                    steps)
    assert compute(none) == {
        "mla.decode_attn_ns_per_ctx_tok": 0.0,
        "mla.decode_attn_roofline": 0.0,
        "mla.decode_attn_device_share": 0.0,
        "mla.proj_device_share": 0.0,
        "mla.prefill_attn_device_share": pytest.approx(2.0)}


@pytest.mark.parametrize("case", ["no trace", "no latent attention",
                                  "a trace that names no scope"])
def test_nothing_to_read_reads_none(monkeypatch, case):
    steps = [window(128, 2, 100_000)]
    calls = [(1_000_000 + 650_000 * i, 600_000) for i in range(14)]
    run = run_with(monkeypatch, built_ops(calls), steps)
    if case == "no trace":
        run["trace_dir"] = None
    elif case == "no latent attention":
        run["config"] = {"num_attention_heads": 16, "head_dim": 128,
                         "num_hidden_layers": 28}
    else:
        monkeypatch.setattr(_mla_trace.st, "read_ops", lambda path: [[
            (0, 1_000_000, "%fusion.1 = fusion(...)", "", "7")]])
    assert set(compute(run).values()) == {None}


def pr50(bench: dict) -> None:
    """One configuration, one cell and the five ``mla.*`` readers: after
    everything accepted before them, together, in their order, in the one
    cell whose model attends latents."""
    order = accepted.names(bench)
    at = order.index(NAMES[0])
    assert order[at:at + 5] == list(NAMES)
    assert at > order.index("lin.prefill_scan_device_share")
    readers = plan.discover_layer_metrics()
    for name in NAMES:
        assert accepted.entry(bench, name) == {
            "name": name, "unit": readers[name].UNIT,
            "better": readers[name].BETTER, "source": "device_trace",
            "layer": readers[name].LAYER, "moves": "out_tok_s",
            "workloads": [CELL]}
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert configs.index("openpangu-ultra-718b-ep16-l7") \
        > configs.index("olmo-hybrid-7b-l16")
    assert cells.index(CELL) > cells.index("olmo-hybrid-7b-l16.reason")
    accepted.the_first_four_stand(bench)


def test_what_pr50_appended_stands_and_what_was_accepted_with_it():
    bench = plan.load_benchmark()
    pr50(bench)
    accepted.pr39(bench)
    accepted.pr38(bench)
    assert plan.lint(bench) == []
    cell = plan.load_cell(CELL, bench)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert set(cell.per_layer) == unlisted | set(NAMES)
    assert cell.end_to_end == ("out_tok_s", "setup_s")
    assert cell.chips == 1 and cell.traffic_name == "reason-closed"
    assert cell.params["clients"] == 136 and cell.params["ramp_s"] == 6
    entry = next(c for c in bench["configs"]
                 if c["name"] == "openpangu-ultra-718b-ep16-l7")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    # the held experts' kernel has readers that list K-EXAONE's cell by
    # name: they are not this cell's (PERF.md section 7)
    assert not any(n.startswith("moe.") for n in cell.per_layer)
    # the traffic is the accepted mix, as it is
    assert cell.traffic == plan.read_json(os.path.join(
        plan.BENCH_ROOT, "traffic", "reason-closed.json"))


def test_the_configuration_file_states_the_catalogs_config():
    """Every key of the catalog's ``config`` under the same key: every
    number as published but the depth, the experts held and the vocabulary
    slice; the published sizes, the deployment and what was assumed beside
    them."""
    from tests.test_openpangu import catalog_config
    published = catalog_config()
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (7, 16, 19200)
    assert CONFIG["published"] == {key: published[key]
                                   for key in CONFIG["reduced"]}
    for key, value in published.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert set(CONFIG["assumed"]) >= {
        "router", "rope_pairing", "norm_placement", "mtp",
        "checkpoint_names", "cut_lists", "weights", "routing_replay",
        "kv_cache"}
    assert "16 chips" in CONFIG["deployment"]
    assert "16 times its share" in CONFIG["deployment"]
    assert "6,161 M parameters, 12.32 GB" in CONFIG["deployment"]
    assert CONFIG["model"] == "FreedomIntelligence/openPangu-Ultra-MoE-718B"
    assert CONFIG["source"].endswith("openPangu-Ultra-MoE-718B/blob/main/"
                                     "config.json")
    assert CONFIG["server_args"] == [
        "--num-blocks", "0", "--max-blocks-per-seq", "128", "--attn-impl",
        "pallas", "--max-num-seqs", "128"]
    assert CONFIG["expect"] == {"attn_impl": "pallas",
                                "block_manager": "NativeBlockManager"}
    assert plan.share_faults(CONFIG) == []
    cell = plan.load_cell(CELL, plan.load_benchmark())
    assert plan.unchecked_keys(cell.config, cell.reference) == []
    assert cell.reference.FIXED == {"sandwich_norm": "sandwich_norms",
                                    "routed_scaling_factor":
                                        "moe_routed_scaling"}
    assert cell.reference.DESCRIPTIVE == ("num_nextn_predict_layers",)
