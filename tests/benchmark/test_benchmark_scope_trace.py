"""Device time by the program's named scopes
(``benchmark/layer_metrics/_scope_trace.py`` and its seven readers): the
filing rules on hand-made events, the readers where there is nothing to
read, the entries in ``BENCHMARK.json``, and the whole of it on a small
trace recorded on the chip that KEEPS the operations' ``tf_op``
(``benchmark/fixtures/crop_scopes.py``; recorded numbers, not measurements
of this machine)."""

import gzip
import os

import jax
import pytest

from benchmark.fixtures.crop_scopes import READERS, plane_text
from benchmark.harness import plan
from benchmark.layer_metrics import _scope_trace as st

FIXTURES = os.path.join(plan.BENCH_ROOT, "fixtures")
FIXTURE = "qwen3_batch_scopes_v5e"
OLD_FIXTURE = "qwen3_batch_spans_v5e"      # cut before the scopes existed
ALL_CELLS = ["qwen3-0.6b.batch", "mistral-7b-l16.batch",
             "falcon-h1-34b-l6.reason", "mellum2-12b-l12.batch"]
CELLS = {name: ALL_CELLS for name in READERS} | {
    "moe.around_gmm_device_share": ["mellum2-12b-l12.batch"],
    "ssm.prefill_scan_device_share": ["falcon-h1-34b-l6.reason"]}
PRE = "jit(decode_multi)/decode/while/body/closed_call/"


def test_the_benchmark_and_the_program_name_the_same_scopes():
    from tpuserve.ops import scopes
    assert st.PHASES == scopes.PHASES
    assert st.PARTS == scopes.PARTS
    from tpuserve.ops.pallas_paged_attention import KERNEL_NAME
    assert st.DECODE_KERNEL == KERNEL_NAME


@pytest.mark.parametrize("op_name,scope", [
    (PRE + "mlp/dot_general", ("decode", "mlp")),
    (PRE + "mlp/moe.route/jit(argsort)/sort", ("decode", "moe.route")),
    (PRE + "mlp/moe.combine/moe.gather/gather", ("decode", "moe.gather")),
    ("jit(forward_ragged)/prefill/attn.kernel/attn.kernel/pallas_call",
     ("prefill", "attn.kernel")),
    ("jit(forward_ragged)/prefill/ssm.scan/while/body/closed_call/ign,jgn->ijg",
     ("prefill", "ssm.scan")),
    ("jit(decode_multi)/decode/while/body/dynamic_update_slice",
     ("decode", "")),
    ("jit(prefill_chunk)/chunk/head/dot_general", ("chunk", "head")),
    ("mlp/jit(silu)/logistic", ("", "mlp")),
    ("jit(_gather_pages)/gather", ("", "")),
    ("jit(decode_multi)/while/body/dot_general", ("", "")),   # no scopes yet
    ("", ("", "")),
])
def test_an_op_name_is_filed_by_its_first_phase_and_its_last_part(op_name,
                                                                 scope):
    assert st.scope_of(op_name) == scope


def _write(tmp_path, text, name="hand"):
    trace_dir = tmp_path / name / "trace"
    trace_dir.mkdir(parents=True)
    (trace_dir / "x.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return {"trace_dir": str(trace_dir), "config": {"num_hidden_layers": 2}}


def test_the_filing_rules_on_hand_made_events(tmp_path, capsys):
    """A ``while`` and its children; the compiler's own operations (no
    ``op_name``) under the next operation of THEIR program that names a
    part, past a bare ``while``; an operation with an ``op_name`` and no
    scope; one with nothing behind it.  Nanoseconds, one chip, two
    layers."""
    k = "%_paged_decode_attention.1 = bf16[] custom-call()"
    ops = [
        # program A: a decode window of two steps
        (0, 1000, "%while.1 = () while()", "jit(decode_multi)/decode/while",
         "A"),
        (0, 100, "%slice-done.1 = bf16[] async-done()", "", "A"),
        (100, 300, "%fusion.1 = bf16[] fusion()", PRE + "attn.qkv/dot", "A"),
        (300, 400, k, PRE + "attn.kernel/pallas_call", "A"),
        (400, 450, k, PRE + "attn.kernel/pallas_call", "A"),
        (450, 500, "%copy.3 = s32[] copy()", "", "A"),
        (500, 600, "%fusion.2 = f32[] fusion()", PRE + "head/dot", "A"),
        (600, 700, k, PRE + "attn.kernel/pallas_call", "A"),
        (700, 800, k, PRE + "attn.kernel/pallas_call", "A"),
        (800, 900, "%dynamic_update_slice.5 = s32[] dynamic-update-slice()",
         "jit(decode_multi)/decode/while/body/dynamic_update_slice", "A"),
        # program B: from before the scopes, interleaved in time with a
        # compiler-made copy of program A that nothing scoped follows
        (1100, 1200, "%fusion.9 = f32[] fusion()", "jit(old)/dot", "B"),
        (1200, 1250, "%copy.4 = s32[] copy()", "", "A"),
        (1300, 1400, "%copy.7 = f32[] copy()", "", "B"),
        (1400, 1500, "%fusion.8 = f32[] fusion()",
         "jit(forward_ragged)/prefill/mlp/dot", "C"),
        # program D: a window's prologue waits for a slice BEFORE its while
        (2000, 2100, "%slice-done.7 = bf16[] async-done()", "", "D"),
        (2100, 2500, "%while.2 = () while()",
         "jit(decode_multi)/decode/while", "D"),
        (2100, 2300, "%fusion.11 = bf16[] fusion()", PRE + "attn.qkv/dot",
         "D"),
        # inside a pipelined loop the compiler names its waits after the loop
        (2300, 2350, "%copy-done.2 = f32[] copy-done()",
         "jit(decode_multi)/decode/while", "D"),
        (2350, 2450, "%fusion.12 = bf16[] fusion()", PRE + "ssm.out/dot",
         "D"),
    ]
    run = _write(tmp_path, plane_text("/device:TPU:0", 0, {"XLA Ops": ops}))
    m = st.measure(run)
    ns = {k: round(v * 1e9) for k, v in m["scopes"].items()}
    assert ns == {
        ("decode", "attn.qkv"): 600,        # two fusions, the waits for them
        ("decode", "ssm.out"): 150,         # and the wait named "while"
        ("decode", "attn.kernel"): 350,
        ("decode", "head"): 150,            # the copy before it is its own
        ("decode", ""): 250,                # the whiles' own, the scan's write
        ("", ""): 250,                      # B's two, and A's last copy
        ("prefill", "mlp"): 100,
    }
    assert round(m["busy_s"] * 1e9) == 1850 == sum(ns.values())
    assert {k: round(v * 1e9) for k, v in m["inherited"].items()} \
        == {"slice-done": 200, "copy": 50, "copy-done": 50}
    assert m["decode_steps"] == 2.0         # four calls over two layers
    readers = plan.discover_layer_metrics(plan.BENCH_ROOT)
    got = {name: readers[name].compute(run) for name in READERS}
    assert got == pytest.approx({
        "trunk.unscoped_device_share": 100 * 500 / 1850,
        "step.prefill_device_share": 100 * 100 / 1850,
        "trunk.decode_proj_ms": 750e-6 / 2,
        "trunk.decode_head_ms": 150e-6 / 2,
        "trunk.decode_glue_ms": 250e-6 / 2,
        "moe.around_gmm_device_share": None,
        "ssm.prefill_scan_device_share": None})
    out = capsys.readouterr().out
    assert "2.00 by the trace" in out and "device time by scope" in out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The fixture unpacked where ``session.find_xplane`` looks, and what
    was read from it when it was cut."""
    tmp = tmp_path_factory.mktemp("scopes")
    trace_dir = tmp / "trace"
    trace_dir.mkdir()
    with gzip.open(os.path.join(FIXTURES, FIXTURE + ".xplane.pb.gz")) as f:
        (trace_dir / "fixture.xplane.pb").write_bytes(f.read())
    expected = plan.read_json(
        os.path.join(FIXTURES, FIXTURE + ".expected.json"))
    return {"trace_dir": str(trace_dir), "config": {
        "num_hidden_layers": expected["scopes"]["layers"]}}, expected


@pytest.mark.parametrize("name", READERS)
def test_a_reader_by_scope(name, recorded, tmp_path):
    """One reader: its entry in ``BENCHMARK.json`` (found by name, its own
    list of cells), what it read from the recorded trace, and None wherever
    there is nothing to read: no trace, no file, a trace cut before the
    program named its scopes."""
    reader = plan.discover_layer_metrics(plan.BENCH_ROOT)[name]
    entry = {m["name"]: m
             for m in plan.load_benchmark()["per_layer"]}[name]
    assert entry == {"name": name, "unit": reader.UNIT,
                     "better": reader.BETTER, "source": "device_trace",
                     "layer": reader.LAYER, "moves": "out_tok_s",
                     "workloads": CELLS[name]}
    run, expected = recorded
    want = expected["scopes"]["metrics"][name]
    got = reader.compute(run)
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))
    for empty in ({}, {"trace_dir": None}, {"trace_dir": "/nonexistent"}):
        assert reader.compute({"config": {"num_hidden_layers": 28},
                               "steps": [], **empty}) is None
    old = tmp_path / "trace"
    old.mkdir()
    with gzip.open(os.path.join(FIXTURES, OLD_FIXTURE + ".xplane.pb.gz")) as f:
        (old / "old.xplane.pb").write_bytes(f.read())
    assert reader.compute({"trace_dir": str(old), "steps": [],
                           "config": {"num_hidden_layers": 28}}) is None


def test_the_recorded_trace_by_scope(recorded):
    run, expected = recorded
    m = st.measure(run)
    want = expected["scopes"]
    assert m["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert {f"{phase}/{part}": s for (phase, part), s in m["scopes"].items()} \
        == pytest.approx(want["seconds"], rel=1e-9)
    assert m["decode_steps"] == pytest.approx(want["decode_steps"])
    # phases and parts add up to the busy time, which is trace_reduce's
    assert sum(m["scopes"].values()) == pytest.approx(m["busy_s"], rel=5e-3)
    assert m["busy_s"] == pytest.approx(expected["busy_s"], rel=5e-3)
    # both phases are in the cut, every part of a dense trunk under decode/
    # (the greedy argmax is fused INTO the head's product: no sample/)
    phases = {phase for phase, _ in m["scopes"]}
    assert {"decode", "prefill"} <= phases
    assert {part for phase, part in m["scopes"] if phase == "decode"} >= {
        "embed", "attn.qkv", "attn.kv_write", "attn.kernel", "attn.out",
        "mlp", "head", "carry"}
    # the wait for a prefetched weight slice has no op_name of its own
    assert m["inherited"].get("slice-done", 0) > 0
    # what names no part is the instrument's error bar: small
    unscoped = st.seconds(m, parts=("",))
    assert 0 < unscoped < 0.05 * m["busy_s"]


def test_what_was_accepted_is_as_it_was():
    """This PR's seven entries are appended, which the pin of
    ``test_benchmark_moe_metrics.py`` (``per_layer[-4:]``) cannot hold, so
    that test is marked from ``tests/conftest.py``.  Every assertion it makes
    is made here, the pin as what it meant: the accepted entries together and
    in their order, the new ones somewhere after them."""
    from benchmark.layer_metrics import _moe_trace
    from tpuserve.ops.pallas_moe_gmm import KERNEL_NAME
    bench = plan.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    moe = ["moe.gmm_device_share", "moe.gmm_ns_per_row", "moe.gmm_roofline"]
    at = names.index(moe[0])
    assert names[at:at + 4] == [*moe, "kv.window_dead_share"]
    # no pin to the end here: the next PR appends after these too
    assert all(names.index(name) >= at + 4 for name in READERS)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in moe:
        assert entries[name]["workloads"] == ["mellum2-12b-l12.batch"]
        assert entries[name]["layer"] == "kernels"
        assert entries[name]["source"] == "device_trace"
    assert entries["kv.window_dead_share"]["workloads"] \
        == ["mellum2-12b-l12.batch"]
    assert entries["kv.window_dead_share"]["source"] == "program_counter"
    assert bench["workloads"][-1]["name"] == "mellum2-12b-l12.batch"
    assert bench["configs"][-1]["name"] == "mellum2-12b-l12"
    assert _moe_trace.KERNEL == KERNEL_NAME
    assert plan.lint(bench) == []
