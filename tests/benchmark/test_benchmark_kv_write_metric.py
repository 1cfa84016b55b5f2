"""``kv.prefill_write_device_share``
(``benchmark/layer_metrics/kv.prefill_write_device_share.py``): a prompt's K
and V on their way into the paged cache, as a share of device busy time —
on hand-made events, on the small trace recorded on the chip that keeps the
operations' ``tf_op`` (recorded numbers, not measurements of this machine),
where there is nothing to read, and its entry in ``BENCHMARK.json``."""

import gzip
import os

import jax
import pytest

from benchmark.fixtures.crop_scopes import READERS, plane_text
from benchmark.harness import plan

NAME = "kv.prefill_write_device_share"
FIXTURES = os.path.join(plan.BENCH_ROOT, "fixtures")
ALL_CELLS = ["qwen3-0.6b.batch", "mistral-7b-l16.batch",
             "falcon-h1-34b-l6.reason", "mellum2-12b-l12.batch"]
WRITE = "attn.kv_write/"
# (start ns, end ns, the event's name, its op_name)
PREFILL_OPS = [
    # a row scatter (a fusion) and a page writer (a custom call): both are
    # read by the scope they run under, whatever the operation is
    (0, 300, "%fusion.1 = bf16[] fusion()",
     "jit(forward_ragged)/prefill/" + WRITE + "scatter"),
    (300, 400, "%_paged_kv_write.1 = bf16[] custom-call()",
     "jit(forward_ragged)/prefill/" + WRITE + "_paged_kv_write/pallas_call"),
    (400, 1000, "%fusion.2 = bf16[] fusion()",
     "jit(forward_ragged)/prefill/mlp/dot_general"),
    (1000, 1100, "%fusion.3 = bf16[] fusion()",
     "jit(prefill_chunk)/chunk/" + WRITE + "scatter"),
]
DECODE_OPS = [
    # decode's own write is not a prompt's: trunk.decode_glue_ms reads it
    (1100, 1500, "%fusion.4 = bf16[] fusion()",
     "jit(decode_multi)/decode/while/body/closed_call/" + WRITE + "scatter"),
    (1500, 2000, "%fusion.5 = bf16[] fusion()",
     "jit(decode_multi)/decode/while/body/closed_call/mlp/dot_general"),
]


@pytest.fixture(scope="module")
def reader():
    return plan.discover_layer_metrics(plan.BENCH_ROOT)[NAME]


def _run(tmp_path, ops):
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    text = plane_text("/device:TPU:0", 0, {
        "XLA Ops": [(*op, "P") for op in ops]})
    (trace_dir / "x.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return {"trace_dir": str(trace_dir), "config": {"num_hidden_layers": 1}}


@pytest.mark.parametrize("ops,want", [
    (PREFILL_OPS + DECODE_OPS, 100 * 500 / 2000),
    (PREFILL_OPS, 100 * 500 / 1100),
    (DECODE_OPS, None),                 # no prefill in the span: no share
    (PREFILL_OPS[2:3] + DECODE_OPS, None),      # a prefill that wrote nothing
], ids=["prefill_chunk_decode", "prefill_only", "decode_only", "no_write"])
def test_the_share_on_hand_made_events(reader, tmp_path, ops, want):
    got = reader.compute(_run(tmp_path, ops))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("fixture,want", [
    # prefill/attn.kv_write 0.004834277 s of 0.059813541 s busy, as cut
    ("qwen3_batch_scopes_v5e", 100 * 0.004834277 / 0.059813541),
    ("qwen3_batch_spans_v5e", None),    # cut before the scopes existed
])
def test_the_share_on_a_recorded_trace(reader, tmp_path, fixture, want):
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    with gzip.open(os.path.join(FIXTURES, fixture + ".xplane.pb.gz")) as f:
        (trace_dir / "fixture.xplane.pb").write_bytes(f.read())
    got = reader.compute({"trace_dir": str(trace_dir),
                          "config": {"num_hidden_layers": 28}})
    assert got == (None if want is None else pytest.approx(want, rel=1e-6))
    if want is not None:
        expected = plan.read_json(
            os.path.join(FIXTURES, fixture + ".expected.json"))["scopes"]
        assert got == pytest.approx(
            100 * expected["seconds"]["prefill/attn.kv_write"]
            / expected["busy_s"], rel=1e-9)


@pytest.mark.parametrize("run", [
    {}, {"trace_dir": None}, {"trace_dir": "/nonexistent"}],
    ids=["no_trace", "none", "no_file"])
def test_nothing_to_read_is_none_and_never_raises(reader, run):
    assert reader.compute({"config": {"num_hidden_layers": 28}, "steps": [],
                           **run}) is None


def test_the_entry_is_appended_and_the_benchmark_lints(reader):
    bench = plan.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert bench["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "out_tok_s",
        "workloads": ALL_CELLS}
    assert (reader.UNIT, reader.LAYER, reader.BETTER, reader.MOVES,
            reader.SOURCE) == ("%", "kernels", "lower", "out_tok_s",
                               "device_trace")
    # after everything accepted before it (no pin to the end: the next PR
    # appends after this one too), in cells that report what it moves
    assert all(names.index(NAME) > names.index(r) for r in READERS)
    assert ALL_CELLS == [w["name"] for w in bench["workloads"]]
    moved = {m["name"]: m for m in bench["end_to_end"]}["out_tok_s"]
    assert set(ALL_CELLS) <= set(moved.get("workloads", ALL_CELLS))
    assert plan.lint(bench) == []
