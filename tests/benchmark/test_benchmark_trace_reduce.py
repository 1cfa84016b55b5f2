"""The reduction from a profiler trace to numbers: its arithmetic on
hand-made intervals, and the whole of it on a small trace recorded on the
chip (``benchmark/fixtures``; recorded numbers, not measurements of this
machine)."""

import json
import os

import pytest

from benchmark.harness import plan, trace_reduce as tr

FIXTURES = os.path.join(plan.BENCH_ROOT, "fixtures")


@pytest.mark.parametrize("intervals,covered,gaps", [
    ([], 0, []),
    ([(0, 10)], 10, []),
    ([(0, 10), (5, 12), (20, 30)], 22, [(12, 8)]),
    ([(20, 30), (0, 10), (10, 20)], 30, []),
    ([(0, 100), (10, 20), (30, 40)], 100, []),            # nested
    ([(0, 1), (3, 4), (9, 10)], 3, [(1, 2), (4, 5)]),
])
def test_union_and_gaps(intervals, covered, gaps):
    assert tr.union_and_gaps(intervals) == (covered, gaps)


def test_self_time_takes_children_off_their_parent():
    events = [(0, 100, "while.1"), (10, 20, "fusion.1"), (30, 50, "fusion.2"),
              (30, 35, "copy.3"), (200, 210, "fusion.9")]
    got = dict()
    for name, dur in tr.self_times(events):
        got[name] = got.get(name, 0) + dur
    assert got == {"while.1": 70, "fusion.1": 10, "fusion.2": 15,
                   "copy.3": 5, "fusion.9": 10}
    assert sum(got.values()) == tr.union_and_gaps(
        [(s, e) for s, e, _ in events])[0]


@pytest.mark.parametrize("name,kind,cls", [
    ("%fusion.123 = bf16[64,1024]{1,0} fusion(bf16[64,1024]{1,0} %p), "
     "kind=kLoop", "fusion", "other"),
    ("%all-reduce.7 = bf16[64,4096]{1,0} all-reduce(bf16[64,4096]{1,0} %x), "
     "replica_groups={{0,1,2,3}}", "all-reduce", "collective"),
    ("%ar.2 = (bf16[8]{0}, bf16[8]{0}) all-reduce-start(bf16[8]{0} %x)",
     "ar", "collective"),
    ("all-reduce-done.2", "all-reduce-done", "collective"),
    ("%_paged_decode_attention.190 = bf16[64,16,128]{2,1,0} custom-call("
     "s32[64,128]{1,0} %a), custom_call_target=\"tpu_custom_call\"",
     "_paged_decode_attention", "kernel"),
    ("%custom-call.41 = f32[8]{0} custom-call(f32[8]{0} %a), "
     "custom_call_target=\"Sharding\"", "custom-call", "other"),
    ("while", "while", "other"),
    ("%reduce-scatter.1 = bf16[16]{0} reduce-scatter(bf16[64]{0} %x)",
     "reduce-scatter", "collective"),
])
def test_operations_are_grouped_by_kind_and_class(name, kind, cls):
    assert tr.op_kind(name) == kind
    assert tr.op_class(name) == cls


def test_gaps_are_named_by_the_programs_around_them():
    modules = [(0, 12, "jit_a(111)"), (20, 30, "jit_b(222)"),
               (40, 90, "jit_a(111)")]
    gaps = [(12, 8), (30, 10), (50, 5), (90, 3)]
    assert tr.name_gaps(gaps, modules) == [
        ("jit_b -> jit_a", 10), ("jit_a -> jit_b", 8), ("jit_a -> jit_a", 5),
        ("jit_a -> end", 3)]


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    from benchmark.harness.session import find_xplane
    path = find_xplane(str(tmp_path))
    assert path is not None
    assert tr.reduce(path) is None           # a CPU trace: no TPU plane


RECORDED = sorted(f for f in os.listdir(FIXTURES)
                  if f.endswith(".xplane.pb.gz"))


@pytest.mark.parametrize("fname", RECORDED)
def test_a_recorded_chip_trace_reduces_to_its_recorded_numbers(fname):
    want = json.load(open(os.path.join(
        FIXTURES, fname.replace(".xplane.pb.gz", ".expected.json"))))
    got = tr.reduce(os.path.join(FIXTURES, fname))
    assert got["chips"] == want["chips"]
    for key in ("busy_s", "window_s", "longest_gap_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["ops"][:5] == [[k, pytest.approx(v, rel=1e-9)]
                              for k, v in want["ops"][:5]]
    assert got["classes"] == pytest.approx(want["classes"], rel=1e-9)
    assert got["gaps"][0][0] == want["gaps"][0][0]
    for name, m in want["modules"].items():
        assert got["modules"][name] == pytest.approx(m, rel=1e-9)
    # every second of self time is inside the busy union
    assert sum(got["classes"].values()) == pytest.approx(
        got["busy_s"], rel=1e-6)


def test_there_is_a_recorded_trace():
    assert RECORDED, "benchmark/fixtures holds no recorded chip trace"
