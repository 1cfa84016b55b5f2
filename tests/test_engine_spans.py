"""The engine loop's spans on the profiler's clock (runtime/hostprof.py).

A tiny engine runs prefill and fused-window cycles, some of which evict
prefix blocks into the KV tier, under ``jax.profiler.start_trace`` with
the options the benchmark traces with.  What is pinned: the spans are in
the trace's host plane and join the step records by ``seq``; they come
from the documented set and nest as documented; a demotion is enqueued
under ``kv.demote`` with no wait before the cycle's dispatch, and a wait
for its copy, where there is one, is a ``sync.demote``; ``flush`` is the
cycle's ``sync.*`` time and nothing else; ``ctx_tokens`` is the context the dispatched rows
attend.  CPU run: control flow and counts, no device number."""

import contextlib
import os
import signal
import sys

import jax
import pytest

from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SamplingParams, SchedulerConfig)

from tier_drive import CHURN, cold_twice, tiny_engine

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT_DIR not in sys.path:
    sys.path.insert(0, ROOT_DIR)
from benchmark.harness import session  # noqa: E402
from benchmark.harness.meter import CompileMeter  # noqa: E402
from benchmark.harness.shapes import warm_shapes  # noqa: E402

TIME_LIMIT_S = 240
ROOT = "engine.step"
# span name (or prefix, ending in ".") -> the spans it may open under
TREE = {
    "slo.admission": {ROOT}, "kv.restore": {ROOT}, "schedule": {ROOT},
    "block": {ROOT}, "kv.demote": {ROOT, "kv.restore", "sample"},
    "dispatch": {ROOT}, "dispatch.": {"dispatch", "sample"},
    "sample": {ROOT}, "sync.demote": {"kv.demote", "kv.restore"},
    "sync.": {ROOT, "sample"}, "detokenize": {ROOT}, "step.close": {ROOT},
}
PARAMS = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)


@pytest.fixture(autouse=True)
def time_limit():
    """This file's own limit: a profiler session that hangs must fail
    here, not eat the suite's."""
    def late(signum, frame):
        raise TimeoutError(f"test passed its {TIME_LIMIT_S}s limit")
    old = signal.signal(signal.SIGALRM, late)
    signal.alarm(TIME_LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def kind_of(name):
    if name in TREE or name == ROOT:
        return name
    head = name.split(".", 1)[0] + "."
    return head if head in TREE and "." in name else None


def read_spans(trace_dir):
    """``[(start, end, name, stats)]`` of the loop thread's line: the one
    that holds ``engine.step`` events, the tracer's own events dropped;
    and every name on that line."""
    path = next(os.path.join(root, f) for root, _, files in os.walk(trace_dir)
                for f in files if f.endswith(".xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = [list(line.events) for plane in data.planes
             if plane.name.startswith("/host:") for line in plane.lines]
    loop = [evs for evs in lines if any(e.name == ROOT for e in evs)]
    assert len(loop) == 1, "engine.step must be on exactly one host line"
    spans = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats)) for e in loop[0] if kind_of(e.name)),
                   key=lambda x: (x[0], -x[1]))
    gathers = sorted((e.start_ns, e.start_ns + e.duration_ns)
                     for e in loop[0]
                     if e.name == "PjitFunction(_gather_pages)")
    return spans, {e.name for e in loop[0]}, gathers


def tiered_engine():
    return tiny_engine(True, multi_step=4)


@contextlib.contextmanager
def tracing(trace_dir):
    """A profiler session with the options the benchmark traces with
    (benchmark/harness/session.py)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    eng = tiered_engine()
    prompts = [list(range(2, 26)), [7] * 13]
    # compile outside the trace, and drive both sets cold twice: a first
    # eviction is declined, so only now does the tier hold the prompts
    cold_twice(eng, prompts, PARAMS)
    first = eng.flight.seq
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with tracing(trace_dir):
        eng.generate(prompts, PARAMS)        # evicts the churn's blocks
        eng.generate(CHURN, PARAMS)          # evicts the prompts' blocks
    steps = {s["seq"]: s for s in eng.flight.steps_snapshot(limit=10_000)
             if s["seq"] > first}
    spans, names, gathers = read_spans(trace_dir)
    return {"spans": spans, "names": names, "steps": steps, "engine": eng,
            "prompts": prompts, "gathers": gathers}


def parents(spans):
    """``[(span, its parent or None)]`` by nesting in time."""
    out, stack = [], []
    for span in spans:
        while stack and stack[-1][1] <= span[0]:
            stack.pop()
        out.append((span, stack[-1] if stack else None))
        stack.append(span)
    return out


def test_every_cycle_has_a_span_that_joins_its_step_record(traced):
    roots = [s for s in traced["spans"] if s[2] == ROOT]
    seqs = [s[3]["seq"] for s in roots]
    assert len(seqs) == len(set(seqs)) == len(traced["steps"]) > 8
    assert set(seqs) == set(traced["steps"])
    kinds = {traced["steps"][q]["kind"] for q in seqs}
    assert {"prefill", "prefill_chunk", "window", "idle"} <= kinds


def test_children_come_from_the_documented_set_and_nest(traced):
    seen = set()
    for span, parent in parents(traced["spans"]):
        kind = kind_of(span[2])
        seen.add(kind)
        if kind == ROOT:
            assert parent is None, "cycles do not nest"
            continue
        assert parent is not None, f"{span[2]} outside every engine.step"
        assert parent[2] in TREE[kind] or kind_of(parent[2]) in TREE[kind], \
            f"{span[2]} opened under {parent[2]}"
        assert parent[0] <= span[0] and span[1] <= parent[1] + 1000, \
            f"{span[2]} leaves its parent {parent[2]}"
    assert {ROOT, "slo.admission", "kv.restore", "schedule", "block",
            "kv.demote", "dispatch", "dispatch.", "sample", "sync.",
            "detokenize", "step.close"} <= seen


def test_a_cycle_with_evictions_dispatches_without_waiting_for_the_copy(
        traced):
    """``kv.demote`` brackets the enqueue (before the cycle's dispatch)
    and the landing (before a blocking sync, or going idle); a wait for a
    copy is a ``sync.demote``, and none lies between a gather's enqueue
    and the dispatch that follows it in its cycle (a restore's gather is
    followed by its scatter, which no ``dispatch`` span brackets)."""
    eng = traced["engine"]
    assert eng.stats.kv_demoted_blocks > 0
    spans = traced["spans"]
    waits = [s for s in spans if s[2] == "sync.demote"]
    assert eng.devprof.sync_counts["demote"] >= len(waits)
    launches = [s for s in spans if s[2] == "dispatch"]
    roots = [s for s in spans if s[2] == ROOT]
    followed = 0
    for start, end in traced["gathers"]:
        under = [s for s in spans if s[2] == "kv.demote"
                 and s[0] <= start and end <= s[1]]
        assert under, "a gather enqueued outside kv.demote"
        root = next(r for r in roots if r[0] <= start and end <= r[1])
        launch = min((s for s in launches if end <= s[0] < root[1]),
                     key=lambda s: s[0], default=None)
        if launch is not None:
            followed += 1
            assert not any(end <= w[0] < launch[0] for w in waits), \
                "the loop waited for a copy between a gather and a dispatch"
    assert followed > 2


def test_a_cycle_of_declined_evictions_opens_kv_demote_and_nothing_under_it(
        tmp_path):
    """Prompts that never come back (the benchmark's traffic): every
    eviction is a hash's first, the tier declines it, and the cycle pays
    the ``kv.demote`` span alone: no gather is enqueued, no copy is waited
    for, and the cycle's dispatch follows as if no tier were there."""
    eng = tiered_engine()
    rounds = [[[100 + 10 * r + i] * 40 for i in range(3)] for r in range(3)]
    eng.generate(rounds[0], PARAMS)          # compile outside the trace
    declined = eng.stats.kv_demote_declined_blocks
    with tracing(str(tmp_path)):
        for prompts in rounds[1:]:
            eng.generate(prompts, PARAMS)
    assert eng.stats.kv_demote_declined_blocks > declined + 8
    assert eng.stats.kv_demoted_blocks == 0 and len(eng._kv_tiers) == 0
    spans, names, gathers = read_spans(str(tmp_path))
    opened = {s[2] for s in spans}
    assert "kv.demote" in opened and "dispatch" in opened
    assert "sync.demote" not in opened and not gathers
    assert "PjitFunction(_gather_pages)" not in names
    assert eng.devprof.sync_counts.get("demote", 0) == 0


def test_the_programs_keep_the_names_the_benchmark_matches(traced):
    """``step.decode_device_ms`` sums the ``XLA Modules`` events whose name
    holds ``decode_multi``, and the ledger's gaps are named by program: the
    jitted functions' names are part of the yardstick (the kernels' names
    are pinned where they compile, tests/test_chip_compile.py)."""
    # a batched prefill on a single chip is the ragged trunk's program
    for program in ("decode_multi", "forward_ragged", "prefill_chunk",
                    "_gather_pages", "_scatter_pages", "sample_tokens"):
        assert f"PjitFunction({program})" in traced["names"], program


def test_flush_is_the_cycles_sync_time(traced):
    checked = 0
    for step in traced["steps"].values():
        phases = step.get("phase_ms") or {}
        syncs = sum(v for k, v in phases.items() if k.startswith("sync."))
        assert phases.get("flush", 0.0) == pytest.approx(syncs, abs=1e-3)
        assert (step.get("dev") or {}).get("device_ms", 0.0) == \
            pytest.approx(syncs, abs=1e-3)
        checked += syncs > 0
    assert checked > 4


def test_ctx_tokens_is_the_context_the_dispatched_rows_attend(traced):
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        multi_step=4))
    lens = [len(p) for p in traced["prompts"]]
    eng.generate(traced["prompts"], PARAMS)
    prefill, first, second = eng.flight.steps_snapshot(limit=3)
    assert [s["kind"] for s in (prefill, first, second)] == \
        ["prefill", "window", "window"]
    assert prefill["ctx_tokens"] == prefill["actual_tokens"] == sum(lens)
    # after the prefill's token a row holds len + 1 tokens; the second
    # window is dispatched while the first (4 steps) is still in flight
    assert first["rows"] == second["rows"] == 2
    assert first["ctx_tokens"] == sum(n + 1 for n in lens)
    assert second["ctx_tokens"] == sum(n + 1 + 4 for n in lens)
    # with a cached or restored prefix, a chunk attends what it skipped
    for step in traced["steps"].values():
        if step["kind"] == "prefill_chunk":
            assert step["ctx_tokens"] >= step["actual_tokens"]
        if step["kind"] == "idle":
            assert step["ctx_tokens"] == 0
    assert any(s["kind"] == "prefill_chunk"
               and s["ctx_tokens"] > s["actual_tokens"]
               for s in traced["steps"].values())


# --------------------------------------------------------------------------
# a prefill's first token is read behind the next dispatch (pipelined decode)
# --------------------------------------------------------------------------

def pipelined_engine(**sched):
    return Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=256, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(**{
            "max_num_seqs": 8, "max_prefill_tokens": 256,
            "min_prefill_bucket": 8, "min_decode_bucket": 2,
            "prefill_chunk_size": 16, **sched}),
        enable_prefix_caching=False, multi_step=4, pipeline_decode=True))


def drive(eng, waves, params=PARAMS):
    """Each wave of prompts joins a running batch three cycles after the
    one before: prefills behind windows."""
    for prompts in waves:
        for p in prompts:
            eng.add_request(prompt_token_ids=p, params=params)
        for _ in range(3):
            eng.step()
    while eng.has_work():
        eng.step()
    eng.requests.clear()


WAVES = [[[5, 6, 7], [8, 9, 10, 11]], [list(range(3, 43))],
         [[21, 22, 23, 24, 25]], [[31, 32], [33, 34, 35]]]


def test_no_sync_sample_between_a_prefills_dispatch_and_the_next(tmp_path):
    """The first tokens a prefill samples stay on the device: the host
    opens no ``sync.sample`` for them between the prefill's ``dispatch``
    and the next cycle's ``dispatch``, and reads them right after that
    one, in the same cycle (a second prefill directly after a first reads
    the first's behind its own dispatch)."""
    eng = pipelined_engine()
    drive(eng, WAVES)                    # compile outside the trace
    first = eng.flight.seq
    early = eng.stats.prefill_first_token_flushed_early
    with tracing(str(tmp_path)):
        drive(eng, WAVES)
    assert eng.stats.prefill_first_token_flushed_early == early
    steps = {s["seq"]: s for s in eng.flight.steps_snapshot(limit=10_000)
             if s["seq"] > first}
    spans, _, _ = read_spans(str(tmp_path))
    roots = [s for s in spans if s[2] == ROOT]
    launches = [s for s in spans if s[2] == "dispatch"]
    reads = [s for s in spans if s[2] == "sync.sample"]
    assert reads
    def kind_at(launch):
        root = next(r for r in roots if r[0] <= launch[0] <= r[1])
        return steps[root[3]["seq"]]["kind"]

    checked = behind_a_prefill = 0
    for prev, launch, nxt in zip([None] + launches, launches, launches[1:]):
        if kind_at(launch) not in ("prefill", "prefill_chunk"):
            continue
        between = [r for r in reads if launch[1] <= r[0] < nxt[0]]
        # what a prefill's cycle may read behind its own dispatch is the
        # PREVIOUS prefill's record, never its own
        if prev is None or kind_at(prev) not in ("prefill", "prefill_chunk"):
            assert not between, \
                "the loop read a first token before the next dispatch"
        assert len(between) <= 1
        behind_a_prefill += len(between)
        checked += 1
    assert checked > 4 and behind_a_prefill >= 1
    # and every read follows a dispatch of its own cycle
    for read in reads:
        root = next(r for r in roots if r[0] <= read[0] <= r[1])
        assert any(root[0] <= s[0] and s[1] <= read[0] for s in launches) \
            or steps[root[3]["seq"]]["kind"] == "idle"


def test_a_warm_engine_chains_prefill_into_window_without_a_compile():
    """``compiles_in_window`` has a limit of 0.  After ``Engine.warmup``
    over the traffic's shapes and the harness's ``warm_chained_decode``,
    run as ``session.build`` runs them, a window that takes its rows'
    input tokens from a pending prefill compiles nothing: at every decode
    bucket, from the packed route's token vector and from the chunk
    route's."""
    meter = CompileMeter()
    # a token vector of 16 from the packed route (no decode bucket has
    # that size, so the harness's pairs do not cover it) and of 1 from
    # the chunk route
    eng = pipelined_engine(max_prefill_seqs=16)
    assert eng._prefill_seqs == 16
    shapes = warm_shapes(eng.scheduler, {"prompt_min": 2, "prompt_max": 40,
                                         "output_max": 24, "total_max": 64})
    eng.warmup(sample_modes=("greedy",), **shapes)
    session.warm_chained_decode(eng, shapes["decode_buckets"])
    assert shapes["decode_buckets"] == [2, 4, 8]
    before = meter.snapshot()["requests"]
    # answers long enough that the short rows still run when the long
    # prompt's last chunk joins them
    params = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    short = [[10 * i + j for j in range(2, 5 + i % 3)] for i in range(1, 9)]
    for rows in (2, 3, 5, 8):
        # the packed route: ``rows`` prompts in one prefill, then a window
        drive(eng, [short[:rows]], params)
        assert eng.flight.steps_snapshot(limit=2)[0]["rows"] == rows
        # the chunk route: the last chunk's one token joins rows - 1
        drive(eng, [short[:rows - 1], [list(range(3, 43))]], params)
        assert max(s["rows"] for s in eng.flight.steps_snapshot(limit=8)
                   if s["kind"] == "window") == rows
    assert meter.snapshot()["requests"] == before
    assert eng.stats.prefill_first_token_deferred >= 2 * (2 + 3 + 5 + 8) - 4
    assert eng.stats.prefill_first_token_flushed_early == 0
