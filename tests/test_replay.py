"""Trace-driven replay harness (ISSUE 11, ROADMAP item 5).

Tier-1 keeps the determinism pin and a small bundle round-trip (the
suite runs near the 870s driver budget — engines here use minimal
buckets and single-digit token counts; two replays share every compiled
shape in-process).  The full storm replays — a REAL runner-produced
fault-storm post-mortem and the seeded 2x-overload chaos soak — are
``slow``/``chaos``-marked and excluded from tier-1.
"""

from __future__ import annotations

import json
import os

import pytest

from tpuserve.replay import (ReplayOptions, Workload, WorkloadRequest,
                             diff_report, replay, workload_from_bundle)
from tpuserve.runtime.flight import FLIGHT_SCHEMA_VERSION, FlightRecorder


def _workload(n=6, span_s=75.0, seed=5, faults=None, classes=True,
              prefix_group=None, max_tokens=4):
    reqs = []
    for i in range(n):
        reqs.append(WorkloadRequest(
            request_id=f"wl-{i}",
            arrival_s=round(i * span_s / max(1, n - 1), 3) if n > 1 else 0.0,
            prompt_tokens=6,
            max_tokens=max_tokens,
            slo_class=(("interactive", "standard", "batch")[i % 3]
                       if classes else "standard"),
            seed=i,
            prefix_group=prefix_group if prefix_group and i % 2 else None,
            prefix_tokens=4 if prefix_group and i % 2 else 0))
    return Workload(requests=reqs, seed=seed, faults=faults)


# ---------------------------------------------------------------------
# tier-1: the determinism pin (acceptance criterion)
# ---------------------------------------------------------------------

def test_replay_determinism_same_seed_identical_tokens_and_sli():
    """ACCEPTANCE: same workload + same seed => identical token streams
    AND identical SLI summary, across two fully fresh engines — with a
    fault rule armed and a shared-prefix conversation in the mix, and
    the sparse 75-virtual-second arrival span replaying >=10x faster
    than the incident's wall span."""
    wl = _workload(faults="decode_dispatch:raise:1.0:count=1:match=wl-3,"
                          "seed=5", prefix_group="conv")
    r1 = replay(wl, ReplayOptions())
    r2 = replay(wl, ReplayOptions())
    assert r1["token_digest"] == r2["token_digest"]
    assert r1["sli_digest"] == r2["sli_digest"]
    assert r1["token_streams"] == r2["token_streams"]
    assert r1["sli"] == r2["sli"]
    assert any(r1["token_streams"].values()), "replay generated nothing"
    # the armed fault actually fired and was salvaged, deterministically
    assert r1["counters"]["salvage_rounds"] == \
        r2["counters"]["salvage_rounds"] >= 1
    # every request reached exactly one terminal state
    assert set(r1["outcomes"]) == {r.request_id for r in wl.requests}
    assert set(r1["outcomes"].values()) == {"length"}
    # virtual time >=10x faster than the recorded span (idle gaps jump)
    assert r1["speedup"] >= 10, (r1["virtual_s"], r1["wall_s"])
    # per-class SLI families are populated like production's
    for cls in ("interactive", "standard", "batch"):
        assert r1["sli"][cls]["ttft"]["n"] >= 1
        assert r1["sli"][cls]["e2e"]["n"] >= 1


def test_bundle_roundtrip_extract_and_diff(tmp_path):
    """A replay run captures its own flight bundle; the bundle extracts
    back into a workload whose shape matches the source, replays, and
    diffs per-class SLI families directly against the bundle's SLIs."""
    src = _workload(n=4, span_s=30.0, seed=7)
    bundle_path = str(tmp_path / "bundle.json")
    r_src = replay(src, ReplayOptions(dump_bundle_path=bundle_path))
    with open(bundle_path) as f:
        bundle = json.load(f)
    assert bundle["schema"] == FLIGHT_SCHEMA_VERSION
    assert bundle["rings"]["events"]["dropped"] == 0
    assert bundle["engine"]["max_num_seqs"] >= 1
    wl = workload_from_bundle(bundle, seed=7)
    assert {r.request_id for r in wl.requests} == \
        {r.request_id for r in src.requests}
    by_id = {r.request_id: r for r in wl.requests}
    for r in src.requests:
        got = by_id[r.request_id]
        assert got.prompt_tokens == r.prompt_tokens
        assert got.max_tokens == r.max_tokens      # finished: output len
        assert got.slo_class == r.slo_class
        assert got.source_outcome == "length"
    # arrivals reproduce the recorded process (stamped at cycle end, so
    # within one modelled step of the scheduled offsets)
    step = r_src["step_time_s"]
    for r in src.requests:
        assert abs(by_id[r.request_id].arrival_s - r.arrival_s) <= \
            2 * step + 1e-6
    rep = replay(wl, ReplayOptions())
    diff = diff_report(rep, wl)
    for cls in ("interactive", "standard", "batch"):
        e = diff["sli"][cls]["ttft"]
        assert e["source"] and e["replay"] and "ratio_p50" in e
    assert diff["replay_outcomes"] == {"length": 4}
    assert diff["source_outcomes"] == {"length": 4}


# ---------------------------------------------------------------------
# tier-1: schema + integrity guards (no engine builds)
# ---------------------------------------------------------------------

def test_workload_schema_guards():
    wl = _workload(n=2)
    data = wl.as_dict()
    # round trip
    back = Workload.from_dict(json.loads(json.dumps(data)))
    assert [r.request_id for r in back.requests] == \
        [r.request_id for r in wl.requests]
    # wrong kind: a flight bundle passed where a workload belongs
    with pytest.raises(ValueError, match="not a replay workload"):
        Workload.from_dict({"kind": "something-else"})
    # unversioned files refuse to load
    noversion = dict(data)
    del noversion["schema_version"]
    with pytest.raises(ValueError, match="schema_version"):
        Workload.from_dict(noversion)
    # files from a newer build refuse to load
    newer = dict(data, schema_version=99)
    with pytest.raises(ValueError, match="newer"):
        Workload.from_dict(newer)


def test_bundle_schema_guards():
    fr = FlightRecorder(events=64, steps=16)
    fr.req_event("r1", "QUEUED", slo_class="standard", prompt_tokens=4,
                 max_tokens=3)
    fr.req_event("r1", "FINISHED", cause="length", output_tokens=3)
    bundle = fr.dump_bundle("test")
    # newer-than-this-build bundles are rejected loudly
    with pytest.raises(ValueError, match="newer"):
        workload_from_bundle(dict(bundle, schema=FLIGHT_SCHEMA_VERSION + 1))
    # legacy (unversioned v1) bundles upgrade loudly, not silently
    legacy = {k: v for k, v in bundle.items()
              if k not in ("schema", "rings", "engine")}
    wl = workload_from_bundle(legacy)
    assert wl.meta.get("upgraded_from_schema") == 1
    assert wl.requests[0].max_tokens == 3


def test_truncated_ring_is_reported_not_silently_shrunk():
    """ISSUE 11 small fix: dump-time cursor/drop markers + timelines
    that lost their QUEUED event surface as meta.truncated, so replay
    extraction reports a shorter-than-reality workload instead of
    synthesizing one quietly."""
    fr = FlightRecorder(events=8, steps=4)
    for i in range(12):      # overflow the 8-slot ring
        fr.req_event(f"r{i}", "QUEUED", slo_class="standard",
                     prompt_tokens=4, max_tokens=2)
    # r-early lost its QUEUED; give it a surviving non-head event
    fr.req_event("r0", "FINISHED", cause="length", output_tokens=2)
    bundle = fr.dump_bundle("test")
    assert bundle["rings"]["events"]["dropped"] > 0
    wl = workload_from_bundle(bundle)
    assert wl.meta.get("truncated") is True
    assert wl.meta.get("ring_dropped_entries", 0) > 0
    assert wl.meta.get("partial_requests", 0) >= 1


# ---------------------------------------------------------------------
# slow/chaos: real post-mortems and the 2x-overload soak
# ---------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_fault_storm_postmortem_replays_deterministically(tmp_path,
                                                          monkeypatch):
    """A REAL runner-produced fault-storm post-mortem bundle (the
    crash-only path: storm -> fail-all -> automatic dump) extracts into
    a workload whose replay re-fires the fault schedule and accounts
    every source request in exactly one terminal state — twice,
    identically."""
    monkeypatch.setenv("TPUSERVE_FLIGHT_DIR", str(tmp_path))
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams, SchedulerConfig)
    from tpuserve.server.runner import AsyncEngineRunner
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64,
                          max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=2),
        faults="decode_dispatch:raise:1.0:count=40", seed=0))
    runner = AsyncEngineRunner(eng)
    # trip the storm fallback (fail-all + automatic fault_storm bundle)
    # before bisection can poison-isolate everything individually
    runner.MAX_FAULTS_PER_WINDOW = 3
    runner.start()
    try:
        params = SamplingParams(max_tokens=4, temperature=0.0,
                                ignore_eos=True)
        subs = [runner.submit(prompt_token_ids=[3 + i, 4, 5],
                              params=params, request_id=f"storm-{i}")
                for i in range(4)]
        failures = 0
        for rid, q in subs:
            while True:
                item = q.get(timeout=120)
                if item is None:
                    break
                if isinstance(item, Exception):
                    failures += 1
        assert failures >= 1, "storm should have failed clients"
    finally:
        runner.shutdown()
    bundles = [f for f in os.listdir(tmp_path)
               if f.startswith("flight-fault_storm")]
    assert bundles, "fault storm wrote no post-mortem bundle"
    with open(tmp_path / sorted(bundles)[0]) as f:
        bundle = json.load(f)
    assert bundle["schema"] == FLIGHT_SCHEMA_VERSION
    wl = workload_from_bundle(bundle, seed=3)
    assert wl.faults and "decode_dispatch:raise" in wl.faults
    storm_rids = {r.request_id for r in wl.requests
                  if r.request_id.startswith("storm-")}
    assert storm_rids == {f"storm-{i}" for i in range(4)}
    r1 = replay(wl, ReplayOptions())
    r2 = replay(wl, ReplayOptions())
    assert r1["token_digest"] == r2["token_digest"]
    assert r1["sli_digest"] == r2["sli_digest"]
    # the extracted fault schedule re-fired and was salvaged through
    assert r1["counters"]["salvage_rounds"] >= 1
    # same terminal-state accounting: every source request reaches
    # exactly ONE terminal state in the replay (and none is dropped)
    assert set(r1["outcomes"]) >= storm_rids
    assert not r1["aborted"]
    assert sum(1 for _ in r1["outcomes"]) == len(r1["outcomes"])


@pytest.mark.slow
@pytest.mark.chaos
def test_overload_soak_roundtrip_sli_comparable(tmp_path):
    """ACCEPTANCE: a seeded 2x-overload chaos soak round-trips: incident
    capture -> bundle -> workload -> deterministic CPU replay in virtual
    time (>=10x faster than the incident span) -> report whose per-class
    SLI families diff directly against the source bundle."""
    # ~2x overload: 24 requests over 60 virtual seconds against 2 seats
    # at 20ms steps, plus a seeded 2% decode fault rate
    reqs = [WorkloadRequest(
        request_id=f"soak-{i:02d}", arrival_s=round(i * 60.0 / 23, 3),
        prompt_tokens=8, max_tokens=6,
        slo_class=("interactive", "standard", "batch")[i % 3], seed=i)
        for i in range(24)]
    incident = Workload(
        requests=reqs, seed=11,
        faults="decode_dispatch:raise:0.02,seed=11",
        meta={"source_engine": {"max_num_seqs": 2, "block_size": 4},
              "mean_step_ms": 20.0})
    bundle_path = str(tmp_path / "soak_bundle.json")
    r_incident = replay(incident,
                        ReplayOptions(dump_bundle_path=bundle_path))
    assert not r_incident["aborted"]
    with open(bundle_path) as f:
        bundle = json.load(f)
    wl = workload_from_bundle(bundle, seed=11)
    r1 = replay(wl, ReplayOptions())
    r2 = replay(wl, ReplayOptions())
    assert r1["token_digest"] == r2["token_digest"]
    assert r1["sli_digest"] == r2["sli_digest"]
    assert r1["speedup"] >= 10, (r1["virtual_s"], r1["wall_s"])
    diff = diff_report(r1, wl, source_sli=bundle.get("sli"))
    for cls in ("interactive", "standard", "batch"):
        e = diff["sli"][cls]["ttft"]
        assert e["source"] and e["replay"] and "ratio_p50" in e, (cls, e)
    # terminal accounting closes on both sides: every request reaches
    # exactly one terminal state, source and replay alike
    assert sum(diff["source_outcomes"].values()) == len(wl.requests)
    assert sum(diff["replay_outcomes"].values()) == len(wl.requests)
