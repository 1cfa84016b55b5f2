"""Step records: every dispatch kind leaves a whole one, and the recorder
outlives whatever rebuilds the engine.

The flight recorder, hostprof's spans and devprof have no off state, so
there is no "off is byte-identical" to pin.  What must hold instead is
that EVERY path records: each per-layer metric of the benchmark
(``benchmark/layer_metrics/``, ``benchmark/harness/host_spans.py``) is
read from the fields checked here, whichever route the engine observes
that it can take."""

import dataclasses
import json
import urllib.request

import pytest

from tpuserve.models.config import get_model_config
from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SamplingParams, SchedulerConfig)
from tpuserve.runtime.spec import SpecConfig

GREEDY = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
MC32 = dataclasses.replace(get_model_config("tiny-qwen3"), dtype="float32")

# what the readers index without asking (s["kind"], s["rows"], ...)
FIELDS = {"t", "seq", "kind", "rows", "actual_tokens", "padded_tokens",
          "ctx_tokens", "ms", "phase_ms", "dev"}


def _engine(cache_dtype="bfloat16", model_cfg=None, sched=None, **kw):
    sched = {"max_num_seqs": 8, "min_prefill_bucket": 8,
             "min_decode_bucket": 2, **(sched or {})}
    return Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=128,
                          max_blocks_per_seq=32, dtype=cache_dtype),
        scheduler=SchedulerConfig(**sched), **kw), model_cfg=model_cfg)


def _drain(eng):
    while eng.has_work():
        eng.step()


def _serve(eng, prompts, params=GREEDY):
    for p in prompts:
        eng.add_request(prompt_token_ids=p, params=params)
    _drain(eng)


def _mixed(eng):
    # a stream is decoding when a second prompt arrives: the next cycle
    # is ONE flat dispatch of its decode row and the new prompt's chunk
    eng.add_request(prompt_token_ids=[3, 4, 5, 6, 7],
                    params=dataclasses.replace(GREEDY, max_tokens=12))
    for _ in range(3):
        eng.step()
    eng.add_request(prompt_token_ids=list(range(10, 30)), params=GREEDY)
    _drain(eng)


SHORT = [[3, 4, 5, 6, 7], [8, 9, 10], [11, 12, 13, 14, 15, 16, 17, 18, 19]]
# n-gram prompt lookup needs a prompt that repeats itself
REPEATING = [[5, 6, 7, 8] * 6]

KINDS = {
    # id: (step-record kind, engine kwargs, drive)
    "prefill-packed": (
        "prefill", dict(cache_dtype="float32", model_cfg=MC32),
        lambda e: _serve(e, SHORT)),
    "prefill-batch-by-length": (
        "prefill", dict(cache_dtype="int8"), lambda e: _serve(e, SHORT)),
    "prefill_chunk": (
        "prefill_chunk", dict(sched=dict(prefill_chunk_size=16)),
        lambda e: _serve(e, [list(range(2, 42))])),
    "mixed": (
        "mixed", dict(sched=dict(mixed_batching=True,
                                 mixed_token_budget=32)), _mixed),
    "decode": ("decode", dict(multi_step=1), lambda e: _serve(e, SHORT)),
    "window": ("window", dict(multi_step=4), lambda e: _serve(e, SHORT)),
    "decode_spec": (
        "spec", dict(multi_step=1,
                     speculative=SpecConfig(num_draft_tokens=3)),
        lambda e: _serve(e, REPEATING,
                         dataclasses.replace(GREEDY, max_tokens=12))),
}
# the prefill route is what the engine observes it can take (int8 pages
# cannot be packed), never a setting
PACKED = {"prefill-packed": True, "prefill-batch-by-length": False}


@pytest.mark.parametrize("case", list(KINDS))
def test_every_dispatch_kind_leaves_a_whole_step_record(case):
    kind, kw, drive = KINDS[case]
    eng = _engine(**kw)
    drive(eng)
    if case in PACKED:
        assert eng._packed_prefill is PACKED[case]
        assert bool(eng.stats.prefill_packed_steps) is PACKED[case]
    steps = eng.flight.steps_snapshot(limit=1 << 30)
    assert [s["seq"] for s in steps] == list(range(1, len(steps) + 1))
    mine = [s for s in steps if s["kind"] == kind]
    assert mine, sorted({s["kind"] for s in steps})
    for s in mine:
        assert FIELDS <= set(s), FIELDS - set(s)
        assert s["rows"] >= 1 and s["ms"] > 0
        assert 0 < s["actual_tokens"] <= s["padded_tokens"]
        assert s["ctx_tokens"] >= s["rows"]
        phases, dev = s["phase_ms"], s["dev"]
        # the dispatch span, and devprof's bracket of the enqueue in it
        assert phases["dispatch"] > 0
        assert any(k.startswith("dispatch.") for k in phases)
        assert dev["dispatch_ms"] > 0
        # flush is not timed twice: it IS the cycle's syncs
        syncs = sum(v for k, v in phases.items() if k.startswith("sync."))
        assert phases.get("flush", 0.0) == pytest.approx(syncs, abs=1e-3)
    # the kind's first dispatch compiled inside its bracket
    assert mine[0]["dev"]["compiles"] >= 1
    assert any((s.get("dev") or {}).get("device_ms", 0) > 0 for s in steps)
    # one engine, one process-wide profiler: from the second record on
    # (the first carries what the process had summed before), a record's
    # flush is this engine's device_ms
    for s in steps[1:]:
        assert (s.get("dev") or {}).get("device_ms", 0.0) == pytest.approx(
            (s.get("phase_ms") or {}).get("flush", 0.0), abs=1e-3)


# ---- what rebuilds the engine keeps the records ---------------------------

def _after_swap():
    eng = _engine(multi_step=4)
    _serve(eng, SHORT[:1])
    recorder, devprof, before = eng.flight, eng.devprof, eng.flight.seq
    eng.swap_model(dataclasses.replace(eng.config, model="tiny-llama"))
    assert eng.flight is recorder and eng.devprof is devprof
    assert eng.scheduler.flight is recorder and recorder.devprof is devprof
    _serve(eng, SHORT[:1])
    steps = recorder.steps_snapshot(limit=1 << 30)
    new = [s for s in steps if s["seq"] > before]
    assert new and all(s.get("dev") for s in new if s["rows"])
    events = [e["event"] for rid in recorder.recent_request_ids()
              for e in recorder.request_timeline(rid)]
    assert "SWAP" in events
    return steps


def _disagg_pair():
    from tpuserve.parallel.disagg import DisaggregatedEngine
    from tpuserve.server.openai_api import OpenAIServer, ServerConfig
    cfg = _engine().config
    pair = DisaggregatedEngine(cfg, cfg)
    srv = OpenAIServer(pair, ServerConfig(host="127.0.0.1", port=0))
    url = f"http://127.0.0.1:{srv.start()}"
    try:
        req = urllib.request.Request(
            url + "/v1/completions", method="POST",
            headers={"Content-Type": "application/json"},
            data=json.dumps({"model": "tiny-qwen3", "prompt": [3, 4, 5, 6],
                             "max_tokens": 4, "temperature": 0}).encode())
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
        with urllib.request.urlopen(url + "/debug/engine", timeout=30) as r:
            snap = json.loads(r.read())
        with urllib.request.urlopen(url + "/debug/engine/dump",
                                    timeout=30) as r:
            dump = json.loads(r.read())
    finally:
        srv.shutdown()
    assert "enabled" not in snap
    prefill, decode = snap["engines"]
    assert {s["kind"] for s in prefill["steps"]} >= {"prefill"}
    assert {s["kind"] for s in decode["steps"]} & {"decode", "window"}
    assert all("devprof" in e for e in snap["engines"])
    assert len(dump["engines"]) == 2
    return prefill["steps"] + decode["steps"]


def _under_a_virtual_clock():
    from tpuserve.replay.harness import ReplayOptions, build_replay_engine
    from tpuserve.replay.workload import Workload, WorkloadRequest
    wl = Workload(requests=[WorkloadRequest(
        request_id="vc-0", arrival_s=0.0, prompt_tokens=6, max_tokens=4,
        slo_class="standard", seed=0)], seed=1)
    eng, clock = build_replay_engine(wl, ReplayOptions())
    assert eng.flight._clock is clock
    stamps = []
    eng.add_request(prompt_token_ids=[3, 4, 5, 6, 7, 8], params=GREEDY)
    while eng.has_work():
        clock.advance(0.5)
        stamps.append(clock.monotonic())
        eng.step()
    steps = eng.flight.steps_snapshot(limit=1 << 30)
    # the injected clock's seconds, not the host's uptime
    assert [s["t"] for s in steps] == stamps
    return steps


@pytest.mark.parametrize("case", [_after_swap, _disagg_pair,
                                  _under_a_virtual_clock],
                         ids=["swap_model", "disagg-pair", "virtual-clock"])
def test_records_survive_what_rebuilds_the_engine(case):
    steps = case()
    assert steps
    for s in steps:
        if s["rows"]:
            assert s["phase_ms"]["dispatch"] > 0 and s["dev"]["dispatch_ms"] > 0
