"""Engine flight recorder: always-on lifecycle tracing + post-mortems.

After PRs 3-7 a request can be queued, deadline-expired, tier-restored,
chunk-prefilled, window-batched, preempted, salvaged, browned-out, or
shed — and before this module none of that lifecycle was observable per
request, only as aggregate counters.  The recorder is the narration
layer the autoscaler (ROADMAP item 2) and the host-overhead work
(item 3) read from, the engine-emitted signal DeepServe scales on
(PAPERS.md, arxiv 2501.14417):

- a fixed-size ring of per-request lifecycle **events** (QUEUED,
  ADMITTED, RESTORING, PREFILL, PREFILL_CHUNK, WINDOW, PREEMPTED,
  SALVAGED, BROWNOUT_CLAMPED, SHED, FAULT, FINISHED-with-cause);
- a fixed-size ring of per-cycle **step records** (dispatch kind, rows,
  actual/padded flat tokens, wall ms, hostprof phase ms, devprof's
  device/dispatch/compile deltas);
- per-SLO-class **SLI reservoirs** (client-observable TTFT/ITL/e2e,
  fed by the runner loop) behind the ``tpuserve_ttft/itl/e2e_seconds``
  histogram families and the brownout controller's transition logs;
- **post-mortem bundles**: on a watchdog trip, fault-storm fail-all, or
  poison isolation the last N cycles + affected request timelines are
  written as one JSON file (``TPUSERVE_FLIGHT_DIR``, the model PVC in
  the manifests) and counted in ``tpuserve_flight_postmortems_total``.

Threading contract: every MUTATING call happens on the engine loop
thread (the same thread that runs ``Engine.step`` — the runner's
salvage/intake paths included).  Serving threads take SNAPSHOTS only:
ring entries are immutable tuples, a snapshot copies the backing list,
and a concurrent append at worst duplicates or misses the newest slot —
never a torn read.  The sole exception is ``postmortem``, which the
watchdog thread may call while the loop thread is wedged inside a stuck
dispatch (that is the point); it reads snapshots and touches only
recorder-owned counters.

Timestamps come from the injectable monotonic clock seam ONLY
(runtime/clock.py — virtual under trace replay, the real clock in
production; no wall-clock deltas, pinned by tests/test_flight.py) and no
device syncs happen anywhere (tpulint P1 stays green: the recorder
stores host-known ints/strs, never a jax array).  There is no off
state: every per-layer metric of the benchmark, the autoscaler's scrape
and the post-mortems read these records, and the cost of writing them
was measured on the chip (PERF.md §6, PR 24).
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Optional, Sequence

from tpuserve.runtime.clock import MONOTONIC
from tpuserve.runtime.hostprof import PROF

logger = logging.getLogger("tpuserve.flight")

#: Post-mortem / on-demand bundle schema.  v1 (implicit — bundles carried
#: no version field) lacked ring-integrity markers, engine facts and
#: max_tokens on QUEUED events; replay extraction (tpuserve/replay/
#: extract.py) upgrades v1 bundles loudly and rejects anything newer
#: than this build understands.
FLIGHT_SCHEMA_VERSION = 2

#: canonical lifecycle event names, in rough lifecycle order (the
#: /debug/requests timeline and the OTLP child spans use these verbatim)
EVENTS = ("QUEUED", "ADMITTED", "RESTORING", "PREFILL", "PREFILL_CHUNK",
          "WINDOW", "PREEMPTED", "SALVAGED", "BROWNOUT_CLAMPED", "SHED",
          "FAULT", "SWAP", "FINISHED")

SLI_KINDS = ("ttft", "itl", "e2e")

# bound post-mortem disk usage: a fault storm must not convert the model
# PVC into a bundle dump
MAX_POSTMORTEMS = 32
# a step record's routing counts, in the order note_moe() takes them: the
# first three for every model with expert layers, seven under a share, the
# eighth under a share behind a group-limited router
MOE_FIELDS = ("moe_rows", "moe_expert_hits", "moe_moves_plain",
              "moe_held_rows", "moe_held_hits", "moe_buffer_rows",
              "moe_held_pieces", "moe_group_rows")


class _Ring:
    """Fixed-size append-only ring of immutable entries.  Single writer;
    ``snapshot()`` is safe from any thread (list copy of tuples)."""

    __slots__ = ("_buf", "_n", "idx")

    def __init__(self, n: int):
        self._buf = [None] * max(2, n)
        self._n = len(self._buf)
        self.idx = 0

    def append(self, item) -> None:
        self._buf[self.idx % self._n] = item
        self.idx += 1

    def snapshot(self) -> list:
        i, buf = self.idx, list(self._buf)
        if i <= self._n:
            return [x for x in buf[:i] if x is not None]
        cut = i % self._n
        return [x for x in buf[cut:] + buf[:cut] if x is not None]


class FlightRecorder:
    def __init__(self, events: int = 0, steps: int = 0,
                 dirpath: Optional[str] = None, clock=None):
        ev_n = events or int(os.environ.get("TPUSERVE_FLIGHT_EVENTS",
                                            0) or 8192)
        st_n = steps or int(os.environ.get("TPUSERVE_FLIGHT_STEPS",
                                           0) or 512)
        self._events = _Ring(ev_n)
        self._steps = _Ring(st_n)
        self._dir = dirpath or os.environ.get("TPUSERVE_FLIGHT_DIR") or None
        # injectable time source (runtime/clock.py): under replay the
        # recorder stamps VIRTUAL time, so a replayed timeline is
        # directly comparable to the recorded incident's
        self._clock = clock or MONOTONIC
        # monotonic->wall anchor for OTLP span export and bundle headers
        # ONLY; every recorded timestamp and every delta stays monotonic
        self._mono0 = self._clock.monotonic()
        self._wall0 = time.time()        # wall-anchor-ok: export mapping, never a delta
        # engine configuration facts (note_engine_facts), carried in
        # bundles so replay can size a comparable engine
        self._facts: dict = {}
        # per-cycle control-plane scalars (note_control): brownout
        # level, per-class queue-delay EWMAs, queue depths — replaced
        # wholesale each cycle, snapshot-read by /debug/engine and the
        # autoscaler's scrape
        self._control: dict = {}
        # per-cycle hostprof deltas are diffs against this snapshot of the
        # module profiler's cumulative seconds
        self._prof_last: dict = {}
        # the current cycle's number (begin_step): the ``seq`` argument of
        # the cycle's engine.step span and of its step record, so a
        # profiler trace and the records join; the recorder owns it
        # because it outlives the engine across a model swap
        self.seq = 0
        # device telemetry handle (runtime/devprof.py): set by the OWNING
        # engine; None only for a recorder built on its own.  Per-engine
        # like the recorder itself — step records carry THIS engine's
        # device deltas, not a process blur
        self.devprof = None
        # client-observable SLI reservoirs: (class, kind) -> bounded ring
        self._sli: dict = {}
        # step seq -> (routed rows, expert hits) of a model with expert
        # layers (note_moe): read from the device after the step's record
        self._moe: dict = {}
        self.postmortems = 0
        self.last_postmortem: Optional[str] = None

    # ---- writes (engine-loop thread) ----------------------------------

    def req_event(self, rid: str, event: str, **detail) -> None:
        self._events.append((self._clock.monotonic(), rid, event,
                             detail or None))

    def req_event_many(self, rids: tuple, event: str, **detail) -> None:
        """Batched twin of :meth:`req_event` for per-dispatch events that
        cover every row (WINDOW): ONE timestamp, ONE ring entry, ONE
        shared detail dict for the whole batch, so the recorder's cost
        per dispatch does not grow with the batch."""
        if not rids:
            return
        self._events.append((self._clock.monotonic(), tuple(rids), event,
                             detail or None))

    def fault_hook(self, site: str, mode: str,
                   rids: Sequence[str]) -> None:
        """FaultInjector.on_fire target: a firing chaos rule shows up in
        every affected request's timeline (post-mortems and the salvage
        sequence become self-explanatory)."""
        t = self._clock.monotonic()
        for rid in rids or ("(engine)",):
            self._events.append((t, rid, "FAULT",
                                 {"site": site, "mode": mode}))

    def begin_step(self) -> int:
        self.seq += 1
        return self.seq

    def note_step(self, kind: str, rows: int, actual: int, padded: int,
                  dur_s: float, ctx_tokens: int = 0,
                  ridden_tokens: int = 0, kda_row_layers: int = 0) -> None:
        """One engine cycle's step record (``ridden_tokens``: the decode
        rows of a mixed step that also carried prompt tokens, of its
        ``actual`` tokens; ``kda_row_layers``: the row-layers the
        channel-gated state update served in it).  Phase ms are deltas of the
        module hostprof profiler since the previous record, one key per
        span name (runtime/hostprof.py): a span still open here
        (engine.step, step.close) or opened by the runner after the step
        lands in the NEXT record.  Exact for a one-engine process (the
        common case); multi-engine processes interleave and the
        attribution is approximate."""
        cur = dict(PROF.seconds)
        phases = {}
        for k, v in cur.items():
            d = v - self._prof_last.get(k, 0.0)
            if d > 0:
                phases[k] = round(d * 1000, 4)
        self._prof_last = cur
        dev = None
        if self.devprof is not None:
            # per-step device-ms / dispatch-ms / compile deltas, same
            # diffing idiom as the hostprof phases above
            dev = self.devprof.step_delta()
        self._steps.append((self._clock.monotonic(), kind, rows, actual, padded,
                            round(dur_s * 1000, 4), phases or None, dev,
                            self.seq, ctx_tokens, ridden_tokens,
                            kda_row_layers))

    def note_moe(self, seq: int, *counts: int) -> None:
        """The routing counts of step ``seq``'s dispatch (a model with
        expert layers): rows the sparse dispatch routed, summed over the
        layers and fused steps, expert-layers that got at least one, and
        the row moves around the kernel that went by the plain gather;
        where the model holds a share of its experts, four more (the
        rows that landed on held experts, the held expert-layers hit,
        the rows of buffer moved and the pieces they moved in) and behind
        a group-limited router a fifth, the rows one of whose surviving
        groups is held here (``MOE_FIELDS``).
        They come back with the dispatch's tokens, a cycle or more after
        its step record was written, so they are kept beside the ring
        (as many as it holds) and joined in ``steps_snapshot``; a step
        with several dispatches (a prefill and a window) sums them."""
        old = self._moe.get(seq)
        if old is not None:
            counts = tuple(a + b for a, b in zip(counts, old))
        self._moe[seq] = counts
        while len(self._moe) > self._steps._n:
            del self._moe[next(iter(self._moe))]

    def note_engine_facts(self, **facts) -> None:
        """Engine configuration facts stamped into every bundle (model,
        max_num_seqs, num_blocks, block_size, multi_step, slo_classes):
        what the replay harness needs to size a *comparable* engine —
        an overload incident replayed against a pool twice the size
        would diff meaninglessly.  Called once at engine construction;
        cheap dict update."""
        self._facts.update({k: v for k, v in facts.items()
                            if v is not None})

    def note_control(self, **scalars) -> None:
        """Current control-plane scalars (engine-loop thread, once per
        cycle): the brownout level and per-class queue-delay EWMAs the
        SLO controller steers by, plus queue depths — published as
        PLAIN numbers so the autoscaler (and operators reading
        /debug/engine or a dump bundle) never reconstruct them from
        histogram buckets.  The dict is replaced atomically; readers on
        serving threads at worst see the previous cycle's values."""
        self._control = scalars

    def note_sli(self, slo_class: str, kind: str, value: float) -> None:
        """Client-observable latency sample (runner loop thread): TTFT /
        inter-token / end-to-end seconds for one request of ``slo_class``.
        Mirrors what the tpuserve_{ttft,itl,e2e}_seconds histograms
        export, kept here so /debug/engine and the brownout transition
        logs can quote recent percentiles without scraping."""
        ring = self._sli.get((slo_class, kind))
        if ring is None:
            ring = self._sli[(slo_class, kind)] = _Ring(256)
        ring.append(value)

    # ---- snapshots (any thread) ---------------------------------------

    def request_timeline(self, rid: str) -> list[dict]:
        """Ordered lifecycle events recorded for ``rid`` (may be partial:
        the ring holds the most recent TPUSERVE_FLIGHT_EVENTS events
        engine-wide).  Scans newest-to-oldest and stops at the request's
        QUEUED event, so per-request span export under load costs the
        request's own event span, not the whole ring (only an unknown
        rid pays a full scan)."""
        out = []
        for t, r, ev, detail in reversed(self._events.snapshot()):
            if r == rid or (type(r) is tuple and rid in r):
                entry = {"t": t, "event": ev}
                if detail:
                    entry["detail"] = detail
                out.append(entry)
                if ev == "QUEUED":
                    break
        out.reverse()
        return out

    def recent_request_ids(self, limit: int = 64) -> list[str]:
        """Most-recently-seen request ids, newest last."""
        seen: dict = {}
        for t, rid, _ev, _d in self._events.snapshot():
            for r in (rid if type(rid) is tuple else (rid,)):
                seen.pop(r, None)
                seen[r] = True
        ids = list(seen)
        return ids[-limit:]

    def steps_snapshot(self, limit: int = 128) -> list[dict]:
        out = []
        for (t, kind, rows, actual, padded, ms, phases, dev, seq, ctx, rode,
             kda) in self._steps.snapshot()[-limit:]:
            rec = {"t": t, "seq": seq, "kind": kind, "rows": rows,
                   "actual_tokens": actual, "padded_tokens": padded,
                   "ctx_tokens": ctx, "ms": ms}
            if kind == "mixed":
                rec["ridden_tokens"] = rode
            if kda:
                rec["kda_row_layers"] = kda
            if phases:
                rec["phase_ms"] = phases
            if dev:
                # device-time attribution deltas (runtime/devprof.py):
                # device_ms / dispatch_ms / compiles for this step
                rec["dev"] = dev
            rec.update(zip(MOE_FIELDS, self._moe.get(seq, ())))
            out.append(rec)
        return out

    def sli_summary(self) -> dict:
        """p50/p95 over the recent reservoirs, per class per kind —
        what the brownout controller logs on level transitions and
        /debug/engine reports."""
        out: dict = {}
        for (cls, kind), ring in list(self._sli.items()):
            vals = sorted(ring.snapshot())
            if not vals:
                continue
            out.setdefault(cls, {})[kind] = {
                "n": len(vals),
                "p50": round(vals[len(vals) // 2], 6),
                "p95": round(vals[min(len(vals) - 1,
                                      int(len(vals) * 0.95))], 6),
            }
        return out

    def engine_snapshot(self, steps: int = 128) -> dict:
        out = {
            "events_recorded": self._events.idx,
            "steps_recorded": self._steps.idx,
            "requests": self.recent_request_ids(),
            "steps": self.steps_snapshot(steps),
            "sli": self.sli_summary(),
            "control": dict(self._control),
            # what the engine is (note_engine_facts): its sizes and the
            # decode route it observed, with the route's two numbers
            "engine": dict(self._facts),
            "postmortems": self.postmortems,
            "last_postmortem": self.last_postmortem,
        }
        if self.devprof is not None:
            # device telemetry: attribution totals, executable ladder,
            # HBM watermark, recorded profiler captures
            out["devprof"] = self.devprof.snapshot()
        return out

    def wall_of(self, t_mono: float) -> float:
        """Map a recorded monotonic timestamp onto the wall clock (OTLP
        span export / bundle headers only)."""
        return self._wall0 + (t_mono - self._mono0)

    # ---- bundles (post-mortem + on-demand dump) ------------------------

    def dump_bundle(self, reason: str, rids: Sequence[str] = (),
                    extra: Optional[dict] = None) -> dict:
        """Build a replay-ready bundle dict: last N cycles, the named (or
        every ring-reachable) request timeline, SLI reservoirs, engine
        facts, schema version, and ring-integrity markers.  Snapshot
        reads only — safe from any thread, including the watchdog thread
        while the loop is wedged (post-mortems) and HTTP handler threads
        (/debug/engine/dump).

        Integrity markers: ``rings`` records each ring's write cursor
        and capacity at dump start, how many entries have already been
        overwritten (``dropped``), and the cursor again after assembly —
        ``torn`` flags a dump raced by a live writer.  Replay extraction
        uses these to REPORT a truncated or torn timeline instead of
        silently synthesizing a shorter workload."""
        ev_cursor, st_cursor = self._events.idx, self._steps.idx
        ids = list(rids) or self.recent_request_ids(limit=10 ** 6)
        bundle = {
            "schema": FLIGHT_SCHEMA_VERSION,
            "reason": reason,
            "written_unix": self.wall_of(self._clock.monotonic()),
            "monotonic_anchor": {"mono": self._mono0,
                                 "wall": self._wall0},
            "engine": dict(self._facts),
            "steps": self.steps_snapshot(256),
            "requests": {rid: self.request_timeline(rid)
                         for rid in ids},
            "sli": self.sli_summary(),
            "control": dict(self._control),
        }
        if self.devprof is not None:
            # ladder/HBM/capture state at dump time: a post-mortem names
            # the jax.profiler traces written beside it (trace_dir under
            # the same TPUSERVE_FLIGHT_DIR)
            bundle["devprof"] = self.devprof.snapshot()
        bundle["rings"] = {
            "events": {"cursor": ev_cursor, "capacity": self._events._n,
                       "dropped": max(0, ev_cursor - self._events._n),
                       "torn": self._events.idx != ev_cursor},
            "steps": {"cursor": st_cursor, "capacity": self._steps._n,
                      "dropped": max(0, st_cursor - self._steps._n),
                      "torn": self._steps.idx != st_cursor},
        }
        if extra:
            bundle["extra"] = extra
        return bundle

    def postmortem(self, reason: str, rids: Sequence[str] = (),
                   extra: Optional[dict] = None) -> Optional[str]:
        """Write the last N cycles + affected request timelines to a JSON
        bundle and return its path (None when capped or the
        write fails — a post-mortem must never take serving down with
        it).  Callable from the watchdog thread while the engine loop is
        wedged: snapshot reads only."""
        if self.postmortems >= MAX_POSTMORTEMS:
            return None
        try:
            import tempfile
            import uuid
            d = self._dir or tempfile.gettempdir()
            os.makedirs(d, exist_ok=True)
            # counter bumped only AFTER the write lands: failed writes
            # (full/read-only PVC) must neither eat the bundle budget nor
            # make the reported count disagree with the files on disk.
            # uuid suffix: a disagg pod runs TWO recorders (same pid,
            # same counter values) into one dir, and the watchdog thread
            # can dump concurrently with the loop thread — names must
            # never collide or os.replace silently drops a bundle
            n = self.postmortems + 1
            path = os.path.join(
                d, f"flight-{reason}-{os.getpid()}-{n}"
                   f"-{uuid.uuid4().hex[:8]}.json")
            # watchdog-path dumps pass the affected rids; a post-mortem
            # with no named requests captures everything in the ring so
            # the incident replays whole (tpuserve/replay/extract.py)
            bundle = self.dump_bundle(reason, rids, extra)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(bundle, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
            self.postmortems = n
            self.last_postmortem = path
            logger.warning("flight post-mortem (%s) written to %s",
                           reason, path)
            return path
        except Exception:
            logger.exception("flight post-mortem write failed")
            return None
