"""Device telemetry: per-dispatch attribution, executable ladder, HBM.

Host-side observability is deep (hostprof phases, the flight recorder's
step records, SLO burn rates) but the device itself was one opaque blob:
nothing said how a step's wall time split into device compute vs host
overhead, which bucketed executable served it, what compiles cost, or
how close HBM sat to the edge — exactly the step-time/HBM breakdowns
the Gemma TPU-serving study leans on (PAPERS.md, arxiv 2605.25645) and
the capability/cost signals heterogeneous routing wants (arxiv
2503.20074).  This module is that layer, with ZERO new device syncs
(tpulint P1 stays green):

- **device-time attribution**: the engine brackets its EXISTING
  designated sync points (window flush, pending flush, sample read,
  spec verify, draft proposal, guided top-k, and a wait for the KV
  tier's copy of evicted pages to the host, ``demote``) with
  ``sync(kind)`` — the
  host seconds blocked in a ``device_get`` are the device time the
  pipelined design successfully hid everywhere else, split per sync
  kind.  Dispatch brackets (``dispatch(kind, key)``) time the ASYNC
  enqueue, i.e. pure host trace/dispatch cost — except on an
  executable's FIRST call, where readying the program (tracing,
  lowering, and the backend's compile OR the persistent cache's read in
  its place) blocks inside the same bracket and is recorded as that
  (kind, bucket)'s ``compile_ms``.
- **executable-ladder registry**: every (dispatch kind, bucket key)
  pair the engine ever dispatched — first-dispatch wall ms, hit count,
  and what that first dispatch WAS, from the process's compile ledger
  (utils/compile_cache.py): ``trace_ms`` / ``lower_ms`` / ``backend_ms``
  and ``cache`` (``"miss"``: XLA compiled it; ``"hit"``: read back from
  the persistent cache; ``"none"``: the cache was not asked, or no ledger
  listens).  **A first dispatch is a compile only where ``cache ==
  "miss"``**: ``compiles`` and ``compile_ms`` keep their names and count
  every first dispatch.  What the ledger saw outside every bracket is the
  one row ``unbracketed``.  So compile storms and ladder bloat are a
  table on /debug/engine, not an inference from step-time spikes.
- **HBM watermark accounting**: the engine reconciles its block-manager
  KV reservation with loaded weight bytes and the backend's
  ``memory_stats`` into one watermark dict (``set_hbm``), exported as
  the ``tpuserve_hbm_bytes{kind=weights|kv|state|other}`` gauges plus a
  headroom scalar.
- **profiler-capture bookkeeping**: ``note_capture`` records every
  ``jax.profiler`` trace taken through /debug/profile or the fast-burn
  SLO auto-capture hook (server/tracing.py holds the capture lock), so
  post-mortem bundles reference the traces written beside them.

Cost contract: ``sync`` and ``dispatch`` are hostprof's one span
primitive (``runtime/hostprof.py`` ``Span``, names ``sync.<kind>`` /
``dispatch.<kind>``, on the profiler's clock while a capture runs) with
this module's accumulators added.  The cost on the chip is a measured
number (PERF.md §6, PR 24), and there is no off state: nothing here
ever touches a jax array or changes a dispatch, and the benchmark's
per-layer metrics read these sums and the ladder.

Threading contract (the flight recorder's): every mutating call happens
on the engine loop thread; serving threads read ``snapshot()`` copies
only.  One profiler per engine — unlike hostprof's module singleton,
the ladder and HBM view are engine-shaped state, so multi-engine
processes (disagg) keep per-engine attribution exact.
"""

from __future__ import annotations

from collections import defaultdict
from functools import partial
from typing import Optional

from tpuserve.runtime.hostprof import PROF, Span
from tpuserve.utils.compile_cache import LEDGER

#: bound the ladder table in snapshots/bundles: a pathological bucket
#: explosion must not turn /debug/engine into a megabyte payload (the
#: registry itself is unbounded — seeing the overflow COUNT is the point)
MAX_LADDER_SNAPSHOT = 128


def _stage_ms(led: dict) -> dict:
    """The compile ledger's three stages over a bracket, as a row's ms."""
    return {"trace_ms": round(led["trace_s"] * 1000, 3),
            "lower_ms": round(led["lower_s"] * 1000, 3),
            "backend_ms": round(led["backend_s"] * 1000, 3)}


def _cache_word(led: dict) -> str:
    """What the persistent cache answered inside a first dispatch."""
    return "miss" if led["misses"] else "hit" if led["hits"] else "none"


class DeviceProfiler:
    """Per-engine device telemetry accumulator (see module docstring)."""

    def __init__(self):
        # host wall spent inside exec-hook brackets (async enqueue +
        # first-call compile), per dispatch kind
        self.dispatch_s: dict[str, float] = defaultdict(float)
        self.dispatch_counts: dict[str, int] = defaultdict(int)
        # host wall blocked in the designated device_get sites, per sync
        # kind — the measurable device time of the pipelined design
        self.sync_s: dict[str, float] = defaultdict(float)
        self.sync_counts: dict[str, int] = defaultdict(int)
        # (kind, bucket key) -> [compile_ms, hits, the compile ledger's
        # events inside the first dispatch's bracket]
        self.ladder: dict[tuple, list] = {}
        self.compiles = 0
        self.compile_s = 0.0
        self.cycles = 0
        # HBM watermark (set_hbm): static reconciliation of weights /
        # KV reservation / backend memory stats, refreshed at engine
        # construction (the reservation is static by design — paged KV
        # is allocated up front)
        self._hbm: dict = {}
        # jax.profiler traces taken while this engine served (manual
        # /debug/profile POSTs and SLO-page auto-captures): newest last,
        # referenced from flight bundles; captures_total is the
        # monotonic count behind the tpuserve_profile_captures counter
        # (the list itself is trimmed)
        self.captures: list[dict] = []
        self.captures_total = 0
        # step_delta() diffs against these totals
        self._last_sync = 0.0
        self._last_dispatch = 0.0
        self._last_compiles = 0

    # ---- hot path (engine loop thread) --------------------------------

    def dispatch(self, kind: str, key: tuple):
        """Span ``dispatch.<kind>`` around one async exec-hook call: host
        dispatch wall per kind, and the (kind, key) ladder entry."""
        name = "dispatch." + kind
        return Span(name, PROF.sinks(name)
                    + ((self.dispatch_s, self.dispatch_counts, kind),),
                    partial(self._note_dispatch, (kind, key)))

    def _note_dispatch(self, lk: tuple, dt: float) -> None:
        ent = self.ladder.get(lk)
        if ent is None:
            # first dispatch of this (kind, bucket): readying the program
            # blocked inside this bracket — that wall IS its cost, and
            # the ledger's events of those dt seconds say what it was
            # (the only call of the ledger a dispatch ever makes)
            first = LEDGER.within(dt)
            self.ladder[lk] = [round(dt * 1000, 3), 1, first]
            self.compiles += 1
            self.compile_s += dt
        else:
            ent[1] += 1

    def sync(self, kind: str):
        """Span ``sync.<kind>`` around one designated device_get: seconds
        the host blocked waiting for the device, per kind; every sync
        also feeds hostprof's ``flush``."""
        name = "sync." + kind
        return Span(name, PROF.sinks(name, "flush")
                    + ((self.sync_s, self.sync_counts, kind),))

    def bump_cycle(self) -> None:
        self.cycles += 1

    # ---- facts (engine construction / capture paths) -------------------

    def set_hbm(self, *, weights: int, kv_reserved: int, limit: int,
                num_blocks: int, block_bytes: int,
                in_use: Optional[int] = None, state: int = 0) -> None:
        """Record the HBM watermark: ``weights`` (loaded param bytes,
        draft included), ``kv_reserved`` (the paged cache's full static
        reservation = num_blocks * block_bytes), ``state`` (the
        recurrent-state pool of a model with state-space layers, one slot
        a decode seat), ``limit`` (detected or
        TPUSERVE_HBM_BYTES-overridden device budget), and the backend's
        live ``bytes_in_use`` when it reports one.  ``other`` is the
        workspace/fragmentation remainder the backend sees beyond
        weights+KV+state; ``headroom`` is what is left under the limit."""
        other = 0
        if in_use is not None:
            other = max(0, int(in_use) - int(weights) - int(kv_reserved)
                        - int(state))
        self._hbm = {
            "limit_bytes": int(limit),
            "weights_bytes": int(weights),
            "kv_reserved_bytes": int(kv_reserved),
            "state_bytes": int(state),
            "other_bytes": int(other),
            "num_blocks": int(num_blocks),
            "block_bytes": int(block_bytes),
            "headroom_bytes": int(limit) - int(weights)
                              - int(kv_reserved) - int(state) - int(other),
        }

    def note_capture(self, trace_dir: str, reason: str,
                     seconds: float) -> None:
        """One jax.profiler trace landed on disk (manual or SLO-page
        auto-capture).  Bounded: bundles reference the 16 newest."""
        self.captures.append({"trace_dir": trace_dir, "reason": reason,
                              "seconds": seconds})
        self.captures_total += 1
        del self.captures[:-16]

    # ---- snapshots (any thread) ---------------------------------------

    def hbm_snapshot(self) -> dict:
        return dict(self._hbm)

    def step_delta(self) -> Optional[dict]:
        """Per-step deltas for the flight recorder's step record (single
        consumer: FlightRecorder.note_step, engine loop thread): device
        ms blocked, host dispatch ms, compiles since the previous
        record.  Mirrors note_step's hostprof diffing."""
        sync_t = sum(self.sync_s.values())
        disp_t = sum(self.dispatch_s.values())
        dev = {}
        d = sync_t - self._last_sync
        if d > 0:
            dev["device_ms"] = round(d * 1000, 4)
        d = disp_t - self._last_dispatch
        if d > 0:
            dev["dispatch_ms"] = round(d * 1000, 4)
        d = self.compiles - self._last_compiles
        if d > 0:
            dev["compiles"] = d
        self._last_sync = sync_t
        self._last_dispatch = disp_t
        self._last_compiles = self.compiles
        return dev or None

    def cache_answers(self) -> dict:
        """Of the ladder's first dispatches, how many the persistent cache
        answered and how many XLA compiled (the rest asked nobody)."""
        words = [_cache_word(ent[2]) for ent in list(self.ladder.values())]
        return {"cache_hits": words.count("hit"),
                "cache_misses": words.count("miss")}

    def ladder_snapshot(self) -> dict:
        """The executable ladder as a bounded table: one row per
        (kind, bucket), hottest first, plus the registry totals (which
        keep counting past the snapshot bound)."""
        items = sorted(self.ladder.items(),
                       key=lambda kv: kv[1][1], reverse=True)
        rows = [{"kind": kind, "bucket": repr(key),
                 "compile_ms": ent[0], "hits": ent[1],
                 **_stage_ms(ent[2]), "cache": _cache_word(ent[2])}
                for (kind, key), ent in items[:MAX_LADDER_SNAPSHOT]]
        loose = LEDGER.unbracketed()
        return {
            "retained": len(self.ladder),
            "compiles": self.compiles,
            "compile_ms": round(self.compile_s * 1000, 2),
            **self.cache_answers(),
            "truncated": max(0, len(self.ladder) - MAX_LADDER_SNAPSHOT),
            "executables": rows,
            # the process's programs no dispatch bracket saw (samplers,
            # token selects, eager jnp, the weights' initialisers)
            "unbracketed": {"requests": loose["requests"],
                            "cache_hits": loose["hits"],
                            "cache_misses": loose["misses"],
                            **_stage_ms(loose)},
        }

    def snapshot(self) -> dict:
        """Machine-readable breakdown (/debug/engine, flight bundles):
        per-kind device/dispatch ms totals, device ms per cycle, ladder
        summary, HBM watermark, recorded captures."""
        cycles = max(self.cycles, 1)
        device = {k: {"total_ms": round(v * 1000, 2),
                      "syncs": self.sync_counts[k]}
                  for k, v in sorted(self.sync_s.items())}
        dispatch = {k: {"total_ms": round(v * 1000, 2),
                        "calls": self.dispatch_counts[k]}
                    for k, v in sorted(self.dispatch_s.items())}
        dev_total = sum(self.sync_s.values())
        return {
            "cycles": self.cycles,
            "device_ms_per_cycle": round(1000 * dev_total / cycles, 4),
            "device": device,
            "dispatch": dispatch,
            "ladder": self.ladder_snapshot(),
            "hbm": self.hbm_snapshot(),
            "captures": list(self.captures),
        }
