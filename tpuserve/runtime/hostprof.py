"""Host hot-path phase timer (opt-in, near-zero cost when off).

The device loop is pipelined (one sync per S-token window), which makes
the PYTHON between dispatches the scaling wall at high stream counts —
DeepServe's host-overhead observation (PAPERS.md, arxiv 2501.14417).
This module gives that cost a number: the engine brackets its per-cycle
phases (schedule / block-accounting / dispatch / detokenize / flush)
with ``PROF.phase(...)`` context managers, and ``tools/profile_step.py
--json`` / ``bench.py --clients-sweep`` report ms-per-cycle per phase.

Disabled, ``phase()`` returns a shared no-op context manager — two
attribute loads and a dict miss per use, no timestamps taken — so
serving pays nothing for the instrumentation.  Enabled, each phase
costs two ``perf_counter`` calls.  Since the flight recorder landed
(runtime/flight.py) the profiler is ALWAYS-ON in practice: building an
engine with the recorder enabled (the default) flips ``PROF.enabled``
so every step record carries its phase breakdown; its cost on the chip
is not measured on the current code, and ``TPUSERVE_FLIGHT=0`` restores
the fully-off state.
The profiler is engine-loop single-threaded like everything else it
brackets; it is NOT meant to be shared across engines running in
different threads (per-cycle deltas in multi-engine processes are
approximate — see FlightRecorder.note_step).
"""

from __future__ import annotations

import time
from collections import defaultdict


class _NoopPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopPhase()


class _Phase:
    __slots__ = ("_prof", "_name", "_t0")

    def __init__(self, prof, name):
        self._prof = prof
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._prof.seconds[self._name] += time.perf_counter() - self._t0
        self._prof.counts[self._name] += 1
        return False


class HostPhaseProfiler:
    """Accumulates wall seconds per named host phase; ``cycles`` is bumped
    once per engine cycle (the denominator for ms-per-cycle)."""

    # canonical phase names, in report order
    PHASES = ("schedule", "block", "dispatch", "detokenize", "flush")
    # the phases that are PURE host time (dispatch covers array build +
    # async dispatch; flush is the device->host sync, i.e. mostly device
    # wait) — "host_ms_per_cycle" sums only these
    HOST_PHASES = ("schedule", "block", "detokenize")

    def __init__(self):
        self.enabled = False
        self.seconds: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self.cycles = 0

    def phase(self, name: str):
        if not self.enabled:
            return _NOOP
        return _Phase(self, name)

    def bump_cycle(self) -> None:
        if self.enabled:
            self.cycles += 1

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()
        self.cycles = 0

    def report(self) -> dict:
        """Per-phase breakdown: ms per engine cycle plus totals — the
        machine-readable shape profile_step --json and the bench rows
        emit (diffable across commits)."""
        cycles = max(self.cycles, 1)
        phases = {}
        for name in list(self.PHASES) + sorted(
                set(self.seconds) - set(self.PHASES)):
            if name not in self.seconds and name not in self.PHASES:
                continue
            phases[name] = {
                "ms_per_cycle": round(1000 * self.seconds[name] / cycles, 4),
                "total_ms": round(1000 * self.seconds[name], 2),
                "calls": self.counts[name],
            }
        total = sum(self.seconds.values())
        host = sum(self.seconds[p] for p in self.HOST_PHASES
                   if p in self.seconds)
        return {
            "cycles": self.cycles,
            # schedule + block accounting + detokenize/emit — the phases
            # the native/batched host path migrated off per-request Python
            "host_ms_per_cycle": round(1000 * host / cycles, 4),
            "all_phases_ms_per_cycle": round(1000 * total / cycles, 4),
            "phases": phases,
        }


# module singleton: the engine loop is single-threaded, and profile runs
# build one engine per process
PROF = HostPhaseProfiler()
