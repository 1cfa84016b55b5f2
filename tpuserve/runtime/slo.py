"""SLO classes, overload estimation, and the brownout ladder.

Production traffic is not uniform: an interactive chat turn and a
background batch job have different latency contracts, and under
sustained overload a scheduler that treats them identically degrades
everyone equally (DeepServe's serverless QoS tiers, arxiv 2501.14417;
the resilience-balancing orchestration of arxiv 2503.20074).  This
module is the policy layer the scheduler and engine consult:

- **Classes** — every request carries one of ``interactive`` /
  ``standard`` / ``batch`` (``SamplingParams.slo_class``), carried from
  the OpenAI API (``X-SLO-Class`` header / ``slo_class`` body field /
  per-tenant default, server/tenants.py).  Lower rank = stricter SLO.
- **Load estimator** — queue depth, padding-waste EWMA (delivered
  compute per dispatched token), and per-class queue-delay EWMAs,
  folded into one dimensionless ``pressure`` score.
- **Brownout ladder** — graceful-degradation levels entered
  immediately when pressure crosses a threshold and exited
  *hysteretically* (one level per ``hold_s``, and only once pressure
  has dropped ``exit_margin`` below the entry threshold), so the
  system never flaps between shedding and admitting at the boundary:

  =====  ==========================================================
  level  effect (cumulative)
  =====  ==========================================================
  0      normal operation
  1      speculation disabled for dispatches carrying batch rows
  2      batch ``max_tokens`` clamped to ``batch_max_tokens_cap``
  3      new batch work shed (429 + Retry-After)
  4      new standard work shed too; interactive falls back to the
         queue-full 503 like before
  =====  ==========================================================

Shedding answers with a clean retryable status *before* any prefill is
spent; the alternative — unbounded queues — turns overload into
timeout storms for every class at once.  The whole layer is behind the
``TPUSERVE_SLO_CLASSES`` kill switch (``=0`` restores classless FIFO
byte-identically).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

from tpuserve.runtime.clock import MONOTONIC

logger = logging.getLogger("tpuserve.slo")

SLO_CLASSES = ("interactive", "standard", "batch")
INTERACTIVE, STANDARD, BATCH = range(3)
_RANK = {name: i for i, name in enumerate(SLO_CLASSES)}


def class_rank(name: str) -> int:
    """Rank of an SLO class name (0 = strictest).  Raises ``ValueError``
    on junk so intake surfaces a 400, not a silent default."""
    try:
        return _RANK[name]
    except KeyError:
        raise ValueError(
            f"unknown slo_class {name!r}; one of {'/'.join(SLO_CLASSES)}"
        ) from None


class ShedError(RuntimeError):
    """Raised at intake when the brownout ladder sheds this request's
    class (HTTP layer: 429 + ``Retry-After``), or when a queue-full
    eviction displaces a lower-class waiting request for a stricter
    arrival.  Retryable by contract — nothing was admitted and no
    prefill was spent."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = retry_after_s


@dataclasses.dataclass(frozen=True)
class SloConfig:
    # Fraction of the prefill/mixed token budget reserved for
    # non-batch classes: batch prefill only admits into the leftover,
    # so an interactive arrival never finds the whole budget pre-booked
    # by background chunks.
    reserve_frac: float = 0.25
    # Class preemptions one request may absorb (scheduler re-prefill
    # replays are token-identical, so correctness is free — this bounds
    # wasted recompute and guarantees batch work still finishes).
    preempt_budget: int = 3
    # Victims preempted for admissions in one engine cycle (each costs
    # a full re-prefill later; bounding it keeps a single cycle's
    # decision cheap and lets the estimator observe the effect).
    max_preempt_per_cycle: int = 4
    # Queue-delay SLO the estimator normalises interactive delay
    # against (standard is held to 2x this).
    target_queue_delay_s: float = 1.0
    ewma_alpha: float = 0.2
    # Pressure thresholds entering brownout levels 1..4.
    enter_levels: tuple = (0.5, 0.75, 0.9, 1.2)
    # Step down only after pressure < enter_threshold - exit_margin ...
    exit_margin: float = 0.15
    # ... sustained for hold_s since the last level change (hysteresis).
    hold_s: float = 3.0
    # Level-2 clamp on batch max_tokens at admission.
    batch_max_tokens_cap: int = 128
    # Base Retry-After for shed responses (scaled by level).
    shed_retry_after_s: float = 2.0
    # Degradations (shed, max_tokens clamp, spec pause) require an
    # ACTUAL queue of at least this fraction of the backpressure cap:
    # the ladder exists to stop unbounded queue growth, and an engine
    # whose queue is empty serves everything at full quality regardless
    # of what its (possibly stale — ticks stop when stepping stops)
    # level or delay history says.
    shed_min_queue_frac: float = 0.125


class SloController:
    """Load estimator + brownout ladder, owned by the engine (all
    mutation happens on the engine loop thread; the runner reads
    ``level`` / drains observations from the same thread)."""

    def __init__(self, cfg: SloConfig, max_waiting: int, clock=None):
        self.cfg = cfg
        self.max_waiting = max(1, max_waiting)
        # injectable time source (runtime/clock.py): the brownout
        # ladder's hold-timer hysteresis must run in the engine's time —
        # virtual under replay — or a storm replayed in seconds would
        # never hold a level long enough to exit it
        self.clock = clock or MONOTONIC
        self.level = 0
        self._level_changed = self.clock.monotonic()
        # per-class queue-delay EWMAs (seconds); None until first sample
        self._delay_ewma: list[Optional[float]] = [None] * len(SLO_CLASSES)
        # padding efficiency EWMA (actual/padded tokens per dispatch):
        # waste derates delivered capacity, so the same queue depth is
        # more pressure on a badly-bucketed workload
        self._pad_eff = 1.0
        self._waiting = 0
        # queue-delay observations pending export into the per-class
        # histograms (drained by server/runner.py on the same thread)
        self.delay_obs: list[tuple[str, float]] = []
        self.shed_total = 0            # mirrored into EngineStats
        # flight recorder (runtime/flight.py), set by the engine (None
        # only for a controller built without one): every ladder
        # transition is logged against the
        # client-observable per-class SLIs the recorder holds, so a
        # brownout decision is auditable against what clients actually
        # experienced at that moment (not just the internal EWMAs)
        self.flight = None

    # ---- estimator inputs ------------------------------------------------

    def note_admission(self, rank: int, delay_s: float) -> None:
        """A fresh request left the waiting queue ``delay_s`` after
        arrival (re-admissions after preemption don't count — their
        wait is preemption policy, not admission load)."""
        a = self.cfg.ewma_alpha
        prev = self._delay_ewma[rank]
        self._delay_ewma[rank] = (delay_s if prev is None
                                  else (1 - a) * prev + a * delay_s)
        if len(self.delay_obs) < 4096:      # runner-less engines: bounded
            self.delay_obs.append((SLO_CLASSES[rank], delay_s))

    def note_step(self, actual: int, padded: int) -> None:
        if padded <= 0:
            return
        a = self.cfg.ewma_alpha
        self._pad_eff = (1 - a) * self._pad_eff + a * (actual / padded)

    def drain_delay_obs(self) -> list:
        obs, self.delay_obs = self.delay_obs, []
        return obs

    # ---- pressure + ladder ----------------------------------------------

    def pressure(self) -> float:
        # Queue term: depth vs the backpressure cap, inflated by padding
        # waste (at 0.5 efficiency half the dispatched tokens are
        # padding, so the queue drains half as fast) — but CAPPED at
        # 1.0: depth alone may climb the ladder only as far as shedding
        # BATCH (level 3 enters below 1.0).  A transient burst of small,
        # badly-bucketed prompts must never shed standard traffic.
        queue_term = min(self._waiting / self.max_waiting
                         * (2.0 - self._pad_eff), 1.0)
        # Delay term: the per-class admission-delay SLIs against their
        # targets.  Only a REAL sustained delay violation (EWMA past the
        # level-4 threshold) escalates past the queue cap.
        delay_term = 0.0
        tgt = self.cfg.target_queue_delay_s
        if self._delay_ewma[INTERACTIVE] is not None:
            delay_term = self._delay_ewma[INTERACTIVE] / tgt
        if self._delay_ewma[STANDARD] is not None:
            delay_term = max(delay_term,
                             self._delay_ewma[STANDARD] / (2 * tgt))
        return max(queue_term, delay_term)

    def tick(self, waiting: int, now: Optional[float] = None) -> None:
        """Re-evaluate the ladder once per engine cycle.  Entry is
        immediate (overload must not wait out a hold timer); exit steps
        down ONE level per hold_s and only under the entry threshold
        minus the margin."""
        self._waiting = waiting
        now = self.clock.monotonic() if now is None else now
        if waiting == 0:
            # an empty queue's admission delay IS zero: decay the
            # per-class EWMAs toward it, or a burst of slow (compile-
            # heavy, faulted) admissions would pin the ladder high on an
            # engine that has long since gone idle — and, since a
            # pinned ladder sheds the very admissions that would feed
            # fresh samples, it would never recover
            a = self.cfg.ewma_alpha
            self._delay_ewma = [None if v is None else (1 - a) * v
                                for v in self._delay_ewma]
        p = self.pressure()
        enter = self.cfg.enter_levels
        desired = 0
        for i, thr in enumerate(enter):
            if p >= thr:
                desired = i + 1
        if desired > self.level:
            self._log_transition(self.level, desired, p)
            self.level = desired
            self._level_changed = now
        elif (self.level > 0
              and p < enter[self.level - 1] - self.cfg.exit_margin
              and now - self._level_changed >= self.cfg.hold_s):
            self._log_transition(self.level, self.level - 1, p)
            self.level -= 1
            self._level_changed = now

    def _log_transition(self, old: int, new: int, pressure: float) -> None:
        """Ladder transitions logged against the flight recorder's
        client-observable SLI percentiles (TTFT/ITL/e2e per class):
        the decision record an operator reads after an incident."""
        sli = self.flight.sli_summary() if self.flight is not None else {}
        logger.info(
            "brownout level %d -> %d (pressure %.3f, waiting %d, "
            "pad_eff %.2f, delay_ewma %s, client SLI %s)",
            old, new, pressure, self._waiting, self._pad_eff,
            ["%.3f" % v if v is not None else "-"
             for v in self._delay_ewma], sli or "{}")

    def snapshot(self) -> dict:
        """Plain-scalar controller state for /debug/engine, flight
        bundles, and the autoscaler's scrape (ISSUE 12): the brownout
        level and per-class queue-delay EWMAs as numbers, so consumers
        never have to reconstruct them from histogram buckets."""
        return {
            "brownout_level": self.level,
            "queue_delay_ewma": {
                SLO_CLASSES[i]: (round(v, 6) if v is not None else None)
                for i, v in enumerate(self._delay_ewma)},
            "pressure": round(self.pressure(), 6),
        }

    # ---- policy queries --------------------------------------------------

    def _queue_pressure_live(self) -> bool:
        """EVERY degradation only BITES while a real queue exists
        (shed_min_queue_frac of the cap): degrading service on an engine
        with an empty queue protects nothing — and since ticks only run
        while the engine steps, a stale high level left over from a
        drained spike must not clamp/shed the lone request that arrives
        hours later."""
        return self._waiting >= self.cfg.shed_min_queue_frac \
            * self.max_waiting

    def shed_retry_after(self, rank: int) -> Optional[float]:
        """Seconds a shed response should ask the client to back off,
        or None when this class is admitted at the current level."""
        if not self._queue_pressure_live():
            return None
        if (self.level >= 4 and rank >= STANDARD) or \
                (self.level >= 3 and rank >= BATCH):
            return self.cfg.shed_retry_after_s * self.level
        return None

    def max_tokens_cap(self, rank: int) -> Optional[int]:
        if (self.level >= 2 and rank >= BATCH
                and self._queue_pressure_live()):
            return self.cfg.batch_max_tokens_cap
        return None

    def spec_paused_for(self, reqs) -> bool:
        """Brownout level 1+: dispatches carrying batch-class rows run
        without speculation (draft compute is the cheapest thing to
        shed — it only buys latency, which batch doesn't contract)."""
        return (self.level >= 1 and self._queue_pressure_live()
                and any(class_rank(r.params.slo_class) >= BATCH
                        for r in reqs))
