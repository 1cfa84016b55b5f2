"""Engine-loop spans: one primitive, on the profiler's clock.

The device loop is pipelined (one sync per S-token window), which makes
the PYTHON between dispatches the scaling wall at high stream counts —
DeepServe's host-overhead observation (PAPERS.md, arxiv 2501.14417).
``Span`` is the one way the engine loop times an interval: it opens a
``jax.profiler.TraceAnnotation`` (so while a profiler capture runs the
interval lands on the host plane of the SAME trace as the device's
``XLA Ops``, with a start and an end on the profiler's clock) and adds
its ``perf_counter`` seconds to the accumulators it was given.
``PROF.phase(name)`` is a span that feeds ``PROF.seconds[name]``;
devprof's ``sync(kind)`` / ``dispatch(kind, key)`` are spans named
``sync.<kind>`` / ``dispatch.<kind>`` that also feed the per-engine
device telemetry (runtime/devprof.py).  Nesting is by time on the one
engine-loop thread, which is how the trace carries a span's parent.

The span tree (where each opens -> what reads it):

    runner.intake   server/runner.py _loop: _drain_intake   idle.host_loop_share
    runner.route    _loop: _drain_engine_errors + _route_outputs      "
    runner.gauges   _loop: _update_gauges                             "
    engine.step     Engine.step, arg seq = the step record's seq; its
                    self time and what lies between spans is
                    idle.unattributed_share; seq joins
                    kernel.decode_attn_ns_per_ctx_tok to ctx_tokens
      slo.admission   _step_inner: deadline expiry, SLO pre-emption   idle.host_loop_share
      kv.restore      _commit_tier_restores + _begin_tier_restores    idle.kv_demote_share
      schedule        scheduler.schedule()            idle.host_loop_share, engine.cycle_host_ms
      block           block-manager crossings (_bm_*, reserve)        "
      kv.demote       _demote_evicted: the evicted pages' gather, enqueued
                      before the cycle's dispatch and handed to the tier
                      store's copier thread; _land_demotions: the copied pages
                      filed in the tier store, before a blocking sync
                      (also under sample) or going idle       idle.kv_demote_share
        sync.demote     only a WAIT for such a copy: the in-flight bound,
                        a restore (kv.restore) taking a hash in flight,
                        going idle                            ", engine.sync_wait_share
      dispatch        input arrays + the _exec_* hook  idle.host_loop_share, engine.cycle_host_ms
        dispatch.<kind> the async enqueue (first call: the compile)   idle.host_loop_share
      sample          _sample: host-side logit edits + sampler        "
      sync.<kind>     a designated device_get (window, decode, sample,
                      verify, draft, guided)   idle.sync_share, engine.sync_wait_share
      detokenize      append, detokenize, stop checks, emission
                                                      idle.host_loop_share, engine.cycle_host_ms
      step.close      note_step, SLO tick, note_control, block check  idle.host_loop_share

``flush`` is not a span: it is the sum of the cycle's ``sync.*`` spans
(every ``sync`` feeds it), kept because step records report it under
that name (``phase_ms.flush``).

START-UP has the same primitive and an accumulator of its own
(``STARTUP``, so the engine loop's per-cycle diffs never see it): what a
pod restart or a scale-up spends before its first token, by stage.  A
capture taken while the process starts (``jax.profiler.start_trace``
around ``build_server``) shows these on the host plane beside the device's
first programs; their seconds are ``/debug/engine`` ``startup.phases``,
beside the compile ledger's totals at the first served token
(utils/compile_cache.py) and ``cold_start_s``.  A slow scale-up is read
from ``/metrics`` first: ``tpuserve_compile_cache_misses_total`` over hits
+ misses says whether the programs were compiled or read, then the stage.

    startup.build     server/openai_api.py build_server, argv parsed to
                      the server object: everything below but the warm-up
                                        tpuserve_startup_build_seconds, setup.build_s
      startup.backend   the first touch of the backend there: the TPU
                        runtime's start (~0 where the caller touched it
                        first, as the benchmark does)
      startup.weights   Engine.__init__: the checkpoint's load or the
                        initialisers' ENQUEUE, adapters, quantisation.  No
                        sync closes it: what the device still owes
                        surfaces under the first span that waits for it
                        (the warm-up's closing block_until_ready)
      startup.pools     Engine.__init__: the paged cache, the state pool,
                        the block manager, scheduler and recorder, up to
                        devprof.set_hbm
    startup.warmup    Engine.warmup (both rounds); a second call adds
                                        tpuserve_startup_warmup_seconds, setup.warmup_s
      startup.warmup.prefill / .decode / .chunk / .ragged
                        one a bucket of each of _warmup's four loops, args
                        round and bucket; decode holds its decode_multi
                        variants and the chained-token selects.  What is
                        left of startup.warmup is the KV tier's gathers,
                        embed buckets and the closing wait for the device

Cost: a span is one ``TraceAnnotation`` and two ``perf_counter`` calls,
always: there is no off state, and the cost was measured on the chip
(PERF.md §6, PR 24).  The profiler is engine-loop single-threaded like
everything it brackets; per-cycle deltas in multi-engine processes are
approximate (FlightRecorder.note_step).
"""

from __future__ import annotations

import time
from collections import defaultdict

from jax.profiler import TraceAnnotation


class Span:
    """One timed interval of the engine loop (see the module docstring).
    ``sinks`` are ``(seconds, counts, key)`` accumulator triples fed at
    exit; ``done(dt)`` is devprof's ladder bookkeeping; ``args`` go into
    the trace as the annotation's arguments."""

    __slots__ = ("_ann", "_sinks", "_done", "_t0")

    def __init__(self, name, sinks, done=None, **args):
        self._ann = TraceAnnotation(name, **args)
        self._sinks = sinks
        self._done = done

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        for seconds, counts, key in self._sinks:
            seconds[key] += dt
            counts[key] += 1
        if self._done is not None:
            self._done(dt)
        return False


class HostPhaseProfiler:
    """Accumulates wall seconds per span name; ``cycles`` is bumped once
    per engine cycle (the denominator for ms-per-cycle)."""

    def __init__(self):
        self.seconds: dict[str, float] = defaultdict(float)
        self.counts: dict[str, int] = defaultdict(int)
        self.cycles = 0

    def sinks(self, *names) -> tuple:
        """Accumulator triples for a span that feeds these names."""
        return tuple((self.seconds, self.counts, n) for n in names)

    def phase(self, name: str, **args):
        return Span(name, self.sinks(name), **args)

    def bump_cycle(self) -> None:
        self.cycles += 1

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()
        self.cycles = 0


# module singleton: the engine loop is single-threaded, and profile runs
# build one engine per process
PROF = HostPhaseProfiler()
# the start-up spans' seconds and counts (``STARTUP.phase(name)``), by the
# process like the compile ledger: engines built or warmed again add to
# the same keys
STARTUP = HostPhaseProfiler()
