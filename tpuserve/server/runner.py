"""Background engine loop with a thread-safe request interface.

The Engine itself is single-threaded (all device work happens on the loop
thread); HTTP handler threads talk to it through an intake queue and
per-request output queues.  This is the process-level analog of vLLM's
AsyncLLMEngine inside the container the reference deploys.
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from collections import deque
from typing import Optional, Sequence, Union

from tpuserve.models import transformer
from tpuserve.runtime.clock import MONOTONIC
from tpuserve.runtime.engine import Engine
from tpuserve.runtime.hostprof import PROF, STARTUP
from tpuserve.runtime.request import RequestOutput, RequestState, SamplingParams
from tpuserve.runtime.slo import ShedError
from tpuserve.utils.compile_cache import LEDGER

logger = logging.getLogger("tpuserve.server")

# Cold-start anchor (ISSUE 12): stamped at module import, which `python
# -m tpuserve.server` reaches before weights load or XLA compiles — so
# first-token minus this is the cold-pod-to-first-token number the
# autoscaler exports as tpuserve_cold_start_seconds.  Wall-bound by
# nature (a pod boots in real seconds, never in replay time).
_BOOT_MONOTONIC = time.monotonic()  # tpulint: sync-ok(cold start is real wall seconds, anchored at process boot)


def _advance_counter(ctr, cumulative) -> None:
    """Advance a prometheus Counter to an engine-side cumulative value
    (counters only go up; engines keep their own monotonic totals)."""
    current = ctr._value.get()
    if cumulative > current:
        ctr.inc(cumulative - current)


@dataclasses.dataclass
class _Submit:
    prompt: Optional[str]
    prompt_token_ids: Optional[list[int]]
    params: SamplingParams
    out_queue: "queue.Queue[RequestOutput | Exception | None]"
    rid_event: threading.Event
    request_id: Optional[str] = None
    assigned_id: Optional[str] = None
    adapter: Optional[str] = None     # multi-LoRA adapter name
    # admission deadline (time.monotonic): still queued past this, the
    # engine aborts the request queue-side (no prefill spent) and the
    # client gets a TimeoutError through the output queue
    deadline: Optional[float] = None
    # model-pool routing (tpuserve/modelpool): a registered-but-not-
    # current model name parks the submit until the pool swaps to it
    model: Optional[str] = None


@dataclasses.dataclass
class _Abort:
    request_id: str


@dataclasses.dataclass
class _SalvageState:
    """Poison-batch bisection in progress: suspect request groups are
    replayed in isolation (scheduler admission filter) until the dispatch
    that faults shrinks to a single request — the poison — which is then
    failed with a clean per-request error while everyone else resumes."""
    groups: deque                 # deque[set[str]] groups still to probe
    cleared: set                  # rids that survived a probe (run freely)
    active: Optional[set] = None  # group currently being probed
    ok_steps: int = 0             # successful steps since the probe started


@dataclasses.dataclass
class _InjectPrefilled:
    """Cross-pod disaggregation: a sequence prefilled on another pod, to be
    adopted into this engine's decode batch (parallel/disagg_net.py)."""
    meta: dict
    seq_kv: list
    out_queue: "queue.Queue[RequestOutput | Exception | None]"
    rid_event: threading.Event
    assigned_id: Optional[str] = None
    error: Optional[Exception] = None


class AsyncEngineRunner:
    """Runs engine.step() on a dedicated thread; routes outputs to callers.

    Works with any engine exposing add_request/step/has_work/abort_request —
    both Engine and DisaggregatedEngine.
    """

    # crash-only tuning knobs (instance attrs so tests/operators can adjust)
    MAX_SALVAGES = 12            # consecutive faulted attempts per request;
    #                              must exceed ~2+log2(batch) so an innocent
    #                              sharing bisection rounds with a poison
    #                              request never exhausts it first
    PROBE_OK_STEPS = 3           # fault-free steps before a group is cleared
    POISON_CONFIRM = 3           # consecutive SINGLETON-probe faults before
    #                              a request is declared poison — transient
    #                              chaos that happened to fault a singleton
    #                              probe once must not kill an innocent
    #                              stream; a real poison re-faults every probe
    MAX_FAULTS_PER_WINDOW = 20   # whole-engine faults inside FAULT_WINDOW_S
    FAULT_WINDOW_S = 30.0        # before falling back to fail-all
    WATCHDOG_WARMUP_STEPS = 10   # early steps may include XLA compiles:
    WATCHDOG_WARMUP_SCALE = 20.0  # scale the hang threshold up for them

    def __init__(self, engine, metrics=None):
        self.engine = engine
        self.metrics = metrics
        # The engine's injectable clock seam (runtime/clock.py): request
        # SLI stamps (_req_started / _route_outputs) run in ENGINE time so
        # a replay-driven engine records virtual-time SLIs; real-wall
        # concerns (watchdog hang detection, client queue waits, fault-
        # storm windows) stay on the real clock below.
        self._clock = getattr(engine, "clock", MONOTONIC)
        # Optional hook fed with the wall-clock seconds of each engine.step()
        # — the TPU duty-cycle source for tpu_metrics.TpuMetricsExporter.
        self.on_step_time = None
        self._intake: "queue.Queue[_Submit | _Abort]" = queue.Queue()
        self._out_queues: dict[str, queue.Queue] = {}
        self._req_started: dict[str, float] = {}
        self._last_token_time: dict[str, float] = {}
        # routed rows at the last pass that wrote the per-expert counters
        self._moe_rows_exported = 0.0
        self._layer_calls_exported = 0
        # compile-ledger events + warm-up calls already on /metrics
        self._startup_exported = -1
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tpuserve-engine-loop")
        self._started = False
        # crash-only recovery state (salvage + bisection + watchdog)
        self.max_salvages = self.MAX_SALVAGES
        self.probe_ok_steps = self.PROBE_OK_STEPS
        self.poison_confirm = self.POISON_CONFIRM
        self._singleton_faults: dict[str, int] = {}
        self.step_watchdog_s = float(getattr(
            getattr(engine, "config", None), "step_watchdog_s", 0.0) or 0.0)
        self._fault_times: list[float] = []
        self._salvage: Optional[_SalvageState] = None
        self._steps_done = 0
        self._step_seq = 0
        self._step_started: Optional[tuple[int, float]] = None
        self._hard_trip_seq: Optional[int] = None
        self._fail_lock = threading.Lock()
        self._watchdog_thread: Optional[threading.Thread] = None
        # boot -> first served token, wall seconds (None until the first
        # token leaves); /healthz + /debug/engine report it and the
        # autoscaler's probe feeds it into tpuserve_cold_start_seconds
        self.cold_start_s: Optional[float] = None
        # the compile ledger's totals as they stood at that token
        # (/debug/engine startup.compile)
        self.startup_compile: Optional[dict] = None
        # In-process SLO burn-rate evaluation (tpuserve/obs/burnrate.py):
        # set by the server when enabled.  Fed and evaluated ONLY on the
        # loop thread (observe at delivery, evaluate throttled in
        # _update_gauges), timestamps through the engine clock seam so a
        # replay-driven runner evaluates in virtual time.
        self.slo_eval = None
        self._slo_eval_last: Optional[float] = None
        # fast-burn auto-capture (runtime/devprof.py + server/tracing.py):
        # wall-clock cooldown stamp so a flapping page takes ONE
        # jax.profiler trace per window, not one per transition
        self._auto_capture_last: Optional[float] = None
        # Model pool (tpuserve/modelpool): set by the server when a
        # catalog is configured and TPUSERVE_MODELPOOL isn't 0.  Submits
        # naming a registered-but-not-current model park here until the
        # pool hot-swaps at an idle boundary (_maybe_swap_pool).
        self.pool = None
        self._parked: list[_Submit] = []

    # ---- lifecycle -----------------------------------------------------

    def start(self) -> None:
        if not self._started:
            self._started = True
            self._thread.start()
            if self.step_watchdog_s > 0:
                self._watchdog_thread = threading.Thread(
                    target=self._watchdog_loop, daemon=True,
                    name="tpuserve-engine-watchdog")
                self._watchdog_thread.start()

    def idle(self) -> bool:
        """No engine work and no undelivered outputs — safe to stop.
        Polled by the server's graceful drain."""
        try:
            busy = self.engine.has_work()
        except Exception:
            busy = False
        # _intake matters too: a request accepted by the handler just
        # before draining flipped may still sit queued for the engine
        # loop — stopping now would silently drop it; same for submits
        # parked behind a pending model swap
        return (not busy and not self._out_queues and self._intake.empty()
                and not self._parked)

    def shutdown(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._started:
            self._thread.join(timeout=30)

    # ---- client API (any thread) ---------------------------------------

    def submit(self, prompt: Optional[str] = None,
               prompt_token_ids: Optional[Sequence[int]] = None,
               params: Optional[SamplingParams] = None,
               request_id: Optional[str] = None,
               adapter: Optional[str] = None,
               deadline: Optional[float] = None,
               model: Optional[str] = None,
               ) -> tuple[str, "queue.Queue[RequestOutput | Exception | None]"]:
        """Enqueue a request; returns (request_id, output queue).  The queue
        yields RequestOutput items, then None when finished; an Exception
        item signals a rejected request.  ``model`` routes through the
        model pool: a registered-but-not-current name parks the request
        until the engine hot-swaps to it."""
        sub = _Submit(prompt=prompt,
                      prompt_token_ids=list(prompt_token_ids) if prompt_token_ids else None,
                      params=params or SamplingParams(),
                      out_queue=queue.Queue(), rid_event=threading.Event(),
                      request_id=request_id, adapter=adapter,
                      deadline=deadline, model=model)
        self._intake.put(sub)
        self._wake.set()
        sub.rid_event.wait(timeout=60)
        if sub.assigned_id is None:
            raise TimeoutError("engine loop did not accept the request")
        return sub.assigned_id, sub.out_queue

    def abort(self, request_id: str) -> None:
        self._intake.put(_Abort(request_id))
        self._wake.set()

    def submit_prefilled(self, meta: dict, seq_kv: list
                         ) -> tuple[str, "queue.Queue"]:
        """Adopt a migrated (already-prefilled) sequence on the engine loop
        thread; raises the loop-side error (MemoryError = pool full, which
        the HTTP layer maps to 503 backpressure)."""
        msg = _InjectPrefilled(meta=meta, seq_kv=seq_kv,
                               out_queue=queue.Queue(),
                               rid_event=threading.Event())
        self._intake.put(msg)
        self._wake.set()
        msg.rid_event.wait(timeout=60)
        if msg.error is not None:
            raise msg.error
        if msg.assigned_id is None:
            raise TimeoutError("engine loop did not accept the migration")
        return msg.assigned_id, msg.out_queue

    def generate_sync(self, prompt=None, prompt_token_ids=None, params=None,
                      timeout: float = 600.0):
        """Blocking convenience: returns (list[RequestOutput], request_id)."""
        rid, q = self.submit(prompt=prompt, prompt_token_ids=prompt_token_ids,
                             params=params)
        outs = []
        # tpulint: sync-ok(client-side wall-clock wait on the output queue, not engine time)
        deadline = time.monotonic() + timeout
        while True:
            # tpulint: sync-ok(client-side wall-clock wait on the output queue, not engine time)
            item = q.get(timeout=max(deadline - time.monotonic(), 0.001))
            if item is None:
                getattr(self.engine, "requests", {}).pop(rid, None)
                return outs, rid
            if isinstance(item, Exception):
                getattr(self.engine, "requests", {}).pop(rid, None)
                raise item
            outs.append(item)

    # ---- engine loop ----------------------------------------------------

    def _drain_intake(self) -> None:
        while True:
            try:
                msg = self._intake.get_nowait()
            except queue.Empty:
                return
            if isinstance(msg, _Abort):
                if self.engine.abort_request(msg.request_id):
                    q = self._out_queues.pop(msg.request_id, None)
                    getattr(self.engine, "requests", {}).pop(msg.request_id, None)
                    self._req_started.pop(msg.request_id, None)
                    self._last_token_time.pop(msg.request_id, None)
                    if q is not None:
                        q.put(None)
                continue
            if isinstance(msg, _InjectPrefilled):
                from tpuserve.parallel.disagg_net import sampling_from_dict
                m = msg.meta
                try:
                    rid = self.engine.adopt_prefilled(
                        m["request_id"], m["prompt_token_ids"],
                        m["first_token"], sampling_from_dict(m["params"]),
                        msg.seq_kv, guided_plan=m.get("guided_plan"))
                except Exception as e:
                    msg.error = e
                    msg.rid_event.set()
                    continue
                msg.assigned_id = rid
                self._out_queues[rid] = msg.out_queue
                self._req_started[rid] = self._clock.monotonic()
                self._last_token_time[rid] = self._req_started[rid]
                if self.metrics:
                    self.metrics.request_total.inc()
                    self.metrics.prompt_tokens.inc(len(m["prompt_token_ids"]))
                msg.rid_event.set()
                continue
            if (msg.model and self.pool is not None
                    and msg.model != self.pool.current):
                # Model-pool routing: a registered foreign model parks
                # until the pool swaps at the next idle boundary
                # (_maybe_swap_pool re-injects it); demand is noted so
                # spill->host prefetch warms the target WHILE the engine
                # drains, and so the autoscaler's per-model signal sees
                # it.  The API edge 404s unknown names first; this is
                # the belt-and-braces typed rejection.
                if self.pool.is_registered(msg.model):
                    self.pool.note_demand(msg.model)
                    self.pool.request_swap(msg.model)
                    self._parked.append(msg)
                    continue
                msg.assigned_id = msg.request_id or "rejected"
                msg.rid_event.set()
                msg.out_queue.put(ValueError(
                    f"model {msg.model!r} is not in this replica's catalog"))
                msg.out_queue.put(None)
                continue
            try:
                kw = {"adapter": msg.adapter} if msg.adapter else {}
                if msg.deadline is not None:
                    kw["deadline"] = msg.deadline
                rid = self.engine.add_request(
                    prompt=msg.prompt, prompt_token_ids=msg.prompt_token_ids,
                    params=msg.params, request_id=msg.request_id, **kw)
            except Exception as e:           # invalid request: report, don't die
                if (self.slo_eval is not None
                        and isinstance(e, (MemoryError, ShedError))
                        and not getattr(msg.params, "canary", False)):
                    # intake shed/backpressure is unavailability the
                    # client saw; invalid-request errors are not
                    self.slo_eval.observe_outcome(
                        getattr(msg.params, "slo_class", "standard"),
                        False)
                msg.assigned_id = msg.request_id or "rejected"
                msg.rid_event.set()
                msg.out_queue.put(e)
                msg.out_queue.put(None)
                continue
            msg.assigned_id = rid
            self._out_queues[rid] = msg.out_queue
            self._req_started[rid] = self._clock.monotonic()
            self._last_token_time[rid] = self._req_started[rid]
            if self.metrics:
                self.metrics.request_total.inc()
                req = getattr(self.engine, "requests", {}).get(rid)
                if req is not None:
                    self.metrics.prompt_tokens.inc(req.num_prompt_tokens)
            msg.rid_event.set()

    def _slo_class_of(self, rid: str) -> str:
        req = getattr(self.engine, "requests", {}).get(rid)
        return getattr(getattr(req, "params", None), "slo_class", "standard")

    def _sli_ident(self, rid: str) -> tuple:
        """(slo_class, canary) for a live request — canary probes
        (tpuserve/obs/canary.py) are excluded from every production SLI
        histogram and the burn-rate stream; they get their own
        black-box families from the prober side."""
        req = getattr(self.engine, "requests", {}).get(rid)
        p = getattr(req, "params", None)
        return (getattr(p, "slo_class", "standard"),
                getattr(p, "canary", False))

    def _route_outputs(self, outputs: list[RequestOutput]) -> None:
        now = self._clock.monotonic()
        # every inner engine's recorder gets the SLIs: a disagg pod's
        # decode engine must not log empty client SLIs on brownout
        flights = self._flights()
        for out in outputs:
            if self.cold_start_s is None and out.new_token_ids:
                # cold-pod-to-first-token: the first token ANY request
                # receives from this process (wall seconds since module
                # import — weights, compiles and warm-prefix restores
                # all inside the measurement)
                self.cold_start_s = round(
                    time.monotonic() - _BOOT_MONOTONIC, 6)  # tpulint: sync-ok(cold start is real wall seconds)
                self.startup_compile = LEDGER.totals()
                logger.info("cold start: first token %.3fs after boot",
                            self.cold_start_s)
            q = self._out_queues.get(out.request_id)
            if self.metrics or flights or self.slo_eval is not None:
                cls, canary = self._sli_ident(out.request_id)
                last = self._last_token_time.get(out.request_id)
                if self.metrics:
                    self.metrics.generation_tokens.inc(
                        len(out.new_token_ids))
                label = dict(model_name=getattr(self.metrics, "model_name",
                                                ""), slo_class=cls)
                if last is not None and not canary:
                    if out.num_output_tokens == 1:
                        ttft = now - self._req_started.get(
                            out.request_id, now)
                        if self.metrics:
                            self.metrics.ttft.observe(ttft)
                            self.metrics.ttft_class.labels(
                                **label).observe(ttft)
                        for fl in flights:
                            fl.note_sli(cls, "ttft", ttft)
                        if self.slo_eval is not None:
                            self.slo_eval.observe(cls, "ttft", ttft)
                    elif not out.from_prefill:
                        # A from_prefill emission with output tokens > 1 is a
                        # re-prefill after preemption: its gap is queue +
                        # recompute time and would blow out the ITL histogram.
                        if self.metrics:
                            self.metrics.itl.observe(now - last)
                            self.metrics.itl_class.labels(
                                **label).observe(now - last)
                        for fl in flights:
                            fl.note_sli(cls, "itl", now - last)
                        if self.slo_eval is not None:
                            self.slo_eval.observe(cls, "itl", now - last)
                self._last_token_time[out.request_id] = now
            if q is not None:
                q.put(out)
            if out.finished:
                if self.metrics or flights or self.slo_eval is not None:
                    started = self._req_started.pop(out.request_id, now)
                    reason = out.finish_reason.value if out.finish_reason else "stop"
                    if canary:
                        # a served canary still proves the path works —
                        # counted in its own family, absent everywhere
                        # a tenant or an SLI reader would see it
                        if self.metrics:
                            self.metrics.canary_requests.inc()
                            self.metrics.request_success.labels(
                                model_name=self.metrics.model_name,
                                finished_reason=reason).inc()
                    else:
                        if self.metrics:
                            self.metrics.observe_finish(reason,
                                                        now - started)
                            self.metrics.e2e_class.labels(
                                **label).observe(now - started)
                        for fl in flights:
                            fl.note_sli(cls, "e2e", now - started)
                        if self.slo_eval is not None:
                            self.slo_eval.observe(cls, "e2e",
                                                  now - started)
                            self.slo_eval.observe_outcome(
                                cls, reason in ("stop", "length"))
                self._last_token_time.pop(out.request_id, None)
                # NOTE: the request record stays in engine.requests — the
                # caller that submitted claims (pops) it for usage/logprobs.
                if q is not None:
                    self._out_queues.pop(out.request_id, None)
                    q.put(None)

    # ---- crash-only recovery: salvage, bisection, watchdog --------------

    def _inner_engines(self) -> list:
        eng = self.engine
        inners = [e for e in (getattr(eng, "prefill", None),
                              getattr(eng, "decode", None)) if e is not None]
        return inners or [eng]

    def _bump_stat(self, name: str, n: int = 1) -> None:
        """Count a recovery event on the engine's stats object (exported by
        _update_gauges); disagg facades carry stats on their inner
        engines — charge the first one so the counter still surfaces."""
        for e in self._inner_engines():
            stats = getattr(e, "stats", None)
            if stats is not None and hasattr(stats, name):
                # tpulint: thread-ok(advisory stats counter; benign race, no engine-loop invariant reads it)
                setattr(stats, name, getattr(stats, name) + n)
                return

    def _set_admission_filter(self, allowed) -> None:
        for e in self._inner_engines():
            sched = getattr(e, "scheduler", None)
            if sched is not None and hasattr(sched, "set_admission_filter"):
                sched.set_admission_filter(allowed)

    def _fail_all(self, message: str, engine_side: bool = True) -> None:
        """The pre-salvage crash-only fallback: fail every in-flight stream
        and drain the engine so nothing re-raises in a tight loop.

        ``engine_side=False`` is the watchdog-thread variant: only the
        client queues (thread-safe) are touched, because the loop thread
        may still be INSIDE the stuck dispatch and scheduler/block-manager
        state must not be mutated under it — `_consume_hard_trip` does the
        engine-side cleanup on the loop thread if the call ever returns."""
        with self._fail_lock:
            for rid, q in list(self._out_queues.items()):
                if engine_side:
                    try:
                        # tpulint: thread-ok(engine_side=True only on the loop thread; watchdog passes False, _consume_hard_trip reconciles loop-side)
                        self.engine.abort_request(rid)
                    except Exception:
                        pass
                    # tpulint: thread-ok(guarded by engine_side, loop-thread-only branch)
                    getattr(self.engine, "requests", {}).pop(rid, None)
                q.put(RuntimeError(message))
                q.put(None)
            # tpulint: thread-ok(client-queue map; writers serialised by _fail_lock, readers tolerate missing entries)
            self._out_queues.clear()
            # tpulint: thread-ok(timing map under _fail_lock; metrics-only)
            self._req_started.clear()
            # tpulint: thread-ok(timing map under _fail_lock; metrics-only)
            self._last_token_time.clear()
            # tpulint: thread-ok(bisection evidence reset under _fail_lock)
            self._singleton_faults.clear()

    def _fail_request(self, rid: str, message: str,
                      poisoned: bool = False,
                      exc: Optional[Exception] = None) -> None:
        """Fail ONE stream with a clean per-request error — the whole point
        of salvage: a poisoned batch costs one request, not a batch.
        ``exc`` overrides the default RuntimeError so typed rejections
        (ShedError -> 429, TimeoutError -> 504) keep their HTTP status."""
        if self.slo_eval is not None or self.metrics:
            # availability SLI: every engine-decided terminal error
            # (shed, deadline expiry, salvage exhaustion, poison) is a
            # bad event for the burn-rate engine — read BEFORE the
            # abort drops the request record
            cls, canary = self._sli_ident(rid)
            if self.slo_eval is not None and not canary:
                self.slo_eval.observe_outcome(cls, False)
            if (self.metrics and not canary and not poisoned
                    and not isinstance(exc, ShedError)):
                # shed and poison have their own counters; this family
                # covers the rest (deadline 504s, salvage errors) so
                # the availability PromQL twin sees the same bad
                # events the in-process evaluator does
                self.metrics.requests_failed.inc()
        try:
            self.engine.abort_request(rid)
        except Exception:
            pass
        getattr(self.engine, "requests", {}).pop(rid, None)
        self._req_started.pop(rid, None)
        self._last_token_time.pop(rid, None)
        q = self._out_queues.pop(rid, None)
        if q is not None:
            q.put(exc if exc is not None else RuntimeError(message))
            q.put(None)
        if poisoned:
            self._bump_stat("requests_poisoned")
            # the isolated request's full lifecycle (faults included) is
            # exactly what a poison investigation needs
            self._dump_postmortem("poison", (rid,))
        logger.warning("request %s failed: %s", rid, message)

    def _drain_engine_errors(self) -> None:
        """Terminal errors the engine decided for QUEUED requests
        (admission-deadline expiry, queue-full class eviction —
        runtime/slo.py): route each to its waiting client with the typed
        exception so the HTTP layer keeps the right status code."""
        for eng in self._inner_engines():
            drain = getattr(eng, "drain_request_errors", None)
            if drain is None:
                continue
            for rid, exc in drain():
                self._fail_request(rid, str(exc), exc=exc)

    def _handle_step_fault(self, exc: Exception) -> None:
        """Salvage instead of mass-fail: requeue every in-flight request
        through the engine's preemption re-prefill path and replay; a
        cohort that faults AGAIN is bisected until the poison request(s)
        are isolated and failed individually.  Engines without the salvage
        hook, and fault storms past MAX_FAULTS_PER_WINDOW, fall back to
        the old fail-all (+ tpuserve_engine_restarts)."""
        # tpulint: sync-ok(fault-storm rate window is a real-wall chaos measure)
        now = time.monotonic()
        self._fault_times = [t for t in self._fault_times
                             if now - t < self.FAULT_WINDOW_S]
        self._fault_times.append(now)
        eng = self.engine
        salvage = getattr(eng, "salvage_requeue", None)
        if (salvage is None
                or len(self._fault_times) > self.MAX_FAULTS_PER_WINDOW):
            self._bump_stat("engine_restarts")
            if len(self._fault_times) > self.MAX_FAULTS_PER_WINDOW:
                # fault storm: capture the flight state BEFORE fail-all
                # wipes the client map — the bundle is the incident record
                self._dump_postmortem("fault_storm")
            self._salvage = None
            self._set_admission_filter(None)
            self._fail_all(f"engine failure: {exc}")
            return
        salvage()
        # charge the fault against the requests that were actually in the
        # faulted dispatch (engine._dispatch_rids); a fault outside any
        # dispatch (window flush at an idle step) charges everyone live
        dispatched = set(getattr(eng, "_dispatch_rids", ()) or ())
        requests = getattr(eng, "requests", {})
        cohort = []
        for rid in list(self._out_queues):
            req = requests.get(rid)
            if req is None or req.finished:
                continue
            if dispatched and rid not in dispatched:
                continue
            req.num_salvages += 1
            if req.num_salvages > self.max_salvages:
                self._fail_request(
                    rid, f"request failed {req.num_salvages} consecutive "
                         f"faulted engine steps (salvage budget "
                         f"{self.max_salvages} exhausted): {exc}",
                    poisoned=True)
            else:
                cohort.append(rid)
                self._bump_stat("requests_salvaged")
        if not cohort:
            self._salvage = None
            self._set_admission_filter(None)
            return
        if self._salvage is None:
            # first fault: replay the whole cohort as one probe group — a
            # transient fault salvages everyone with no bisection at all
            self._salvage = _SalvageState(groups=deque([set(cohort)]),
                                          cleared=set())
        else:
            st = self._salvage
            suspect = set(st.active if st.active else cohort) & set(cohort)
            st.active = None
            st.ok_steps = 0
            if len(suspect) <= 1:
                for rid in suspect:
                    n = self._singleton_faults.get(rid, 0) + 1
                    self._singleton_faults[rid] = n
                    if n >= self.poison_confirm:
                        self._singleton_faults.pop(rid, None)
                        self._fail_request(
                            rid, "poison request isolated by fault "
                                 f"bisection ({n} consecutive solo "
                                 f"faults): {exc}", poisoned=True)
                    else:
                        # could still be transient chaos that landed on a
                        # solo probe: re-probe before condemning it
                        st.groups.appendleft({rid})
            else:
                # the probed group faulted again: bisect and probe halves
                ordered = sorted(suspect)
                half = len(ordered) // 2
                st.groups.appendleft(set(ordered[half:]))
                st.groups.appendleft(set(ordered[:half]))
        self._advance_salvage()

    def _advance_salvage(self) -> None:
        """Arm the next probe group (admission filter = cleared ∪ active);
        lift the filter when nothing is left to probe."""
        st = self._salvage
        if st is None:
            self._set_admission_filter(None)
            return
        while st.active is None and st.groups:
            group = {rid for rid in st.groups.popleft()
                     if rid in self._out_queues}
            if group:
                st.active = group
                st.ok_steps = 0
        if st.active is None:
            self._salvage = None
            self._set_admission_filter(None)
            return
        self._set_admission_filter(st.cleared | st.active)

    def _note_salvage_progress(self) -> None:
        """Called after every successful engine step while a probe is
        armed: a group that ran PROBE_OK_STEPS fault-free dispatches (or
        finished outright) is cleared, and the next suspect group probes."""
        st = self._salvage
        if st is None or st.active is None:
            return
        live = {rid for rid in st.active if rid in self._out_queues}
        if live:
            requests = getattr(self.engine, "requests", {})
            if not all(getattr(requests.get(rid), "state", None)
                       == RequestState.RUNNING for rid in live):
                return          # probe group not fully (re-)admitted yet
            st.ok_steps += 1
            if st.ok_steps < self.probe_ok_steps:
                return
        for rid in st.active:
            # a clean solo probe exonerates: reset its poison evidence
            self._singleton_faults.pop(rid, None)
        st.cleared |= st.active
        st.active = None
        self._advance_salvage()

    # ---- hang watchdog ---------------------------------------------------

    def _fault_injectors(self) -> list:
        return [f for f in (getattr(e, "faults", None)
                            for e in self._inner_engines()) if f is not None]

    def _flights(self) -> list:
        """Flight recorders of the inner engines (runtime/flight)."""
        return [f for f in (getattr(e, "flight", None)
                            for e in self._inner_engines())
                if f is not None]

    def _dump_postmortem(self, reason: str, rids=()) -> None:
        """Write flight post-mortem bundles (last N cycles + affected
        request timelines) and count them.  Called from the loop thread
        on fault-storm fail-all / poison isolation, and from the
        WATCHDOG thread on a trip — the recorder's snapshot-read
        contract makes the cross-thread dump safe even while the loop
        thread is wedged inside the stuck dispatch."""
        for fl in self._flights():
            # snapshot-read dump, safe from the watchdog thread: the
            # recorder mutates only its own counters (runtime/flight.py
            # threading contract)
            if fl.postmortem(reason, rids) is not None:
                self._bump_stat("flight_postmortems")

    def _watchdog_threshold(self) -> float:
        if self._steps_done < self.WATCHDOG_WARMUP_STEPS:
            # early steps legitimately include multi-second XLA compiles
            return self.step_watchdog_s * self.WATCHDOG_WARMUP_SCALE
        return self.step_watchdog_s

    def _watchdog_loop(self) -> None:
        """Monitor thread: engine.step() entries are stamped by the loop;
        a step past the threshold is declared stuck.  Stage 1 (trip):
        count it and release injected hangs, which then raise into the
        normal salvage path.  Stage 2 (a REAL hang, still stuck past 2x):
        fail the waiting clients from here — crash-only, the loop thread
        may never come back — so a wedged device call never strands
        clients behind a silent server."""
        poll = max(0.005, min(0.05, self.step_watchdog_s / 5))
        tripped_seq = None
        while not self._stop.wait(poll):
            cur = self._step_started
            if cur is None:
                continue
            seq, t0 = cur
            threshold = self._watchdog_threshold()
            running_s = time.monotonic() - t0  # tpulint: sync-ok(watchdog measures REAL hang time; a virtual clock would never trip)
            if running_s < threshold:
                continue
            if self._step_started != cur:
                # the step completed between the stamp read and now: a
                # healthy (if slow) dispatch, not a hang — don't trip
                continue
            if tripped_seq != seq:
                tripped_seq = seq
                self._bump_stat("watchdog_trips")
                logger.warning(
                    "engine step stuck for %.2fs (watchdog %.2fs): "
                    "releasing injected hangs, failing the dispatch",
                    running_s, threshold)
                # capture the stuck step's flight state NOW, from this
                # thread — the loop thread is inside the wedged dispatch
                # and may never come back to write it
                self._dump_postmortem("watchdog_trip")
                for inj in self._fault_injectors():
                    inj.release_hangs()
            elif (running_s > 2 * threshold
                    and self._hard_trip_seq != seq):
                # nothing released it: a real wedged dispatch.  Fail the
                # clients now; the loop thread reconciles engine state if
                # and when the stuck call ever returns.
                self._hard_trip_seq = seq
                self._bump_stat("engine_restarts")
                logger.error("engine step still stuck after %.2fs: failing "
                             "all in-flight clients (crash-only restart)",
                             running_s)
                # clients only: the loop thread is wedged inside the
                # dispatch, so engine state is reconciled loop-side by
                # _consume_hard_trip, never mutated from this thread
                self._fail_all("engine step stuck (watchdog)",
                               engine_side=False)

    def _consume_hard_trip(self, seq: int) -> bool:
        """Loop-side reconciliation after a stage-2 watchdog trip: the
        clients are already failed, so drop the step's outcome and reset
        engine-side request state."""
        if self._hard_trip_seq != seq:
            return False
        self._hard_trip_seq = None
        eng = self.engine
        for rid in list(getattr(eng, "requests", {})):
            try:
                eng.abort_request(rid)
            except Exception:
                pass
            eng.requests.pop(rid, None)
        self._salvage = None
        self._set_admission_filter(None)
        return True

    def _evaluate_slo(self) -> None:
        """Advance the in-process burn-rate engine (loop thread; at most
        once per engine-clock second — the window math scans buckets)
        and export its state: transitions counter, per-objective burn
        gauge, firing count."""
        ev = self.slo_eval
        if ev is None:
            return
        from tpuserve.obs.burnrate import EVAL_INTERVAL_S
        now = self._clock.monotonic()
        if (self._slo_eval_last is not None
                and now - self._slo_eval_last < EVAL_INTERVAL_S):
            return
        self._slo_eval_last = now
        transitions = ev.evaluate()
        for tr in transitions:
            logger.warning("SLO burn-rate alert %s: %s/%s "
                           "(burn %.1fx long / %.1fx short)",
                           tr["state"].upper(), tr["objective"],
                           tr["window"], tr["burn_long"],
                           tr["burn_short"])
        self._maybe_auto_capture(transitions)
        if not self.metrics:
            return
        model = self.metrics.model_name
        for tr in transitions:
            self.metrics.slo_transitions.labels(
                model_name=model, objective=tr["objective"],
                window=tr["window"], state=tr["state"]).inc()
        # reuse the snapshot evaluate() just published instead of
        # re-scanning every window's bucket deque a second time
        state = ev.last_state
        for key, (burn_long, _short) in state.get("burn", {}).items():
            name, _, window = key.rpartition("/")
            self.metrics.slo_burn_rate.labels(
                model_name=model, objective=name,
                window=window).set(burn_long)
        self.metrics.slo_alerts_firing.set(
            len(state.get("firing", ())))

    # fast-burn auto-capture: a SHORT trace (the incident is happening
    # now; a long one only delays the next) and a long cooldown so a
    # flapping page cannot fill the flight dir with traces
    AUTO_CAPTURE_SECONDS = 3.0
    AUTO_CAPTURE_COOLDOWN_S = 600.0

    def _maybe_auto_capture(self, transitions: list) -> None:
        """Fast-burn SLO pages self-instrument: when a fast-window
        burn-rate alert FIRES, take a short jax.profiler trace on a
        daemon thread (the engine loop must keep serving — the trace is
        OF the degraded serving).  The trace lands under
        TPUSERVE_FLIGHT_DIR beside any post-mortem and is recorded on
        each engine's DeviceProfiler, so bundles written during the
        incident reference it.  No-ops inside the cooldown, or when a
        manual capture holds the process lock."""
        fired = [tr for tr in transitions
                 if tr.get("state") == "firing"
                 and tr.get("window") == "fast"]
        if not fired:
            return
        profs = [dp for dp in (getattr(e, "devprof", None)
                               for e in self._inner_engines())
                 if dp is not None]
        if not profs:
            return
        now = time.monotonic()  # tpulint: sync-ok(capture cooldown is real wall seconds; jax.profiler cannot run in replay time)
        if (self._auto_capture_last is not None
                and now - self._auto_capture_last
                < self.AUTO_CAPTURE_COOLDOWN_S):
            return
        self._auto_capture_last = now
        reason = f"slo-{fired[0]['objective']}"

        def _run():
            from tpuserve.server.tracing import (CaptureBusy,
                                                 capture_profile_locked)
            try:
                out = capture_profile_locked(self.AUTO_CAPTURE_SECONDS,
                                             reason=reason,
                                             profilers=profs)
                logger.warning("fast-burn auto-capture -> %s",
                               out["trace_dir"])
            except CaptureBusy:
                logger.info("fast-burn auto-capture skipped: a capture "
                            "is already in progress")
            except Exception:
                logger.exception("fast-burn auto-capture failed")

        threading.Thread(target=_run, daemon=True,
                         name="tpuserve-auto-capture").start()

    def _maybe_swap_pool(self) -> None:
        """Model-pool hot-swap at the idle boundary (loop thread only).
        The engine having no work IS the drain-to-window-boundary
        precondition; the pool then demotes the outgoing weights through
        the tiers, restores the incoming set from the warmest tier, and
        parked submits for the new model re-enter intake."""
        pool = self.pool
        if pool is None:
            return
        # expire parked submits whose admission deadline passed while
        # waiting for the swap — same typed 504 as queue-side expiry
        if self._parked:
            still = []
            # tpulint: sync-ok(admission deadlines are client wall-clock contracts)
            now = time.monotonic()
            for msg in self._parked:
                if msg.deadline is not None and now > msg.deadline:
                    msg.assigned_id = msg.request_id or "rejected"
                    msg.rid_event.set()
                    msg.out_queue.put(TimeoutError(
                        "admission deadline expired while parked for a "
                        f"model swap to {msg.model!r}"))
                    msg.out_queue.put(None)
                else:
                    still.append(msg)
            self._parked = still
        if pool.pending is None:
            if not self._parked:
                return
            # multiple target models can park at once; the single-slot
            # pending may have been consumed by an earlier swap — re-aim
            # at the oldest still-parked model
            pool.request_swap(self._parked[0].model)
        if self.engine.has_work():
            return
        outcome = pool.maybe_swap(self.engine)
        if outcome is None:
            return
        logger.info("model swap -> %s (source tier: %s)",
                    pool.current, outcome)
        still = []
        for msg in self._parked:
            if msg.model == pool.current:
                self._intake.put(msg)
            else:
                still.append(msg)
        self._parked = still
        self._wake.set()

    def _update_gauges(self) -> None:
        self._evaluate_slo()
        if not self.metrics:
            return
        eng = self.engine
        scheds = []
        if hasattr(eng, "scheduler"):
            scheds = [eng.scheduler]
        elif hasattr(eng, "prefill"):
            scheds = [eng.prefill.scheduler, eng.decode.scheduler]
        running = sum(s.num_running for s in scheds)
        waiting = sum(s.num_waiting for s in scheds)
        self.metrics.running.set(running)
        self.metrics.waiting.set(waiting)
        self.metrics.active_requests.set(running + waiting)
        bms = []
        if hasattr(eng, "block_manager"):
            bms = [eng.block_manager]
        elif hasattr(eng, "decode"):
            bms = [eng.prefill.block_manager, eng.decode.block_manager]
        if bms:
            total = sum(bm.num_blocks for bm in bms)
            free = sum(bm.num_free_blocks for bm in bms)
            self.metrics.kv_usage.set((total - free) / max(total, 1))
            self.metrics.kv_pool_tokens.set(
                sum(bm.num_blocks * bm.block_size for bm in bms))
            # direct attribute access (not getattr-by-string) so the
            # metrics-consistency lint can see these families are fed
            _advance_counter(self.metrics.prefix_hits,
                             sum(getattr(bm, "prefix_hits", 0)
                                 for bm in bms))
            _advance_counter(self.metrics.prefix_queries,
                             sum(getattr(bm, "prefix_queries", 0)
                                 for bm in bms))
        # engine-level stats live on the inner engines for the disagg
        # wrappers (DisaggStats has neither counter) — same special-casing
        # as the scheduler/block-manager reads above
        inners = [e for e in (getattr(eng, "prefill", None),
                              getattr(eng, "decode", None)) if e is not None]
        stats_objs = [i.stats for i in (inners or [eng])
                      if hasattr(i, "stats")]
        if stats_objs:
            _advance_counter(
                self.metrics.preemptions,
                sum(getattr(s, "preemptions", 0) for s in stats_objs))
            _advance_counter(
                self.metrics.window_overrun,
                sum(getattr(s, "window_overrun_tokens", 0)
                    for s in stats_objs))
            for attr, metric in (("spec_proposed", self.metrics.spec_proposed),
                                 ("spec_accepted", self.metrics.spec_accepted),
                                 ("spec_pauses", self.metrics.spec_pauses),
                                 ("released_blocks",
                                  self.metrics.released_blocks),
                                 ("latency_windows",
                                  self.metrics.latency_windows),
                                 ("guided_fallbacks",
                                  self.metrics.guided_fallbacks),
                                 ("guided_fsm_requests",
                                  self.metrics.guided_fsm_requests),
                                 ("guided_fsm_windows",
                                  self.metrics.guided_fsm_windows),
                                 ("padded_tokens_total",
                                  self.metrics.padded_tokens_total),
                                 ("actual_tokens_total",
                                  self.metrics.actual_tokens_total),
                                 ("prefill_tokens_total",
                                  self.metrics.prefill_tokens_total),
                                 ("prefill_padded_tokens_total",
                                  self.metrics.prefill_padded_tokens_total),
                                 ("prefill_packed_steps",
                                  self.metrics.prefill_packed_steps),
                                 ("prefill_kv_tokens_paged_total",
                                  self.metrics.prefill_kv_tokens_paged),
                                 ("kv_latent_tokens_attended_total",
                                  self.metrics.kv_latent_tokens_attended),
                                 ("prefill_first_token_deferred",
                                  self.metrics.first_tokens_deferred),
                                 ("prefill_first_token_flushed_early",
                                  self.metrics.first_tokens_flushed_early),
                                 ("num_mixed_steps",
                                  self.metrics.mixed_steps),
                                 ("decode_tokens_ridden",
                                  self.metrics.decode_tokens_ridden),
                                 ("kv_demoted_blocks",
                                  self.metrics.kv_demoted),
                                 ("kv_demote_declined_blocks",
                                  self.metrics.kv_demote_declined),
                                 ("kv_demote_waited_blocks",
                                  self.metrics.kv_demote_waited),
                                 ("kv_spilled_blocks",
                                  self.metrics.kv_spilled),
                                 ("kv_tier_dropped_blocks",
                                  self.metrics.kv_tier_dropped),
                                 ("kv_restored_blocks",
                                  self.metrics.kv_restored),
                                 ("requests_shed",
                                  self.metrics.requests_shed),
                                 ("slo_preemptions",
                                  self.metrics.requests_preempted),
                                 ("requests_salvaged",
                                  self.metrics.requests_salvaged),
                                 ("requests_poisoned",
                                  self.metrics.requests_poisoned),
                                 ("watchdog_trips",
                                  self.metrics.watchdog_trips),
                                 ("engine_restarts",
                                  self.metrics.engine_restarts),
                                 ("flight_postmortems",
                                  self.metrics.flight_postmortems),
                                 ("ssm_state_resets",
                                  self.metrics.ssm_state_resets),
                                 ("ssm_rebuilt_tokens",
                                  self.metrics.ssm_rebuilt_tokens),
                                 ("moe_routed_rows",
                                  self.metrics.moe_routed_rows),
                                 ("moe_row_moves_plain",
                                  self.metrics.moe_row_moves_plain),
                                 ("moe_held_rows",
                                  self.metrics.moe_held_rows),
                                 ("moe_held_hits",
                                  self.metrics.moe_held_hits),
                                 ("moe_buffer_rows",
                                  self.metrics.moe_buffer_rows),
                                 ("moe_group_rows",
                                  self.metrics.moe_group_rows),
                                 ("kda_state_row_layers",
                                  self.metrics.kda_state_row_layers)):
                _advance_counter(
                    metric, sum(getattr(s, attr, 0) for s in stats_objs))
            by_expert = [s.moe_expert_rows for s in stats_objs
                         if getattr(s, "moe_expert_rows", None) is not None]
            if by_expert and self.metrics.moe_routed_rows._value.get() \
                    != self._moe_rows_exported:
                # one labelled child an expert: only when rows were routed
                # since the last pass
                self._moe_rows_exported = \
                    self.metrics.moe_routed_rows._value.get()
                rows = sum(by_expert)
                for e, n in enumerate(rows):
                    _advance_counter(self.metrics.moe_expert_rows.labels(
                        model_name=self.metrics.model_name, expert=str(e)),
                        int(n))
                self.metrics.moe_expert_load.set(
                    float(rows.max() / rows.mean()))
            # last-step padding-waste gauges (the bucketing win's live
            # observability; sums across disagg halves like kv_usage)
            self.metrics.step_padded_tokens.set(
                sum(getattr(s, "step_padded_tokens", 0)
                    for s in stats_objs))
            self.metrics.step_actual_tokens.set(
                sum(getattr(s, "step_actual_tokens", 0)
                    for s in stats_objs))
            # tier-restore latency histogram: the engine accumulates
            # begin->commit wall times; drain them here (loop thread —
            # same thread that appended them)
            for s in stats_objs:
                lats = getattr(s, "restore_latencies", None)
                if lats:
                    for v in lats:
                        self.metrics.kv_restore_latency.observe(v)
                    lats.clear()
            # overload robustness (runtime/slo.py): current brownout
            # level (max across disagg halves) + the per-class
            # queue-delay observations the scheduler noted at admission
            # (drained loop-side, same thread that appended them)
            self.metrics.brownout_level.set(
                max((getattr(s, "brownout_level", 0) for s in stats_objs),
                    default=0))
            for e in (inners or [eng]):
                ctl = getattr(e, "_slo", None)
                if ctl is not None:
                    for cls, delay in ctl.drain_delay_obs():
                        self.metrics.queue_delay.labels(
                            slo_class=cls,
                            model_name=self.metrics.model_name,
                        ).observe(delay)
        # tiered-KV residency gauges: tier=hbm is the device cached pool,
        # host/spill come from the engines' tier stores (exactly-one-tier:
        # the three gauges partition every resolvable prefix hash)
        label = {"model_name": self.metrics.model_name}
        self.metrics.ssm_state_slots.set(sum(
            seats.in_use for seats in (getattr(bm, "seats", None)
                                       for bm in bms) if seats is not None))
        self.metrics.kv_tier_blocks.labels(tier="hbm", **label).set(
            sum(getattr(bm, "num_cached_blocks", 0) for bm in bms))
        self.metrics.moe_experts_held.set(
            getattr(getattr(eng, "model_cfg", None), "moe_experts_held", 0))
        if getattr(eng, "model_cfg", None) is not None:
            self.metrics.set_layer_kinds(eng.model_cfg)
        dead = [e.window_dead_tokens() for e in (inners or [eng])
                if hasattr(e, "window_dead_tokens")]
        if dead:
            self.metrics.kv_window_dead_tokens.set(sum(dead))
        stores = [t for t in (getattr(e, "_kv_tiers", None)
                              for e in (inners or [eng])) if t is not None]
        self.metrics.kv_tier_blocks.labels(tier="host", **label).set(
            sum(t.host_count for t in stores))
        self.metrics.kv_tier_blocks.labels(tier="spill", **label).set(
            sum(t.spill_count for t in stores))
        # the trunks' layer bodies traced and called (a process's own
        # counts, models/transformer.py; they move while programs compile)
        calls = sum(transformer.LAYER_CALLS.values())
        if calls != self._layer_calls_exported:
            self._layer_calls_exported = calls
            for body, n in transformer.LAYER_CALLS.items():
                _advance_counter(self.metrics.trunk_layer_traces.labels(
                    body=body, **label), transformer.LAYER_TRACES[body])
                _advance_counter(self.metrics.trunk_layer_calls.labels(
                    body=body, **label), n)
        # the process's compile ledger and start-up spans: they move only
        # while something compiles or warms, one comparison a cycle else
        mark = LEDGER.events + STARTUP.counts["startup.warmup"]
        if mark != self._startup_exported:
            self._startup_exported = mark
            led = LEDGER.totals()
            for ctr, field in (
                    (self.metrics.jit_trace_seconds, "trace_s"),
                    (self.metrics.jit_lower_seconds, "lower_s"),
                    (self.metrics.backend_compile_seconds, "backend_s"),
                    (self.metrics.compile_cache_read_seconds,
                     "cache_read_s"),
                    (self.metrics.compile_requests, "requests"),
                    (self.metrics.compile_cache_hits, "hits"),
                    (self.metrics.compile_cache_misses, "misses")):
                _advance_counter(ctr, led[field])
            self.metrics.startup_build_seconds.set(
                STARTUP.seconds["startup.build"])
            self.metrics.startup_warmup_seconds.set(
                STARTUP.seconds["startup.warmup"])
        # device telemetry (runtime/devprof.py): HBM watermark gauges,
        # per-sync-kind device seconds, ladder compile totals, capture
        # count.  Engines keep cumulative totals; counters advance by
        # delta (_advance_counter), gauges set wholesale.
        profs = [dp for dp in (getattr(e, "devprof", None)
                               for e in (inners or [eng]))
                 if dp is not None]
        if profs:
            hbm = [dp.hbm_snapshot() for dp in profs]
            for kind, field in (("weights", "weights_bytes"),
                                ("kv", "kv_reserved_bytes"),
                                ("state", "state_bytes"),
                                ("other", "other_bytes")):
                self.metrics.hbm_bytes.labels(kind=kind, **label).set(
                    sum(h.get(field, 0) for h in hbm))
            self.metrics.hbm_headroom.set(
                min((h.get("headroom_bytes", 0) for h in hbm if h),
                    default=0))
            sync_totals: dict = {}
            for dp in profs:
                for k, v in dp.sync_s.items():
                    sync_totals[k] = sync_totals.get(k, 0.0) + v
            for k, v in sync_totals.items():
                _advance_counter(
                    self.metrics.device_seconds.labels(kind=k, **label), v)
            _advance_counter(self.metrics.exec_compiles,
                             sum(dp.compiles for dp in profs))
            _advance_counter(self.metrics.exec_compile_seconds,
                             sum(dp.compile_s for dp in profs))
            self.metrics.execs_retained.set(
                sum(len(dp.ladder) for dp in profs))
            _advance_counter(self.metrics.profile_captures,
                             sum(dp.captures_total for dp in profs))
        # Model pool (tpuserve/modelpool): swap totals/latency come off
        # the engine stats (carried across swap_model rebuilds, so the
        # counters stay monotonic); tier residency off the pool's weight
        # store.  No pool -> the families stay at zero.
        pool = self.pool
        if pool is not None:
            swaps_by: dict = {}
            for s in stats_objs:
                for outcome, n in getattr(s, "model_swaps_by_outcome",
                                          {}).items():
                    swaps_by[outcome] = swaps_by.get(outcome, 0) + n
            for outcome, n in swaps_by.items():
                _advance_counter(
                    self.metrics.model_swaps.labels(outcome=outcome,
                                                    **label), n)
            for s in stats_objs:
                lats = getattr(s, "swap_latencies", None)
                if lats:
                    for _tier, dt in lats:
                        self.metrics.model_swap_seconds.observe(dt)
                    lats.clear()
            # hbm = the serving params + co-resident sets; the serving
            # share is cached per current model (tree walks every 50ms
            # idle tick would be wasteful on big param trees)
            cached = getattr(self, "_pool_hbm_cache", None)
            if cached is None or cached[0] != pool.current:
                from tpuserve.models.weights import param_nbytes
                serving = sum(
                    param_nbytes(e.params)
                    for e in (inners or [eng])
                    if getattr(e, "params", None) is not None)
                cached = (pool.current, serving)
                self._pool_hbm_cache = cached
            tiers = pool.tiers.bytes_by_tier()
            self.metrics.weight_tier_bytes.labels(tier="hbm", **label).set(
                cached[1] + pool.resident_nbytes())
            self.metrics.weight_tier_bytes.labels(tier="host", **label).set(
                tiers.get("host", 0))
            self.metrics.weight_tier_bytes.labels(tier="spill", **label).set(
                tiers.get("spill", 0))
            self.metrics.models_resident.set(sum(
                1 for entry in pool.catalog_status()
                if entry["tier"] in ("serving", "resident")))

    def _loop(self) -> None:
        logger.info("engine loop started")
        while not self._stop.is_set():
            with PROF.phase("runner.intake"):
                self._drain_intake()
            if not self.engine.has_work():
                self._maybe_swap_pool()
                self._update_gauges()
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            self._step_seq += 1
            seq = self._step_seq
            step_start = time.monotonic()  # tpulint: sync-ok(step wall time feeds the watchdog stamp and TPU duty cycle)
            self._step_started = (seq, step_start)
            try:
                outputs = self.engine.step()
                if self.on_step_time is not None:
                    # tpulint: sync-ok(step wall time feeds the watchdog stamp and TPU duty cycle)
                    self.on_step_time(time.monotonic() - step_start)
            except Exception as e:
                self._step_started = None
                logger.exception("engine step failed")
                if self._consume_hard_trip(seq):
                    continue
                # Crash-only salvage: requeue in-flight requests through
                # the preemption re-prefill path and replay (bisecting on
                # repeat faults) instead of mass-failing every stream.
                self._handle_step_fault(e)
                time.sleep(0.05)
                continue
            self._step_started = None
            self._steps_done += 1
            if self._consume_hard_trip(seq):
                continue
            self._note_salvage_progress()
            with PROF.phase("runner.route"):
                self._drain_engine_errors()
                self._route_outputs(outputs)
            with PROF.phase("runner.gauges"):
                self._update_gauges()
        # demotions whose copy is still in flight are filed (or counted
        # as dropped) before the loop's thread goes: none is lost silently
        for e in self._inner_engines():
            store = getattr(e, "_kv_tiers", None)
            if store is not None:
                store.flush()
        logger.info("engine loop stopped")
