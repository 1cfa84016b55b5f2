"""Request tracing + on-demand device profiling.

The reference stands up an OTLP trace receiver (grpc 4317 / http 4318) with
a traces pipeline but nothing ever emits a span (reference:
otel-observability-setup.yaml:504-509,633-636; SURVEY.md §5 "plumbing
exists, no real trace backend, and nothing emits traces").  Here the engine
server emits one span per API request so that pipeline actually carries
data.  The OpenTelemetry SDK is optional: when it isn't importable or no
OTLP endpoint is configured, everything degrades to a no-op with the same
API (the container image does not bake opentelemetry).

Profiling: ``capture_profile`` wraps ``jax.profiler`` trace capture — the
TPU-native replacement for the profilers the reference never had
(SURVEY.md §5 "No profiler anywhere").
"""

from __future__ import annotations

import contextlib
import logging
import os
import sys
import tempfile
import threading
import time

logger = logging.getLogger("tpuserve.tracing")


class _NoopSpan:
    def set_attribute(self, key, value):  # pragma: no cover - trivial
        pass


class RequestTracer:
    """One span per served request; OTLP-backed when available, no-op
    otherwise.  ``request_span`` never raises."""

    def __init__(self):
        self._tracer = None
        endpoint = os.environ.get("OTEL_EXPORTER_OTLP_ENDPOINT")
        if not endpoint:
            return
        try:
            from opentelemetry import trace
            from opentelemetry.exporter.otlp.proto.http.trace_exporter import (
                OTLPSpanExporter)
            from opentelemetry.sdk.resources import Resource
            from opentelemetry.sdk.trace import TracerProvider
            from opentelemetry.sdk.trace.export import BatchSpanProcessor
            provider = TracerProvider(resource=Resource.create(
                {"service.name": os.environ.get("OTEL_SERVICE_NAME",
                                                "tpuserve")}))
            provider.add_span_processor(
                BatchSpanProcessor(OTLPSpanExporter()))
            trace.set_tracer_provider(provider)
            self._tracer = trace.get_tracer("tpuserve")
            logger.info("OTLP tracing enabled -> %s", endpoint)
        except Exception as e:   # SDK absent or misconfigured: no-op
            logger.info("OTLP tracing unavailable (%s); spans are no-ops", e)

    @property
    def active(self) -> bool:
        return self._tracer is not None

    @contextlib.contextmanager
    def request_span(self, name: str, context=None, **attrs):
        """``context``: an extracted W3C parent context (see
        :func:`extract_context`) — the gateway's span, or the caller's
        own trace — so gateway -> server -> engine is ONE tree in the
        reference-parity OTel pipeline.  None = new root span."""
        if self._tracer is None:
            yield _NoopSpan()
            return
        try:
            # context passed only when present: tracers predating the
            # kwarg (tests' fakes included) keep working
            kw = {"context": context} if context is not None else {}
            cm = self._tracer.start_as_current_span(name, **kw)
            span = cm.__enter__()
        except Exception:
            yield _NoopSpan()
            return
        try:
            for k, v in attrs.items():
                if v is not None:
                    span.set_attribute(k, v)
            yield span
        except BaseException:
            # propagate the real exc_info so the span records error status —
            # a bare __exit__(None, None, None) would export failed requests
            # as successful spans
            if not cm.__exit__(*sys.exc_info()):
                raise
        else:
            cm.__exit__(None, None, None)


_tracer: RequestTracer | None = None


def get_tracer() -> RequestTracer:
    global _tracer
    if _tracer is None:
        _tracer = RequestTracer()
    return _tracer


# ---- W3C trace-context propagation (gateway -> server -> engine) ---------

def extract_context(headers):
    """Parent context from incoming ``traceparent``/``tracestate``
    headers (W3C), or None.  Degrades to None exactly like the tracer:
    no opentelemetry API installed, no header, or a malformed value all
    mean "start a new root"."""
    try:
        tp = headers.get("traceparent")
        if not tp:
            return None
        from opentelemetry.propagate import extract
        carrier = {"traceparent": tp}
        ts = headers.get("tracestate")
        if ts:
            carrier["tracestate"] = ts
        return extract(carrier)
    except Exception:
        return None


def inject_headers(headers: dict) -> dict:
    """Inject the CURRENT span's context as ``traceparent`` into
    ``headers`` (mutated and returned).  No-op without the SDK or
    outside a recording span — callers should pre-populate any incoming
    traceparent first so pass-through still works SDK-less."""
    try:
        from opentelemetry.propagate import inject
        inject(headers)
    except Exception:
        pass
    return headers


def emit_timeline_spans(tracer: RequestTracer, timeline, wall_of) -> None:
    """Export a flight-recorder request timeline as OTLP child spans of
    the CURRENT span (call inside ``request_span``).  Each lifecycle
    event becomes one ``engine.<event>`` span from its timestamp to the
    next event's (FINISHED closes on itself); ``wall_of`` maps the
    recorder's monotonic stamps onto the wall clock
    (FlightRecorder.wall_of).  Never raises; no-op when inactive."""
    if not tracer.active or not timeline:
        return
    try:
        tr = tracer._tracer
        for i, ev in enumerate(timeline):
            start_ns = int(wall_of(ev["t"]) * 1e9)
            end_t = timeline[i + 1]["t"] if i + 1 < len(timeline) \
                else ev["t"]
            span = tr.start_span("engine." + ev["event"].lower(),
                                 start_time=start_ns)
            try:
                for k, v in (ev.get("detail") or {}).items():
                    if isinstance(v, (bool, int, float, str)):
                        span.set_attribute(f"tpuserve.{k}", v)
            finally:
                span.end(end_time=max(start_ns,
                                      int(wall_of(end_t) * 1e9)))
    except Exception:
        logger.debug("timeline span export failed", exc_info=True)


def capture_profile(seconds: float, out_dir: str | None = None) -> dict:
    """Capture a jax.profiler device trace for ``seconds``.

    Returns {"trace_dir": path, "seconds": n}.  The directory holds the
    TensorBoard-loadable profile (plugins/profile/...): the device's
    planes and, on the host plane, the engine loop's spans
    (runtime/hostprof.py) on the same clock.  The Python tracer stays
    off: the spans say what the loop was doing, and tracing every Python
    call stalls the loop that is being traced (a fast-burn auto-capture
    cost a loaded server a second-long stall and ~10 % of a 45 s
    window's tokens, PERF.md PR 24).
    """
    import jax
    seconds = min(max(seconds, 0.1), 60.0)
    out_dir = out_dir or tempfile.mkdtemp(prefix="tpuserve-profile-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    return {"trace_dir": out_dir, "seconds": seconds}


class CaptureBusy(RuntimeError):
    """A jax.profiler capture is already running in this process.

    jax allows ONE active trace per process; a second start_trace raises
    deep inside the profiler plugin.  Callers (POST /debug/profile, the
    SLO fast-burn auto-capture) turn this into HTTP 409 / a skipped
    auto-capture instead of a 500."""


# one trace at a time per process: guards manual /debug/profile requests
# racing each other AND the SLO auto-capture thread racing either
_capture_lock = threading.Lock()


def profile_out_dir(reason: str) -> str | None:
    """Trace destination under ``TPUSERVE_FLIGHT_DIR`` (the model PVC in
    the manifests) so traces land BESIDE the post-mortem bundles that
    reference them — or None (capture_profile falls back to a tmpdir)
    when no flight dir is configured.  Same naming scheme as
    FlightRecorder.postmortem: reason + pid + uuid, collision-proof for
    disagg pods and concurrent threads."""
    import uuid
    d = os.environ.get("TPUSERVE_FLIGHT_DIR")
    if not d:
        return None
    path = os.path.join(d, f"profile-{reason}-{os.getpid()}"
                           f"-{uuid.uuid4().hex[:8]}")
    os.makedirs(path, exist_ok=True)
    return path


def capture_profile_locked(seconds: float, *, reason: str = "manual",
                           profilers=()) -> dict:
    """Serialized :func:`capture_profile`: raises :class:`CaptureBusy`
    instead of stacking a second trace, writes under the flight dir when
    configured, and records the capture on every engine
    ``DeviceProfiler`` handle passed in ``profilers`` (so bundles and
    the tpuserve_profile_captures counter see it)."""
    if not _capture_lock.acquire(blocking=False):
        raise CaptureBusy("a profiler capture is already in progress")
    try:
        out = capture_profile(seconds, out_dir=profile_out_dir(reason))
    finally:
        _capture_lock.release()
    out["reason"] = reason
    for dp in profilers:
        if dp is not None:
            dp.note_capture(out["trace_dir"], reason, out["seconds"])
    return out
