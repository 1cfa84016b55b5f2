"""Where the persistent XLA compilation cache lives.

One rule for every process that builds an engine (the server,
benchmark/run.py, chip_smoke.py, the tools): when ``JAX_COMPILATION_CACHE_DIR`` is set —
the manifests mount it on the model PVC — JAX reads it itself and this
module sets nothing; otherwise the cache goes to ONE fixed directory
inside the checkout.  The directory is part of what a cache entry is
found by, so a path that moves (a temp dir, a pid, a platform suffix)
never hits.

What an entry is found by also holds each operation's NAME (its
``jax.named_scope`` path, ``tpuserve/ops/scopes.py``) and not its source
line, in either case.  By default JAX leaves all metadata out of the key,
so a program compiled before a scope existed, or under its old name, is
found again and carries the old ``op_name``s into the trace, where the
scopes are what device time is read by; with the metadata in the key as
JAX writes it, the key would also hold file and line of every frame above
each operation, and an edit that moves a line would compile every program
of a cell again.  So the operations' locations carry no Python frames at
all: a renamed or added scope compiles again, a moved line does not
(tests/test_compile_cache.py).  The price: HLO dumps and profiles name an
operation by its scope path alone, with no ``source`` beside it.

``configure()`` also starts the process's COMPILE LEDGER (``LEDGER``): what
JAX itself reports of every program it readies, by stage (tracing,
lowering, the backend's part) and by what the persistent cache answered.
``/metrics``, ``/debug/engine`` ``startup`` and devprof's ladder rows read
it (runtime/devprof.py, server/runner.py).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
# compiles quicker than this are not worth a file each
MIN_COMPILE_SECS = 1.0


# JAX's own monitoring events (jax 0.9: jax/_src/dispatch.py,
# compiler.py, compilation_cache.py).  The three stages open with a scalar
# event and close with a duration under the same name.
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "backend"}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_COUNTS = {"/jax/compilation_cache/compile_requests_use_cache": "asked",
           "/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
FIELDS = ("trace_s", "traces", "lower_s", "lowers", "backend_s", "requests",
          "cache_read_s", "asked", "hits", "misses")


class CompileLedger:
    """What this PROCESS spent readying programs, from JAX's monitoring
    events: process-wide, as the events are (two engines in one process
    share it, and it counts every program, an engine's or not).

    - ``trace_s`` / ``traces``: tracing Python to a jaxpr.  A jit traced
      while another is being traced (a trunk's layer body) reports a
      duration of its own INSIDE the outer one: each stage is kept as its
      SELF time (its duration less what closed inside it on the same
      thread), so the three stages never count a second twice and sum to
      no more than the wall time; ``traces`` counts every one.
    - ``lower_s`` / ``lowers``: jaxpr to an MLIR module.
    - ``backend_s`` / ``requests``: what the backend was asked for: an XLA
      compile, or the persistent cache's read in its place
      (``cache_read_s`` is the part of ``backend_s`` that was a read).
    - ``asked`` / ``hits`` / ``misses``: requests that asked the
      persistent cache, those it answered, and those compiled and WRITTEN
      to it.  A compile the cache declines to keep (quicker than
      ``jax_persistent_cache_min_compile_time_secs``, smaller than the
      entry floor) is neither: ``asked - hits - misses``, compiled again
      at every start.

    ``totals()`` is what ``/metrics`` exports; ``within(dt)`` is devprof's
    lookup for an executable's FIRST dispatch (and nothing else calls it:
    ``lookups`` counts the calls); ``unbracketed()`` is what no such
    bracket claimed: the samplers, the token selects, eager ``jnp``
    programs, the weights' initialisers."""

    #: records kept for ``within``: one an OUTERMOST stage (a trunk's trace
    #: with the hundreds of small ``jnp`` jits traced inside it is one), so
    #: a first dispatch's bracket holds three or four
    KEPT = 512

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = dict.fromkeys(FIELDS, 0)
        self._claimed = dict.fromkeys(FIELDS, 0)
        # (perf_counter at an outermost stage's end, {field: amount})
        self._recent: deque = deque(maxlen=self.KEPT)
        # per thread: the open stages' child seconds, and what the
        # outermost open one has gathered for its record
        self._open = threading.local()
        self.events = 0                  # every event taken: the export's mark
        self.lookups = 0
        self.listening = False

    def listen(self) -> None:
        """Register the listeners, once a process."""
        with self._lock:
            if self.listening:
                return
            self.listening = True
        from jax import monitoring
        monitoring.register_scalar_listener(self._on_open)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _thread(self):
        here = self._open
        if not hasattr(here, "stack"):
            here.stack, here.gathered = [], {}
        return here

    def _on_open(self, event: str, value, **_) -> None:
        if event in _STAGES:
            self._thread().stack.append(0.0)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            if event == _CACHE_READ:
                self._take((("cache_read_s", duration),))
            return
        stack = self._thread().stack
        inside = stack.pop() if stack else 0.0
        if stack:
            stack[-1] += duration
        count = "requests" if stage == "backend" else stage + "s"
        self._take(((stage + "_s", max(0.0, duration - inside)), (count, 1)))

    def _on_event(self, event: str, **_) -> None:
        field = _COUNTS.get(event)
        if field is not None:
            self._take(((field, 1),))

    def _take(self, amounts) -> None:
        here = self._thread()
        gathered = here.gathered
        for field, amount in amounts:
            gathered[field] = gathered.get(field, 0) + amount
        with self._lock:
            self.events += 1
            for field, amount in amounts:
                self._totals[field] += amount
            if not here.stack:           # the outermost stage just closed
                self._recent.append((time.perf_counter(), gathered))
                here.gathered = {}

    def totals(self) -> dict:
        with self._lock:
            return dict(self._totals)

    def within(self, dt: float) -> dict:
        """What the outermost stages that ENDED in the last ``dt`` seconds
        add up to (a record is stamped at its stage's end), taken off the
        kept records and claimed for the bracket that asks (so no second
        bracket and not ``unbracketed`` counts them again)."""
        since = time.perf_counter() - dt
        got = dict.fromkeys(FIELDS, 0)
        with self._lock:
            self.lookups += 1
            while self._recent and self._recent[-1][0] >= since:
                for field, amount in self._recent.pop()[1].items():
                    got[field] += amount
                    self._claimed[field] += amount
        return got

    def unbracketed(self) -> dict:
        with self._lock:
            return {f: self._totals[f] - self._claimed[f] for f in FIELDS}


LEDGER = CompileLedger()


def configure() -> str:
    """Place the persistent compile cache, start the compile ledger and
    return the cache's directory."""
    import jax
    LEDGER.listen()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return CHECKOUT_CACHE_DIR


def entries(cache_dir: str) -> int:
    """Number of cache entries on disk (0 for a directory not made yet)."""
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0
