"""Counting compile requests, so that a window with a compile in it is
seen.  (Copied from chip_smoke.py's ``Meter``: JAX's own monitoring
events.)"""

from __future__ import annotations

import logging

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileMeter:
    """``requests`` counts every executable JAX asked its backend for,
    whether it was compiled (``misses``) or read back from the persistent
    cache (``hits``); ``seconds`` is the time that took."""

    def __init__(self):
        import jax
        self.requests = self.hits = self.misses = 0
        self.seconds = 0.0
        self.names: list = []
        self._handler = None
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.requests += 1
            self.seconds += duration

    def watch_names(self, on: bool) -> None:
        """While on, JAX logs each compile and ``names`` collects what was
        compiled: a window with a compile in it can then say which."""
        import jax
        jax.config.update("jax_log_compiles", bool(on))
        logger = logging.getLogger("jax")
        if on:
            self.names = []
            self._handler = _Collect(self.names)
            logger.addHandler(self._handler)
        elif self._handler is not None:
            logger.removeHandler(self._handler)
            self._handler = None

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "misses": self.misses, "seconds": self.seconds}


class _Collect(logging.Handler):
    def __init__(self, into: list):
        super().__init__(level=logging.DEBUG)
        self.into = into

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling"):
            self.into.append(msg[:160])
