"""What the seven ``setup.*`` readers share: the ``/metrics`` page the
harness scrapes AS THE WINDOW OPENS (``run["metrics_start"]``), so a series
there holds everything the process did before the window, which is what
``setup_s`` spans: the program's start-up spans (``tpuserve_startup_*``
gauges, ``runtime/hostprof.py`` ``STARTUP``) and its compile ledger
(``tpuserve_jit_*`` / ``tpuserve_backend_compile_*`` /
``tpuserve_compile_*`` counters, ``utils/compile_cache.py``: JAX's own
events, one sample a series).  ``read`` gives the number, 0.0 where the
series is on the page and reads zero, None only where the program has no
such series (a program from before PR 57)."""


def read(run, series):
    page = run.get("metrics_start") or {}
    if series not in page:
        return None
    return float(page[series])
