"""Seconds of ``setup_s`` under the program's ``startup.build`` span
(``build_server``: flags to the server object: the backend's start where
the harness had not touched it, the weights' initialisers enqueued, the
cache pools, the scheduler; no warm-up): the gauge
``tpuserve_startup_build_seconds`` on the page scraped as the window opens
(``_setup_page``).  None for a program without the series."""

from benchmark.layer_metrics import _setup_page

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"


def compute(run):
    return _setup_page.read(run, "tpuserve_startup_build_seconds")
