"""Seconds of ``setup_s`` under the program's ``startup.warmup`` span
(``Engine.warmup``, both rounds: every executable of the cell's ladder
readied and run once, closed by the wait for the device): the gauge
``tpuserve_startup_warmup_seconds`` on the page scraped as the window opens
(``_setup_page``).  The harness's own two ladders and the probes run after
it and are not in it.  None for a program without the series."""

from benchmark.layer_metrics import _setup_page

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"


def compute(run):
    return _setup_page.read(run, "tpuserve_startup_warmup_seconds")
