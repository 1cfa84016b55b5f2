"""Seconds inside the BACKEND's part of readying programs before the
window (JAX's ``backend_compile_duration`` events in the program's compile
ledger): XLA compiles on a first run, the persistent cache's reads in
their place on a warm one (``setup.cache_miss_share`` says which):
``tpuserve_backend_compile_seconds_total`` on the page scraped as the
window opens (``_setup_page``).  None for a program without the series."""

from benchmark.layer_metrics import _setup_page

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(run):
    return _setup_page.read(run, "tpuserve_backend_compile_seconds_total")
