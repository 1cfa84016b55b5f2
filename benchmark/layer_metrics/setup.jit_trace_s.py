"""Seconds the process spent TRACING Python to jaxprs before the window
(JAX's ``jaxpr_trace_duration`` events as the program's compile ledger
keeps them: self time, a layer body traced inside a trunk counted once):
``tpuserve_jit_trace_seconds_total`` on the page scraped as the window
opens (``_setup_page``).  Paid at every start, whatever the compile cache
holds.  None for a program without the series."""

from benchmark.layer_metrics import _setup_page

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(run):
    return _setup_page.read(run, "tpuserve_jit_trace_seconds_total")
