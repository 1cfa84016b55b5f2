"""Seconds the process spent LOWERING jaxprs to MLIR modules before the
window (JAX's ``jaxpr_to_mlir_module_duration`` events in the program's
compile ledger): ``tpuserve_jit_lower_seconds_total`` on the page scraped
as the window opens (``_setup_page``).  Paid at every start, like tracing.
None for a program without the series."""

from benchmark.layer_metrics import _setup_page

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(run):
    return _setup_page.read(run, "tpuserve_jit_lower_seconds_total")
