"""Programs the process asked its backend for before the window, compiled
or read back from the persistent cache (the count of JAX's
``backend_compile_duration`` events in the program's compile ledger):
``tpuserve_compile_requests_total`` on the page scraped as the window opens
(``_setup_page``).  Every one is traced and lowered first.  None for a
program without the series."""

from benchmark.layer_metrics import _setup_page

LAYER = "start-up"
UNIT = "count"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(run):
    return _setup_page.read(run, "tpuserve_compile_requests_total")
