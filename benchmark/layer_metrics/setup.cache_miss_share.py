"""Of the compile requests before the window that the persistent cache
answered or took, the share XLA COMPILED: misses / (hits + misses) of the
program's compile ledger (``tpuserve_compile_cache_misses_total``,
``tpuserve_compile_cache_hits_total`` on the page scraped as the window
opens, ``_setup_page``).  0 on a warm start, ~100 on a first run on an
empty cache: the covariate that says which ``setup_s`` a line is.  0.0
where no request missed (none asked included); None for a program without
the series."""

from benchmark.layer_metrics import _setup_page

LAYER = "start-up"
UNIT = "%"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"


def compute(run):
    hits = _setup_page.read(run, "tpuserve_compile_cache_hits_total")
    misses = _setup_page.read(run, "tpuserve_compile_cache_misses_total")
    if hits is None or misses is None:
        return None
    if misses <= 0:
        return 0.0
    return 100.0 * misses / (hits + misses)
