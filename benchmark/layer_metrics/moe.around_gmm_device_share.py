"""Share of device busy time an expert layer spends OUTSIDE its grouped
products: self time in ``moe.route`` (router, top-k, the stable sort, the
counts), ``moe.gather`` (the rows into expert order and back) and
``moe.combine`` (the add-back's permutation, the weighted sum), in every
phase, over the union of all device operations in the traced span (per
chip; ``_scope_trace``).  ``moe.gmm_device_share`` is the kernel itself;
``moe.experts`` less that is the activation between its calls."""

from benchmark.layer_metrics import _scope_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("moe.route", "moe.gather", "moe.combine")


def compute(run):
    return _scope_trace.share_of_busy(run, parts=PARTS)
