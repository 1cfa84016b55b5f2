"""Nanoseconds of the expert layers' grouped products per HELD row, under
a share of the experts: self time of ``_moe_grouped_matmul`` in the traced
span (per chip) over the rows that landed on the experts this chip holds
there (the step records' ``moe_held_rows`` joined to the trace's
``engine.step`` spans by ``seq``, ``_moe_held_trace.py``).  One row passes
through three products (gate, up, down).  The raw quantity
``moe.held_gmm_roofline`` is computed from."""

from benchmark.layer_metrics import _moe_held_trace

LAYER = "kernels"
UNIT = "ns/row"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _moe_held_trace.measure(run)
    return None if m is None else m["kernel_ns"] / m["rows"]
