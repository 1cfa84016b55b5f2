"""Nanoseconds of the expert layers' grouped products per routed row: self
time of ``_moe_grouped_matmul`` in the traced span (per chip) over the
rows it served there — tokens of a dispatch x experts a token x expert
layers x fused steps, the step records' ``moe_rows`` joined to the trace's
``engine.step`` spans by ``seq`` (``_moe_trace.py``).  One row passes
through three products (gate, up, down).  The raw quantity
``moe.gmm_roofline`` is computed from; it mixes the span's packed prefills
(many rows an expert, MXU-bound) with its decode windows (a few rows an
expert, bound by the experts' kernels read once a touched expert)."""

from benchmark.layer_metrics import _moe_trace

LAYER = "kernels"
UNIT = "ns/row"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _moe_trace.measure(run)
    return None if m is None else m["kernel_ns"] / m["rows"]
