"""Nanoseconds the gated delta-rule state update takes a row a layer: self
time of ``_gdn_state_update`` under ``decode/`` in the traced span (per
chip) over the row-layers those calls served (``_lin_trace.py``: the
trace's calls times the real rows a call of the joined decode records).
A row-layer moves 4.49 MB whatever the context's length: 5.5 us at the
HBM rate (``lin.state_update_roofline``)."""

from benchmark.layer_metrics import _lin_trace

LAYER = "kernels"
UNIT = "ns/row"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _lin_trace.measure(run)
    if m is None:
        return None
    return m["kernel_ns"] / m["row_layers"]
