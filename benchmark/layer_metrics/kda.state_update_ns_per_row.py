"""Nanoseconds the Kimi-delta state update takes a row a layer: self time
of ``_kda_state_update`` under ``decode/`` in the traced span (per chip)
over the row-layers those calls served (``_kda_trace.py``).  A row-layer
moves 4.28 MB whatever the context's length: 5.2 us at the HBM rate
(``kda.state_update_roofline``)."""

from benchmark.layer_metrics import _kda_trace

LAYER = "kernels"
UNIT = "ns/row"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _kda_trace.measure(run)
    if m is None:
        return None
    return m["kernel_ns"] / m["row_layers"]
