"""Nanoseconds of the decode-time state update per row and layer: self
time of ``_ssm_state_update`` in the traced span (per chip) over the
row-layers it served there (decode rows x fused steps x layers, the step
records joined to the trace's ``engine.step`` spans by ``seq``;
``_ssm_trace.py``).  The raw quantity ``ssm.state_update_roofline`` is
computed from; independent of the batch and of the context length, since
a row's state has one size however long its sequence is."""

from benchmark.layer_metrics import _ssm_trace

LAYER = "kernels"
UNIT = "ns/row"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _ssm_trace.measure(run)
    return None if m is None else m["kernel_ns"] / m["row_layers"]
