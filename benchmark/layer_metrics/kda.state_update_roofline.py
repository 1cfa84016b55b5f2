"""The Kimi-delta state update's share of its roofline: the least time the
chip could take for the row-layers ``_kda_state_update`` served in the
traced span, over its self time there (``_kda_trace.py``: time and work
from the same calls; ``work_per_row_layer`` there counts what a row-layer
is from the published sizes, 4.28 MB and 3.7 MFLOP: least time 5.2 us).
Never clipped."""

from benchmark.harness import roofline
from benchmark.layer_metrics import _kda_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _kda_trace.measure(run)
    if m is None or not run.get("peaks"):
        return None
    flops, nbytes = _kda_trace.work_per_row_layer(run["config"])
    part = roofline.share(m["kernel_ns"] * 1e-9, flops * m["row_layers"],
                          nbytes * m["row_layers"], run["peaks"])
    return None if part is None else 100.0 * part
