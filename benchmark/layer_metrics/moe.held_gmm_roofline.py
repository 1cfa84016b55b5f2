"""The grouped products' share of their roofline under a SHARE of the
experts: the least time the chip could take for what
``_moe_grouped_matmul`` had to serve in the traced span, over its self
time there (``_moe_held_trace.py``).

What it had to serve is the HELD rows, whatever implements the layer: a
row routed to a held expert is multiplied by that expert's gate, up and
down kernels, ``2 x 3 x hidden x width`` operations, and moves its input
and output (``hidden`` each) and the three ``width``-wide rows between the
products, at 2 bytes; a held expert's three kernels (``3 x hidden x
width`` at the weights' 2 bytes) must be read once for every held
expert-layer that got at least one row (``moe_held_hits``, NOT the experts
held: an untouched expert is never read).  Rows routed to experts other
chips hold are no work of this chip's: a layer that gathered and
multiplied them anyway would read a LOWER share, which is the point.  The
larger of the two least times is taken (``roofline.share``): a span of
decode windows reads as memory-bound.  Never clipped."""

from benchmark.harness import roofline
from benchmark.layer_metrics import _moe_held_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "device_trace"
ITEMSIZE = 2                        # bf16 weights and activations


def work(cfg: dict, rows: float, hits: float) -> tuple:
    """``(operations, bytes)`` of ``rows`` held rows over ``hits`` touched
    held expert-layers."""
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2.0 * 3 * hidden * width * rows
    nbytes = (hits * 3 * hidden * width
              + rows * (2 * hidden + 3 * width)) * ITEMSIZE
    return flops, float(nbytes)


def compute(run):
    m = _moe_held_trace.measure(run)
    if m is None or not run.get("peaks"):
        return None
    flops, nbytes = work(run["config"], m["rows"], m["hits"])
    part = roofline.share(m["kernel_ns"] * 1e-9, flops, nbytes, run["peaks"])
    return None if part is None else 100.0 * part
