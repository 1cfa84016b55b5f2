"""The grouped products' share of their roofline: the least time the chip
could take for what ``_moe_grouped_matmul`` served in the traced span,
over its self time there (both as ``moe.gmm_ns_per_row`` takes them).

One routed row is multiplied by its expert's gate, up and down kernels:
``2 x 3 x hidden x width`` operations, and moves its input and output
(``hidden`` each) and the three ``width``-wide rows between the products,
at 2 bytes.  An expert's three kernels (``3 x hidden x width`` at the
weights' 2 bytes) must be read once for every expert-layer that got at
least one row — counted from the step records' ``moe_expert_hits``, NOT
from the number of experts: few rows touch few experts, and a count from
all of them would put the share over 100 %.  The operations and the bytes
are summed over the span's dispatches and the larger of the two least
times is taken (``roofline.share``), so a span of decode windows reads as
memory-bound and one of large prefills as compute-bound.  Never clipped."""

from benchmark.harness import roofline
from benchmark.layer_metrics import _moe_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "device_trace"
ITEMSIZE = 2                        # bf16 weights and activations


def work(cfg: dict, rows: float, hits: float) -> tuple:
    """``(operations, bytes)`` of ``rows`` routed rows over ``hits``
    touched expert-layers."""
    hidden, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2.0 * 3 * hidden * width * rows
    nbytes = (hits * 3 * hidden * width
              + rows * (2 * hidden + 3 * width)) * ITEMSIZE
    return flops, float(nbytes)


def compute(run):
    m = _moe_trace.measure(run)
    if m is None or not run.get("peaks"):
        return None
    flops, nbytes = work(run["config"], m["rows"], m["hits"])
    part = roofline.share(m["kernel_ns"] * 1e-9, flops, nbytes, run["peaks"])
    return None if part is None else 100.0 * part
