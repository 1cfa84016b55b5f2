"""Share of device busy time spent in the grouped products of an expert
layer that holds a share of its experts: self time of
``_moe_grouped_matmul`` over the union of all device operations in the
traced span (per chip), reported where the step records say rows landed
on held experts (``_moe_held_trace.py``).  The sort of the picks, the
rows' gather and the add to their tokens around the kernel are XLA
operations and are not in it; the shared expert is
``moe.shared_device_share``."""

from benchmark.layer_metrics import _moe_held_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _moe_held_trace.measure(run)
    trace = run.get("trace")
    if m is None or not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * m["kernel_ns"] * 1e-9 / trace["busy_s"]
