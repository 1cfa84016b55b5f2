"""Share of device busy time spent in the expert layers' grouped products:
self time of ``_moe_grouped_matmul`` over the union of all device
operations in the traced span (per chip).  ``kernel.attn_device_share``
counts every custom call, this one included: attention alone is that less
this.  The sort, the gather of the rows and the add-back around the kernel
are XLA operations and are not in it (``breakdown.device_ops`` has them)."""

from benchmark.layer_metrics import _moe_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _moe_trace.measure(run)
    trace = run.get("trace")
    if m is None or not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * m["kernel_ns"] * 1e-9 / trace["busy_s"]
