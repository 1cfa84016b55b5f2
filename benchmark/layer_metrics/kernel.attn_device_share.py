"""Share of device busy time spent in custom calls, which is how the
Pallas attention kernels appear in the trace (self time of ``XLA Ops``
events classed ``kernel`` over the union of all of them)."""

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    trace = run.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    kernel = trace["classes"].get("kernel")
    if not kernel:
        return None
    return 100.0 * kernel / trace["busy_s"]
