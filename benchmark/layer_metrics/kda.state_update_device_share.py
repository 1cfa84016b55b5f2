"""Share of device busy time spent in the Kimi-delta state update: self
time of ``_kda_state_update`` under ``decode/`` over the union of all
device operations in the traced span (per chip).
``kernel.attn_device_share`` counts every custom call, this one included."""

from benchmark.layer_metrics import _kda_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _kda_trace.measure(run)
    trace = run.get("trace")
    if m is None or not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * m["kernel_ns"] * 1e-9 / trace["busy_s"]
