"""Share of device busy time that names no model part: self time of the
``XLA Ops`` events whose ``op_name`` holds no part of the program's scope
table (``tpuserve/ops/scopes.py``), and of the compiler's own operations
that no scoped operation of their program follows, over the union of all
of them (per chip; ``_scope_trace``).  The error bar of every other
reader by scope: time in here could belong to any of them."""

from benchmark.layer_metrics import _scope_trace

LAYER = "model trunk"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    m = _scope_trace.measure(run)
    if m is None or m["busy_s"] <= 0:
        return None
    return 100.0 * _scope_trace.seconds(m, parts=("",)) / m["busy_s"]
