"""What the three ``moe.*`` readers share (the leading underscore keeps
``plan.discover_layer_metrics`` from taking this for a metric).

Each projection of a sparse expert layer is one Pallas custom call,
``_moe_grouped_matmul`` (``tpuserve/ops/pallas_moe_gmm.py``): three calls a
layer (gate, up, down) in every dispatch, prefill and decode alike, over
the rows the dispatch routed.  ``measure(run)`` gives its self time in the
traced span (per chip, the ``XLA Ops`` line's events less their children)
and what it served there: the routed rows and the expert-layers that got
at least one row, which the step records carry as ``moe_rows`` and
``moe_expert_hits`` (counted on the device, read with the dispatch's
tokens) for every dispatch whose ``engine.step`` span lies in the trace
(step records joined by ``seq``).  None where the run has no trace, the
trace has no such kernel (a program from before it existed, a model
without experts) or no step record with routing counts joined.
"""

from benchmark.harness import host_spans
from benchmark.harness import trace_reduce as tr

KERNEL = "_moe_grouped_matmul"
_KEY = "_moe_trace"


def kernel_self_ns(path: str) -> float:
    """Self nanoseconds of the kernel per chip in the trace at ``path``."""
    total, chips = 0, 0
    for plane in tr.load(path).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
               for line in plane.lines if line.name == tr.OPS_LINE
               for e in line.events]
        if not ops:
            continue
        chips += 1
        total += sum(d for name, d in tr.self_times(ops)
                     if tr.op_kind(name) == KERNEL)
    return total / max(chips, 1)


def measure(run):
    """``{"kernel_ns", "rows", "hits"}`` of a traced run, or None."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    spans = host_spans.analyse(run)
    if not spans:
        return None
    from benchmark.harness.session import find_xplane
    ns = kernel_self_ns(find_xplane(run["trace_dir"]))
    steps = [s for s in spans["steps_joined"] if s.get("moe_rows")]
    rows = sum(s["moe_rows"] for s in steps)
    hits = sum(s["moe_expert_hits"] for s in steps)
    if ns <= 0 or rows <= 0:
        return None
    run[_KEY] = {"kernel_ns": ns, "rows": rows, "hits": hits}
    return run[_KEY]
