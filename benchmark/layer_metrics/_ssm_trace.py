"""What the three ``ssm.*`` readers share (the leading underscore keeps
``plan.discover_layer_metrics`` from taking this for a metric).

The decode-time state update of a model with state-space layers is one
Pallas custom call, ``_ssm_state_update``
(``tpuserve/ops/pallas_ssm_update.py``): one call a layer a decode step,
one grid row a batch row.  ``measure(run)`` gives its self time in the
traced span (per chip, the ``XLA Ops`` line's events less their children,
as ``harness/host_spans.py`` takes the decode attention kernel's) and the
row-layers it served there: the rows of every decode dispatch whose
``engine.step`` span lies in the trace (step records joined by ``seq``),
times the dispatch's fused steps, times the layers.  None where the run
has no trace, the trace has no such kernel (a program from before it
existed, a model without state-space layers) or no decode step joined.
"""

from benchmark.harness import host_spans
from benchmark.harness import trace_reduce as tr

KERNEL = "_ssm_state_update"
_KEY = "_ssm_trace"


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


def row_layers(steps: list, layers: int) -> float:
    """Row-layers the kernel served in the joined decode dispatches: a
    window of S fused steps over n rows is ``actual_tokens`` = n S rows."""
    return layers * sum(s["actual_tokens"] for s in steps
                        if s.get("kind") in ("window", "decode")
                        and s.get("rows"))


def measure(run):
    """``{"kernel_ns", "row_layers"}`` of a traced run, or None."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    spans = host_spans.analyse(run)
    if not spans:
        return None
    from benchmark.harness.session import find_xplane
    ns = kernel_self_ns(find_xplane(run["trace_dir"]))
    rows = row_layers(spans["steps_joined"],
                      run["config"]["num_hidden_layers"])
    if ns <= 0 or rows <= 0:
        return None
    run[_KEY] = {"kernel_ns": ns, "row_layers": rows}
    return run[_KEY]
