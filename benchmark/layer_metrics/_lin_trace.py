"""What the ``lin.state_update_*`` readers share (the leading underscore
keeps ``plan.discover_layer_metrics`` from taking this for a metric).

The decode-time state update of a model with gated delta-rule
linear-attention layers is one Pallas custom call, ``_gdn_state_update``
(``tpuserve/ops/pallas_gdn_update.py``): one call a linear layer a decode
step, one grid row a batch row.  ``measure(run)`` gives its self time in
the traced span (per chip) and the row-layers it served THERE.

**Time and work from the same calls** (``_moe_held_trace.py``'s way,
PERF.md §7 row 18): the trace gives the kernel's calls and self time by
phase (the scope on each operation, ``_scope_trace.read_ops``); the step
records joined to the trace's ``engine.step`` spans by ``seq`` give the
rows a CALL serves (a decode dispatch's real rows a fused step: its
``actual_tokens`` over its steps; padding rows sit on the trash seat and
are no work).  Row-layers = calls x rows a call.  The join misses a
dispatch whose span opened before the capture and counts one whose device
work runs after it, so the joined records' OWN sum of rows x layers,
against the whole trace's kernel time, reads a third off either way; it is
printed beside the calls for the reader of the log (the linear layers
counted from ``layer_types``, never from ``num_hidden_layers``: 12 of 16
call this kernel).  A capture may be too short for the join to hold a
decode dispatch (the driver's first check of PR 43 captured 0.58 s of the
2 s it asked for), or to hold an ``engine.step`` span at all (0.36 s
inside one fused window): the rows a call then come from the decode records
stamped inside the traced span, and failing those from the whole window's
(a closed loop's rows hardly move: 61-63 of 64 seats), so that a span
that holds the kernel's calls always reads.  None where the run has no
trace, the trace has no such kernel (a model without linear layers, a
program from before them) or the run recorded no decode step at all.
"""

from benchmark.harness import host_spans
from benchmark.harness import trace_reduce as tr
from benchmark.layer_metrics import _scope_trace as st

KERNEL = "_gdn_state_update"
DECODE_KINDS = ("window", "decode")
_KEY = "_lin_trace"


def kernel_by_phase(path: str) -> dict:
    """``{phase: [self ns, calls]}`` of the kernel per chip."""
    chips, out = 0, {}
    for ops in st.read_ops(path):
        chips += 1
        for i, ns in tr.self_times([(s, e, i) for i, (s, e, *_)
                                    in enumerate(ops)]):
            if tr.op_kind(ops[i][2]) == KERNEL:
                cell = out.setdefault(st.scope_of(ops[i][3])[0], [0, 0])
                cell[0] += ns
                cell[1] += 1
    return {p: [ns / chips, calls / chips] for p, (ns, calls) in out.items()}


def rows_a_call(steps: list):
    """Real rows of one decode-time call, from step records: the rows of
    a fused step, weighted by the steps; None where none is a decode."""
    decodes = [s for s in steps if s.get("kind") in DECODE_KINDS
               and s.get("rows")]
    fused = st.fused_steps(decodes)
    if fused <= 0:
        return None
    return sum(s["actual_tokens"] for s in decodes) / fused


def rows_of(run, joined: list):
    """Rows a call from the nearest records that hold a decode step: the
    ``seq`` join's, those stamped inside the traced span, the window's."""
    span = run.get("trace_span")
    steps = run.get("steps") or []
    for records in (joined,
                    [s for s in steps if span and span[0] <= s["t"] < span[1]],
                    steps):
        rows = rows_a_call(records)
        if rows is not None:
            return rows
    return None


def linear_layers(config: dict) -> int:
    return sum(t == "linear_attention"
               for t in config.get("layer_types") or ())


def measure(run):
    """``{"kernel_ns", "row_layers", "calls"}`` of a traced run, or None."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    if not run.get("trace_dir"):
        return None
    joined = (host_spans.analyse(run) or {}).get("steps_joined") or []
    rows = rows_of(run, joined)
    from benchmark.harness.session import find_xplane
    path = find_xplane(run["trace_dir"])
    if rows is None or not path:
        return None
    by_phase = kernel_by_phase(path)
    ns, calls = by_phase.get("decode", (0, 0))
    if ns <= 0 or calls <= 0:
        return None
    layers = linear_layers(run["config"])
    joined = st.fused_steps(joined)
    print(f"[bench] {KERNEL}: {calls:.0f} calls under decode/ "
          f"({calls / max(layers, 1):.2f} fused steps over {layers} linear "
          f"layers of layer_types; the seq join holds {joined:.2f}), "
          f"{rows:.2f} real rows a call; other phases "
          f"{ {p: c for p, (_, c) in by_phase.items() if p != 'decode'} }",
          flush=True)
    run[_KEY] = {"kernel_ns": ns, "row_layers": calls * rows, "calls": calls}
    return run[_KEY]
