"""What the three ``moe.held_*`` readers share (the leading underscore
keeps ``plan.discover_layer_metrics`` from taking this for a metric).

An expert layer that holds a SHARE of its experts
(``ModelConfig.moe_experts_held``: one chip of a deployment whose chips
share each layer) multiplies only the rows routed to the experts it holds,
a piece of buffer at a time, three grouped products a piece (the custom
call ``_moe_grouped_matmul``).  ``measure(run)`` gives the kernel's self
time in the traced span (per chip) and what it served THERE: the rows that
landed on held experts and the held expert-layers that got at least one.

**Time and work from the same calls.**  The step records carry
``moe_held_rows``, ``moe_held_hits`` and ``moe_held_pieces`` (counted on
the device, read with the dispatch's tokens), joined to the trace's
``engine.step`` spans by ``seq``.  That join misses a dispatch whose span
opened before the capture and counts one whose device work runs after it
(a fused window of 32 steps is a quarter of a two-second capture), so
summing the joined records against the whole trace's kernel time reads a
third off either way (found on the chip, PERF.md §6, PR 41).  Here the
joined records give the work a CALL does, by phase (rows and hits over 3
x pieces: a decode window's and a packed prefill's calls differ a
hundredfold), and the trace gives the calls and their time by phase (the
scope on each operation, ``_scope_trace.read_ops``); a phase with calls
and no joined record is left out of both.  None where the run has no
trace, the trace has no such kernel, or no joined step record carries the
counts: a model that holds every expert, a program from before the share.
"""

from benchmark.harness import host_spans
from benchmark.harness import trace_reduce as tr
from benchmark.layer_metrics import _scope_trace as st

KERNEL = "_moe_grouped_matmul"
CALLS_A_PIECE = 3                   # gate, up, down
# step record kind -> the phase its trunk opens (tpuserve/ops/scopes.py)
PHASE = {"window": "decode", "decode": "decode", "prefill": "prefill",
         "mixed": "prefill", "prefill_chunk": "chunk"}
_KEY = "_moe_held_trace"


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


def measure(run):
    """``{"kernel_ns", "rows", "hits"}`` of a traced run, or None."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    spans = host_spans.analyse(run)
    if not spans:
        return None
    done = {}                   # phase -> [rows, hits, pieces] a record
    for s in spans["steps_joined"]:
        if s.get("moe_held_pieces") and s.get("kind") in PHASE:
            cell = done.setdefault(PHASE[s["kind"]], [0, 0, 0])
            for i, key in enumerate(("moe_held_rows", "moe_held_hits",
                                     "moe_held_pieces")):
                cell[i] += s[key]
    if not done:
        return None
    from benchmark.harness.session import find_xplane
    ns = rows = hits = 0.0
    for phase, (t, calls) in kernel_by_phase(
            find_xplane(run["trace_dir"])).items():
        if phase in done:
            a_call = calls / (CALLS_A_PIECE * done[phase][2])
            ns += t
            rows += done[phase][0] * a_call
            hits += done[phase][1] * a_call
    if ns <= 0 or rows <= 0:
        return None
    run[_KEY] = {"kernel_ns": ns, "rows": rows, "hits": hits}
    return run[_KEY]
