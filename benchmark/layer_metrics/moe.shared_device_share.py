"""Share of device busy time spent in the SHARED expert of the expert
layers: self time under the scope ``moe.shared`` (``tpuserve/ops/
scopes.py``), in every phase, over the union of all device operations in
the traced span (per chip).  The shared expert is a dense gated MLP every
token passes through, beside the routed ones; a chip that holds a share of
the routed experts holds it whole.

The accepted reader of scopes (``_scope_trace.py``) keeps its own list of
parts, taken before this one existed, and files this time under ``mlp``,
which encloses it; so this file reads the trace's operations itself
(``_scope_trace.read_ops``) with that reader's rule: an operation's part
is the last component of its ``op_name`` that names one, and what the
compiler made (no ``op_name``: the wait for a prefetched slice of a weight
matrix) goes to the next operation of its program that names a part.
None where the run has no trace or the trace names no such scope."""

from benchmark.harness import trace_reduce as tr
from benchmark.layer_metrics import _scope_trace as st

LAYER = "model trunk"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PART = "moe.shared"
_PARTS = st.PARTS + (PART,)


def part_ns(ops: list, part: str = PART) -> tuple:
    """``(self ns under part, busy ns)`` of one chip's events."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    owner, nxt = [None] * len(ops), {}
    for i in range(len(ops) - 1, -1, -1):
        _, _, name, op_name, program = ops[i]
        if st.compiler_made(name, op_name):
            owner[i] = nxt.get(program)
            continue
        owner[i] = next((c for c in reversed(op_name.split("/"))
                         if c in _PARTS), "")
        if owner[i]:
            nxt[program] = owner[i]
    busy, _ = tr.union_and_gaps([(s, e) for s, e, *_ in ops])
    return sum(ns for i, ns in tr.self_times(
        [(s, e, i) for i, (s, e, *_) in enumerate(ops)])
        if owner[i] == part), busy


def compute(run):
    if not run.get("trace_dir"):
        return None
    from benchmark.harness.session import find_xplane
    path = find_xplane(run["trace_dir"])
    if not path:
        return None
    chips = [part_ns(ops) for ops in st.read_ops(path)]
    under, busy = (sum(c[i] for c in chips) for i in (0, 1))
    if under <= 0 or busy <= 0:
        return None
    return 100.0 * under / busy
