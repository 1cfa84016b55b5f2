"""What the ``kda.*`` readers share (the leading underscore keeps
``plan.discover_layer_metrics`` from taking this for a metric).

The decode-time state update of a model with Kimi-delta linear-attention
layers (a decay for every key channel) is one Pallas custom call,
``_kda_state_update`` (``tpuserve/ops/pallas_kda_update.py``): one call a
linear layer a decode step, one grid row a batch row.  ``measure(run)``
gives its self time in the traced span (per chip) and the row-layers it
served THERE, as ``_lin_trace.measure`` does for the scalar gate's
``_gdn_state_update`` and by its rules: time and work from the same calls
(the trace's calls under ``decode/`` times the real rows a call), the rows
a call from the nearest step records that hold a decode step (the ``seq``
join's, those stamped inside the traced span, the whole window's:
``_lin_trace.rows_of``), so that a span that holds the kernel's calls
always reads.  The linear layers are counted from the configuration's own
keys (``layer_group_size`` p: layer i attends where ``(i + 1) % p == 0``),
never from ``num_hidden_layers`` alone: 10 of 12 call this kernel.  None
where the run has no trace, the configuration no such layer, the trace no
such kernel (a program from before it) or the run recorded no decode step
at all.

``work_per_row_layer`` is what a row-layer IS, whatever implements it, from
the PUBLISHED sizes: the row's state read and written once, ``H d d``
float32 elements each way (``assumed.state``), beside its q, k and decay
column (``H d`` each), v and o (``H d`` each) and a step size a head,
float32; about 7 operations a state element (the decay; ``S^T k``, a
multiply and an add; the rank-one write, a multiply and an add; ``S^T q``,
a multiply and an add).  4,276,352 B against 3.7 MFLOP at the published
sizes (32 x 128 x 128): memory-bound by two orders of magnitude, least
time 5.2 us.  Bytes a kernel moves beyond these are no work and read as
lost share.
"""

from benchmark.harness import host_spans
from benchmark.harness import trace_reduce as tr
from benchmark.layer_metrics import _lin_trace
from benchmark.layer_metrics import _scope_trace as st

KERNEL = "_kda_state_update"
STATE_ITEMSIZE = 4
OPS_A_STATE_ELEMENT = 7.0
_KEY = "_kda_trace"


def linear_layers(config: dict) -> int:
    """The Kimi-delta layers among the layers that run."""
    period = config.get("layer_group_size") or 0
    if not period or "kda_lower_bound" not in config:
        return 0
    return sum(bool((i + 1) % period)
               for i in range(config.get("num_hidden_layers", 0)))


def work_per_row_layer(cfg: dict) -> tuple:
    """``(operations, bytes)`` of one row of one Kimi-delta layer."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    state = h * d * d
    nbytes = 2 * state * STATE_ITEMSIZE + (5 * h * d + h) * 4
    return OPS_A_STATE_ELEMENT * state, float(nbytes)


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


def scopes(run):
    """``_scope_trace.measure(run)`` for a configuration with Kimi-delta
    layers and a trace with busy time, else None."""
    if not linear_layers(run.get("config") or {}):
        return None
    m = st.measure(run)
    return m if m is not None and m["busy_s"] > 0 else None


def share_of_busy(run, phases, parts):
    """Per cent of busy time under these scopes; 0.0 where the span holds
    none of them."""
    m = scopes(run)
    if m is None:
        return None
    return 100.0 * st.seconds(m, phases, parts) / m["busy_s"]


def measure(run):
    """``{"kernel_ns", "row_layers", "calls"}`` of a traced run, or None."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    layers = linear_layers(run.get("config") or {})
    if not run.get("trace_dir") or not layers:
        return None
    joined = (host_spans.analyse(run) or {}).get("steps_joined") or []
    rows = _lin_trace.rows_of(run, joined)
    from benchmark.harness.session import find_xplane
    path = find_xplane(run["trace_dir"])
    if rows is None or not path:
        return None
    by_phase = kernel_by_phase(path)
    ns, calls = by_phase.get("decode", (0, 0))
    if ns <= 0 or calls <= 0:
        return None
    print(f"[bench] {KERNEL}: {calls:.0f} calls under decode/ "
          f"({calls / layers:.2f} fused steps over {layers} Kimi-delta "
          f"layers; the seq join holds {st.fused_steps(joined):.2f}), "
          f"{rows:.2f} real rows a call; other phases "
          f"{ {p: c for p, (_, c) in by_phase.items() if p != 'decode'} }",
          flush=True)
    run[_KEY] = {"kernel_ns": ns, "row_layers": calls * rows, "calls": calls}
    return run[_KEY]
