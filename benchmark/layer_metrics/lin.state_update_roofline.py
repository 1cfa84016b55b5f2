"""The gated delta-rule state update's share of its roofline: the least
time the chip could take for the row-layers ``_gdn_state_update`` served in
the traced span, over its self time there (``_lin_trace.py``: time and work
from the same calls).

The work a row-layer IS, whatever implements it, from the PUBLISHED sizes:
the row's state is read and written once -- ``H dk dv`` float32 elements
each way (the configuration's ``assumed.state``) -- beside its q and k
(``H dk`` each), v and o (``H dv`` each) and two scalars a head, float32;
about 7 operations a state element (the decay; ``S^T k``, a multiply and an
add; the rank-one write, a multiply and an add; ``S^T q``, a multiply and an
add).  4.49 MB against 3.9 MFLOP at the published sizes (30 x 96 x 192):
memory-bound by two orders of magnitude, least time 5.5 us.  Bytes the
kernel moves beyond these -- lane padding of a layout, inputs spread over
lanes -- are no work and read as lost share.  Never clipped."""

from benchmark.harness import roofline
from benchmark.layer_metrics import _lin_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "device_trace"
STATE_ITEMSIZE = 4
OPS_A_STATE_ELEMENT = 7.0


def work_per_row_layer(cfg: dict) -> tuple:
    """``(operations, bytes)`` of one row of one linear layer."""
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    state = h * dk * dv
    nbytes = 2 * state * STATE_ITEMSIZE + (2 * h * dk + 2 * h * dv
                                           + 2 * h) * 4
    return OPS_A_STATE_ELEMENT * state, float(nbytes)


def compute(run):
    m = _lin_trace.measure(run)
    if m is None or not run.get("peaks"):
        return None
    flops, nbytes = work_per_row_layer(run["config"])
    part = roofline.share(m["kernel_ns"] * 1e-9, flops * m["row_layers"],
                          nbytes * m["row_layers"], run["peaks"])
    return None if part is None else 100.0 * part
