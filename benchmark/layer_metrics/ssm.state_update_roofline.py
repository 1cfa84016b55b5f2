"""The decode-time state update's share of its roofline: the least time
the chip could take for the row-layers the kernel served in the traced
span, over its self time there (both as ``ssm.state_update_ns_per_row``
takes them).  For one row of one layer the kernel must read and write the
row's state once — ``H P N`` elements each way at the pool's item size
(float32, the configuration's ``assumed.ssm_state``) — and its small
inputs and output (decay, ``dt x`` and ``y``: ``H P`` float32 each; ``B``
and ``C``: ``G N`` each), and spends 5 operations a state element (decay,
outer product, add, times ``C``, sum).  8.4 MB against 5.2 MFLOP at the
published sizes: memory-bound by two orders of magnitude, so the share is
those bytes over the HBM rate over the measured time.  The convolution's
memory is shifted outside the kernel and is not counted."""

from benchmark.harness import roofline
from benchmark.layer_metrics import _ssm_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "device_trace"
STATE_ITEMSIZE = 4


def work_per_row_layer(cfg: dict) -> tuple:
    """``(operations, bytes)`` of one row of one layer."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    state = h * p * n
    nbytes = 2 * state * STATE_ITEMSIZE + (3 * h * p + 2 * g * n) * 4
    return 5.0 * state, float(nbytes)


def compute(run):
    m = _ssm_trace.measure(run)
    if m is None or not run.get("peaks"):
        return None
    flops, nbytes = work_per_row_layer(run["config"])
    part = roofline.share(m["kernel_ns"] * 1e-9, flops * m["row_layers"],
                          nbytes * m["row_layers"], run["peaks"])
    return None if part is None else 100.0 * part
