"""Share of device busy time spent prefilling: self time under the phases
``prefill/`` (a packed or batched prefill, ``forward_ragged`` and
``prefill``) and ``chunk/`` (``prefill_chunk``) over the union of all
device operations in the traced span (per chip; ``_scope_trace``).  Two
seconds of trace hold 5-15 prefill batches, so this swings from one traced
run to the next, and every reader that mixes prefill time with decode time
(``moe.gmm_ns_per_row``, ``kernel.attn_device_share``, the shares of busy
time) swings with it: read them beside it."""

from benchmark.layer_metrics import _scope_trace

LAYER = "model trunk"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return _scope_trace.share_of_busy(run, _scope_trace.PREFILL_PHASES)
