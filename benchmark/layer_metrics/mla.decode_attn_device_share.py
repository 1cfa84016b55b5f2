"""Share of device busy time the latent decode attention takes: self time
under ``decode/attn.kernel`` (the paged decode kernel's latent entry) over
the union of all device operations in the traced span (per chip;
``_scope_trace``).  ``kernel.attn_device_share`` counts EVERY custom call,
the held experts' grouped product too.  0.0 where the span holds no decode
call."""

from benchmark.layer_metrics import _mla_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return _mla_trace.share_of_busy(run, ("decode",), ("attn.kernel",))
