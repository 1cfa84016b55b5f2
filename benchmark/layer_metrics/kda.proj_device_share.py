"""Share of device busy time a decode step's Kimi-delta layers spend
OUTSIDE their state update: self time under ``decode/`` in ``ssm.in_proj``
(the six projections: q, k, v as one, the output gate, the decay's, the
step size's; ``ssm.gate`` lies inside it and is filed under it),
``ssm.conv`` (the convolution memory's step in place, the activations, the
decay gate's) and ``ssm.out`` (the head norm, the output gate, the output
projection), over the union of all device operations in the traced span
(per chip; ``_scope_trace``).  At 128 rows these read their weights once a
step (126 MB a layer at the published sizes) whatever the batch, where the
state update's time follows the rows.  0.0 where the span holds no decode
step."""

from benchmark.layer_metrics import _kda_trace

LAYER = "model trunk"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return _kda_trace.share_of_busy(
        run, ("decode",), ("ssm.in_proj", "ssm.conv", "ssm.out"))
