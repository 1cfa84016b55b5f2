"""Device milliseconds per fused decode step in the head and the sampler:
self time under ``decode/head`` (final norm, the vocabulary projection) and
``decode/sample`` (the argmax XLA fuses into it, penalties, masks, logprob
gathers) over the fused decode steps in the span, counted from the same
events (``_scope_trace``).  A depth cut leaves the head whole, so it is the
part of a step that the cut enlarges."""

from benchmark.layer_metrics import _scope_trace

LAYER = "model trunk"
UNIT = "ms"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("head", "sample")


def compute(run):
    return _scope_trace.per_decode_step_ms(run, PARTS)
