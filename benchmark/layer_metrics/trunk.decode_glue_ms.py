"""Device milliseconds per fused decode step around the layers: self time
under ``decode/`` in ``embed``, ``attn.kv_write`` (the new token's scatter
into the paged cache) and ``carry`` (a fused window's step arithmetic), and
under the bare phase (the ``while``'s own time, the scan's output write,
whatever else names no part), over the fused decode steps in the span,
counted from the same events (``_scope_trace``).  What a step pays for
being a step; a wait the compiler made is filed with the part that uses
what it waited for, not here."""

from benchmark.layer_metrics import _scope_trace

LAYER = "model trunk"
UNIT = "ms"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("embed", "attn.kv_write", "carry", "")


def compute(run):
    return _scope_trace.per_decode_step_ms(run, PARTS)
