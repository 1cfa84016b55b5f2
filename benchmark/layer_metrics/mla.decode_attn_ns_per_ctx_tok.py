"""Nanoseconds the latent decode attention takes a cached token a LAYER:
self time under ``decode/attn.kernel`` (the paged decode kernel's latent
entry, one call a layer a fused step) over the token-layers those calls
attended (``_mla_trace.decode_attention``: calls from the same events,
context tokens a call from the step records).  Against 1.41 ns by bytes
(1,152 B at 819 GB/s) and 1.41 ns by operations (278,528 at 197e12) at the
published sizes.  ``kernel.decode_attn_ns_per_ctx_tok`` is over ALL layers
of a token; this is one layer's.  0.0 where the span holds no decode
call."""

from benchmark.layer_metrics import _mla_trace

LAYER = "kernels"
UNIT = "ns/token"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    d = _mla_trace.decode_attention(run)
    if d is None:
        return None
    return d["ns"] / d["token_layers"] if d["token_layers"] > 0 else 0.0
