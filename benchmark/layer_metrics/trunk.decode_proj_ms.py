"""Device milliseconds per fused decode step in the dense projections:
self time under ``decode/`` in ``attn.qkv``, ``attn.out``, ``mlp``,
``ssm.in_proj`` and ``ssm.out`` (norms and rotary with them) over the fused
decode steps in the span, counted from the same events (``_scope_trace``:
calls of the paged decode kernel under ``decode/`` over the layers).

This is the projections' EXPOSED time, not the whole read of their
weights: the compiler prefetches a weight matrix in row slices
(``slice-start`` ... ``slice-done``) ahead of the product that reads it,
and only the wait that is left when the product is due (the ``slice-done``,
filed under the product that consumes it) is the product's.  Where the
slices fly beside the attention or expert kernel (PERF.md §5: all of
Qwen3's 0.87 GB a step does) the reading lies UNDER the weights' bytes at
the chip's HBM rate; where products follow products it lies at or above
it.  An expert layer's products are ``moe.experts``, not in it."""

from benchmark.layer_metrics import _scope_trace

LAYER = "model trunk"
UNIT = "ms"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("attn.qkv", "attn.out", "mlp", "ssm.in_proj", "ssm.out")


def compute(run):
    return _scope_trace.per_decode_step_ms(run, PARTS)
