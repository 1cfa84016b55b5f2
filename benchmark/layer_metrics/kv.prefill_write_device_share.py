"""Share of device busy time a prompt's K and V take on their way into the
paged cache: self time in ``attn.kv_write`` under the phases ``prefill/``
(a packed or batched prefill) and ``chunk/`` (``prefill_chunk``) over the
union of all device operations in the traced span (per chip;
``_scope_trace``).  The write is one scatter index a token row, or one copy
a page where the trunk owns a page-aligned stream; decode's own write (one
row a sequence a step) is ``decode/attn.kv_write``, in
``trunk.decode_glue_ms``.  It moves with how much of the span is prefill:
read it beside ``step.prefill_device_share``."""

from benchmark.layer_metrics import _scope_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("attn.kv_write",)


def compute(run):
    return _scope_trace.share_of_busy(run, _scope_trace.PREFILL_PHASES, PARTS)
