"""Nanoseconds of the paged decode kernel per context token attended: self
time of ``_paged_decode_attention`` in the traced span (per chip) over the
context tokens the decode windows in that span attend, from the step
records' ``ctx_tokens`` joined to the trace's ``engine.step`` spans by
``seq`` (``benchmark/harness/host_spans.py``).  The raw quantity a
roofline share would be computed from; independent of the traffic's mix
of prefill and decode."""

from benchmark.harness import host_spans

LAYER = "kernels"
UNIT = "ns/token"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    result = host_spans.analyse(run)
    if not result or result["decode_attn_ns"] <= 0 \
            or result["decode_ctx_tokens"] <= 0:
        return None
    return result["decode_attn_ns"] / result["decode_ctx_tokens"]
