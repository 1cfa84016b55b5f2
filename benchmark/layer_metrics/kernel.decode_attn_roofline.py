"""The paged decode kernel's share of its roofline: the least time the chip
could take to attend the context tokens its decode windows attend in the
traced span, over the kernel's self time there (both as
``kernel.decode_attn_ns_per_ctx_tok`` takes them, from
``benchmark/harness/host_spans.py``).  Per context token the kernel must
read that token's keys and values in every layer once
(``run["kv_bytes_per_token"]``, the cache's own bytes a token) and spends
2 multiply-adds a query head and feature on it (scores, then values); on
one query row a step that is memory-bound by two orders of magnitude, so
the share is the cache's bytes over the HBM rate over the measured time.
A sliding window shorter than the context would let a kernel read less
than this counts: the share then reads low, never high.  The trace's time
is per chip, so a cache sharded over a cell's chips counts a chip's part."""

from benchmark.harness import host_spans, roofline

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def work_per_ctx_token(run) -> tuple:
    """``(operations, bytes)`` the kernel needs for one context token of
    one decode row, over all layers."""
    cfg = run["config"]
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    flops = 4.0 * heads * head_dim * cfg["num_hidden_layers"]
    return flops / run["chips"], run["kv_bytes_per_token"] / run["chips"]


def compute(run):
    result = host_spans.analyse(run)
    if not result or result["decode_attn_ns"] <= 0 \
            or result["decode_ctx_tokens"] <= 0 \
            or not run.get("kv_bytes_per_token") or not run.get("peaks"):
        return None
    flops, nbytes = work_per_ctx_token(run)
    tokens = result["decode_ctx_tokens"]
    part = roofline.share(result["decode_attn_ns"] * 1e-9, flops * tokens,
                          nbytes * tokens, run["peaks"])
    return None if part is None else 100.0 * part
