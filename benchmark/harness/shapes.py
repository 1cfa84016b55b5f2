"""The shapes a cell's traffic can make the engine dispatch: what set-up
warms, and nothing else.

Derived from the traffic file's length bounds through the scheduler's own
bucket functions, so a change to the bucketing moves the warm-up with it.
"""

from __future__ import annotations


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def warm_shapes(scheduler, bounds: dict) -> dict:
    """Arguments for ``Engine.warmup``: the ``(batch, length)`` prefill
    buckets, the chunk buckets and the decode buckets that prompts of
    ``prompt_min..prompt_max`` tokens and answers of up to ``output_max``
    can reach.  A preempted request re-prefills its prompt plus what it has
    generated, so lengths run up to ``total_max``."""
    cfg = scheduler.cfg
    chunk = cfg.prefill_chunk_size
    chunked = cfg.allow_chunked_prefill and bounds["total_max"] > chunk
    longest_batched = min(bounds["total_max"], chunk) if chunked \
        else bounds["total_max"]
    lengths = sorted({scheduler.prefill_bucket(n) for n in
                      range(bounds["prompt_min"], longest_batched + 1)})
    prefill = set()
    for length in lengths:
        for picked in range(1, cfg.max_prefill_seqs + 1):
            # the admission rule: a further request joins only while
            # bucket * picked stays inside the token budget
            if picked > 1 and length * picked > cfg.max_prefill_tokens:
                break
            prefill.add((_pow2_ceil(picked), length))
    chunks = set()
    if chunked:
        # a chunked prompt runs whole chunks and one padded tail; a
        # prefix hit starts anywhere, so every tail bucket can occur
        chunks = {scheduler._chunk_bucket(n)
                  for n in range(1, bounds["total_max"] + 1)}
    decode = sorted({scheduler.decode_bucket(n)
                     for n in range(1, cfg.max_num_seqs + 1)})
    return {"prefill_buckets": sorted(prefill),
            "chunk_buckets": sorted(chunks),
            "decode_buckets": decode}
