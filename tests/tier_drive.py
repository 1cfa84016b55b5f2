"""What the KV-tier tests share: how a prefix gets INTO the tier.

The tier admits a block only when its chain hash has left HBM before
(runtime/kv_tiers.py ``admit``), so one cold pass is declined and the
second demotes.  Imported by test_kv_tiers.py, test_kv_tier_admission.py,
test_engine_spans.py and test_autoscale.py, whose engines all hold 24
blocks of 4 tokens."""

from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SchedulerConfig)

# unrelated prompts that exhaust such a pool: whatever was cached leaves HBM
CHURN = [[100 + i] * 40 for i in range(3)]


def tiny_engine(tiers, dtype="bfloat16", **kw):
    """A prefix-caching tiny-qwen3 engine over that pool; ``tiers`` is
    ``EngineConfig.kv_tiers``, ``dtype`` the cache pages'."""
    return Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=24, max_blocks_per_seq=16,
                          dtype=dtype),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        enable_prefix_caching=True, kv_tiers=tiers, **kw))


def cold_twice(eng, prompts, params):
    """Serve ``prompts`` and push their blocks out of HBM with ``CHURN``,
    twice over: the first eviction of each hash is only remembered, the
    recompute proves the prefix returns, the second eviction demotes."""
    for _ in range(2):
        eng.generate(prompts, params)
        eng.generate(CHURN, params)
