"""Admission to the KV tier: a block enters only when its chain hash has
left HBM before (runtime/kv_tiers.py ``admit``; Engine._demote_evicted).

A first eviction is declined with nothing gathered, copied or threaded; a
prefix that went cold twice is demoted and restores token-identically
under both block managers and both page dtypes; a conversation whose
turns go cold serves the same tokens as a tier-off engine for fewer
prefill tokens; the memory of hashes is bounded and in no tier.  CPU run:
control flow and counts, no device number."""

import numpy as np
import pytest

from tpuserve.runtime import SamplingParams, kv_tiers
from tpuserve.runtime.block_manager import BlockManager
from tpuserve.runtime.kv_tiers import TieredPageStore

from tier_drive import CHURN, cold_twice
from tier_drive import tiny_engine as _mk_engine

SHARED = list(range(2, 26))      # 24 tokens = 6 full blocks at block_size 4
PARAMS = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
PROBE = 84     # clear of a tie in all six tokens (tests/test_kv_tiers.py)


def _pages(nbytes=64):
    return [{"k": np.arange(nbytes, dtype=np.int8)}]


# ---------------------------------------------------------------------------
# the store's rule
# ---------------------------------------------------------------------------

def test_a_hash_is_admitted_from_its_second_eviction_on():
    st = TieredPageStore(host_bytes=1 << 20)
    assert not st.admit(5)              # never out of HBM before: declined
    assert st.admit(5) and st.admit(5)  # from then on, every time
    assert not st.admit(6)


def test_a_remembered_hash_is_in_no_tier():
    st = TieredPageStore(host_bytes=1 << 20)
    assert not st.admit(5)
    assert not st.has(5) and st.where(5) is None
    assert list(st.hashes()) == [] and len(st) == 0
    assert st.host_count == st.spill_count == st.in_flight_count == 0
    assert st.take(5) is None
    st.drop(5)                          # nothing to drop, nothing raised
    assert st.admit(5)                  # ... and still remembered
    st.clear()
    assert not st.admit(5)              # clear() forgets it too


def test_the_memory_of_hashes_is_bounded_and_forgets_the_oldest_first():
    st = TieredPageStore(host_bytes=1 << 20)
    bound = kv_tiers.MAX_SEEN_HASHES
    assert bound == kv_tiers.DEFAULT_MAX_SPILL_ENTRIES == 1 << 16
    for h in range(bound + 10):
        assert not st.admit(h)
        assert len(st._seen) <= bound
    assert len(st._seen) == bound and len(st) == 0
    assert st.admit(bound + 9) and st.admit(10)     # the newest, the oldest
    assert not st.admit(0)              # forgotten: it proves itself again
    assert len(st._seen) == bound
    assert not st.admit(10)             # ... and pushed the oldest out


def test_what_a_restore_took_has_left_hbm_before(tmp_path):
    """A direct ``put`` stays unconditional, and pages that were in the
    store (a spill file adopted at start too) prove their hash."""
    st = TieredPageStore(host_bytes=1, spill_dir=str(tmp_path))
    st.put(11, _pages())
    st.flush()
    assert st.where(11) == "spill"
    adopted = TieredPageStore(host_bytes=1 << 20, spill_dir=str(tmp_path))
    assert adopted.has(11) and adopted.take(11) is not None
    assert adopted.admit(11)
    assert adopted.take(12) is None and not adopted.admit(12)


# ---------------------------------------------------------------------------
# the engine asks before it gathers
# ---------------------------------------------------------------------------

def test_a_first_eviction_is_declined_and_touches_nothing(monkeypatch):
    from tpuserve.runtime import kv_cache
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    gathers = []
    real = kv_cache.enqueue_block_pages_gather
    monkeypatch.setattr(
        kv_cache, "enqueue_block_pages_gather",
        lambda cache, blocks: (gathers.append(len(blocks)),
                               real(cache, blocks))[1])
    eng = _mk_engine(True)
    store = eng._kv_tiers
    assert isinstance(eng.block_manager, BlockManager)
    eng.generate([SHARED + [30 + i] for i in range(2)], PARAMS)
    eng.generate(CHURN, PARAMS)         # the shared prefix leaves HBM
    chain = eng.block_manager.prefix_chain(SHARED + [PROBE])
    assert chain and not any(eng.block_manager.prefix_resolvable(h)
                             for h in chain)
    assert eng.stats.kv_demote_declined_blocks >= len(chain)
    assert eng.stats.kv_demoted_blocks == 0 and not gathers
    assert len(store) == 0 and store.in_flight_batches == 0
    assert store._copier is None, "a declined eviction made the copier"
    assert not eng._demote_budget_read
    assert all(h in store._seen for h in chain)
    # the prefix is gone as with no tier: recomputed, and the same tokens
    # (strict mode: a remembered hash now resolvable in HBM is in one tier)
    tiered = eng.generate([SHARED + [PROBE]], PARAMS)[0]
    assert eng.stats.kv_restores == 0
    cold = _mk_engine(False).generate([SHARED + [PROBE]], PARAMS)[0]
    assert tiered.output_token_ids == cold.output_token_ids
    eng._check_block_integrity()
    # its second time cold it is demoted
    eng.generate(CHURN, PARAMS)
    assert eng.stats.kv_demoted_blocks > 0 and gathers
    assert all(store.has(h) for h in chain)


@pytest.mark.parametrize("manager", ["python", "native"])
@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_a_prefix_cold_twice_restores_token_identically(monkeypatch, dtype,
                                                        manager):
    from tpuserve import native
    if manager == "native" and not native.native_available():
        pytest.skip("the native block manager is not built here")
    monkeypatch.setenv("TPUSERVE_BLOCK_MANAGER", manager)
    if manager == "python":             # the native one has no such check
        monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True, dtype)
    assert isinstance(eng.block_manager, BlockManager) == \
        (manager == "python")
    cold_twice(eng, [SHARED + [30]], PARAMS)
    assert eng.stats.kv_demoted_blocks > 0
    assert eng.stats.kv_demote_declined_blocks > 0
    assert eng.stats.kv_restores == 0
    chain = eng.block_manager.prefix_chain(SHARED + [PROBE])
    assert all(eng._kv_tiers.has(h) for h in chain)
    tiered = eng.generate([SHARED + [PROBE]], PARAMS)[0]      # third arrival
    assert eng.stats.kv_restores == 1
    assert eng.stats.kv_restored_blocks == len(chain)
    cold = _mk_engine(False, dtype).generate([SHARED + [PROBE]], PARAMS)[0]
    assert tiered.output_token_ids == cold.output_token_ids


def test_a_conversation_whose_turns_go_cold_prefills_less_than_tier_off(
        monkeypatch):
    """Four turns, each the history plus eight new tokens, every turn
    pushed out of HBM before the next.  Late admission costs one more
    recompute a block than admitting at once, and still beats no tier:
    turn 2 proves turn 1's blocks, turns 3 and 4 restore them."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)

    def converse(eng):
        history, served = list(range(2, 14)), []
        for turn in range(4):
            out = eng.generate([history], params)[0].output_token_ids
            served.append(list(out))
            history = history + list(out) + [40 + 8 * turn + i
                                            for i in range(8)]
            eng.generate(CHURN, params)
        return served

    on, off = _mk_engine(True), _mk_engine(False)
    assert converse(on) == converse(off)
    assert on.stats.kv_demote_declined_blocks > 0
    assert on.stats.kv_restored_blocks > 0
    assert on.stats.prefill_tokens_total < off.stats.prefill_tokens_total


def test_the_declined_counter_is_exported():
    from tpuserve.server.metrics import ServerMetrics
    from tpuserve.server.runner import AsyncEngineRunner
    eng = _mk_engine(True)
    eng.generate([SHARED + [30]], PARAMS)
    eng.generate(CHURN, PARAMS)
    declined = eng.stats.kv_demote_declined_blocks
    assert declined > 0
    runner = AsyncEngineRunner(eng, ServerMetrics("tiny-qwen3"))
    runner._update_gauges()
    text = runner.metrics.render().decode()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("tpuserve_kv_blocks_demote_declined_total"))
    assert float(line.rsplit(" ", 1)[1]) == declined
    assert "tpuserve_kv_blocks_demoted_total" in text
