"""Tiered KV cache: HBM -> host-DRAM -> PVC prefix offload.

Covers the tier store (budget/spill/exactly-one-tier), the engine's
demote -> restore round trip (pinned token-identical to cold prefill,
with TPUSERVE_STRICT_BLOCKS cross-checking block and tier accounting
every cycle), the restore-in-flight state machine, the per-lookup
honesty of the prefix hit-rate counters, and the cache-aware routing
digest (server/kv_digest.py + gateway preference)."""

import os

import numpy as np
import pytest

from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SamplingParams, SchedulerConfig)
from tpuserve.runtime.block_manager import BlockManager
from tpuserve.runtime.kv_tiers import TieredPageStore

from tier_drive import CHURN, cold_twice
from tier_drive import tiny_engine as _mk_engine


def _pages(nbytes=64, dtype=np.int8):
    return [{"k": np.arange(nbytes, dtype=dtype)}]


# ---------------------------------------------------------------------------
# tier store
# ---------------------------------------------------------------------------

def test_store_budget_cascades_to_spill(tmp_path):
    st = TieredPageStore(host_bytes=200, spill_dir=str(tmp_path))
    for h in range(5):                      # 5 x 64B > 200B budget
        st.put(h, _pages())
    assert st.host_count + st.spill_count == 5
    assert st.host_bytes_used <= 200
    assert st.spill_count >= 2 and st.spilled_blocks == st.spill_count
    st.flush()                              # writes land off-thread
    assert len(os.listdir(tmp_path)) == st.spill_count
    # every hash still resolvable (demoted hashes must stay resolvable)
    for h in range(5):
        assert st.has(h)


def test_store_drops_without_spill_dir():
    st = TieredPageStore(host_bytes=200, spill_dir=None)
    for h in range(5):
        st.put(h, _pages())
    assert st.host_count <= 3
    assert st.dropped_blocks == 5 - st.host_count
    assert st.spill_count == 0


def test_store_take_removes_from_exactly_one_tier(tmp_path):
    st = TieredPageStore(host_bytes=200, spill_dir=str(tmp_path))
    for h in range(5):
        st.put(h, _pages())
    st.flush()
    for h in range(5):
        where = st.where(h)
        pages = st.take(h)
        assert pages is not None and pages[0]["k"].dtype == np.int8
        assert not st.has(h), f"hash {h} still resolvable after take"
        if where == "spill":
            assert not os.path.exists(st._spill_path(h))
    assert len(st) == 0 and st.host_bytes_used == 0


def test_store_spill_roundtrips_bfloat16(tmp_path):
    import jax.numpy as jnp
    st = TieredPageStore(host_bytes=1, spill_dir=str(tmp_path))
    a = np.asarray(jnp.arange(8, dtype=jnp.bfloat16))
    st.put(7, [{"k": a}])
    st.flush()           # force the real .npz round trip, not the
    assert st._spill     # in-memory pending-write path
    out = st.take(7)
    assert out is not None
    assert out[0]["k"].dtype == a.dtype
    np.testing.assert_array_equal(out[0]["k"].astype(np.float32),
                                  a.astype(np.float32))


def test_store_unreadable_spill_is_a_miss(tmp_path):
    st = TieredPageStore(host_bytes=1, spill_dir=str(tmp_path))
    st.put(3, _pages())
    st.flush()
    assert st.where(3) == "spill"
    with open(st._spill_path(3), "wb") as f:
        f.write(b"corrupt")
    dropped = st.dropped_blocks
    assert st.take(3) is None       # caller falls back to recompute
    assert not st.has(3)
    # the KV was LOST, not restored — the tier-loss counter must move
    assert st.dropped_blocks == dropped + 1


def test_store_rescan_survives_restart(tmp_path):
    """A new store over an existing spill dir adopts the files (pod
    restart): same-hash takes succeed — the restart-survival story the
    manifests' PVC spill dir exists for (stable hashes = the native
    manager's FNV; this test uses literal keys, which are stable)."""
    st = TieredPageStore(host_bytes=1, spill_dir=str(tmp_path))
    st.put(11, _pages())
    st.put(1 << 63 | 5, _pages())           # high-bit (native-style) hash
    st.flush()
    st2 = TieredPageStore(host_bytes=1, spill_dir=str(tmp_path))
    assert st2.has(11) and st2.has(1 << 63 | 5)
    out = st2.take(11)
    assert out is not None and out[0]["k"].dtype == np.int8
    assert st2.take(1 << 63 | 5) is not None


def test_store_rescan_enforces_cap(tmp_path):
    st = TieredPageStore(host_bytes=1, spill_dir=str(tmp_path))
    for h in range(6):
        st.put(h, _pages())
    st.flush()
    st2 = TieredPageStore(host_bytes=1, spill_dir=str(tmp_path),
                          max_spill_entries=3)
    assert len(os.listdir(tmp_path)) == 3   # oldest trimmed at rescan


# ---------------------------------------------------------------------------
# block-manager tier state machine
# ---------------------------------------------------------------------------

def test_restore_in_flight_blocks_unevictable_and_uncharged():
    bm = BlockManager(8, 4)
    bm.record_evictions = True
    bm.allocate("a", list(range(8)))        # 2 hashed blocks
    bm.free("a")
    bm.allocate("fill", [9] * 32)           # evicts both cached blocks
    ev = bm.take_evictions()
    assert len(ev) == 2
    bm.free("fill", cache_blocks=False)
    hashes = [h for _, h in ev]
    blocks = bm.begin_restore(hashes)
    assert blocks is not None and bm.num_restoring_blocks == 2
    # restore-in-flight blocks are in NO pool: an allocation storm can
    # neither evict nor hand them out
    assert bm.num_free_blocks == 6
    bm.allocate("b", [5] * 24)              # takes all 6 remaining
    assert bm.num_free_blocks == 0
    with pytest.raises(MemoryError):
        bm.allocate("c", [6] * 4)
    assert set(blocks) & set(bm._seqs["b"].blocks) == set()
    bm.check_integrity(expected_seq_ids=["b"])
    assert bm.commit_restore(hashes, blocks) == 2
    assert bm.num_restoring_blocks == 0
    sh, cached = bm.lookup_prefix(list(range(8)) + [1], count_stats=False)
    assert cached == 8 and sh == blocks
    bm.check_integrity(expected_seq_ids=["b"])


def test_abort_restore_returns_blocks():
    bm = BlockManager(8, 4)
    bm.record_evictions = True
    bm.allocate("a", list(range(8)))
    bm.free("a")
    bm.allocate("fill", [9] * 32)
    ev = bm.take_evictions()
    bm.free("fill", cache_blocks=False)
    blocks = bm.begin_restore([h for _, h in ev])
    free_before = bm.num_free_blocks
    bm.abort_restore(blocks)
    assert bm.num_free_blocks == free_before + len(blocks)
    bm.check_integrity(expected_seq_ids=[])


def test_commit_restore_yields_to_fresh_registration():
    """A hash re-registered (identical prompt recomputed) while its
    restore was in flight wins; the redundant restored block goes back to
    the free list instead of double-mapping the hash."""
    bm = BlockManager(8, 4)
    bm.record_evictions = True
    prompt = list(range(8))
    bm.allocate("a", prompt)
    bm.free("a")
    bm.allocate("fill", [9] * 32)
    ev = bm.take_evictions()
    bm.free("fill", cache_blocks=False)
    hashes = [h for _, h in ev]
    blocks = bm.begin_restore(hashes)
    bm.allocate("again", prompt)            # re-registers the same hashes
    assert bm.commit_restore(hashes, blocks) == 0
    bm.free("again")
    bm.check_integrity(expected_seq_ids=[])


def test_prefix_query_counted_once_per_lookup_on_first_block_miss():
    """The hit-rate gauge's honesty: a lookup whose FIRST block already
    misses still counts exactly one query and no hit — in both impls."""
    impls = [BlockManager(16, 4)]
    try:
        from tpuserve.native import NativeBlockManager, native_available
        if native_available():
            impls.append(NativeBlockManager(16, 4))
    except Exception:
        pass
    for bm in impls:
        blocks, n = bm.lookup_prefix([1, 2, 3, 4, 5])   # nothing cached
        assert (blocks, n) == ([], 0)
        assert bm.prefix_queries == 1, type(bm).__name__
        assert bm.prefix_hits == 0, type(bm).__name__
        bm.allocate("s", [1, 2, 3, 4, 5])
        bm.free("s")
        bm.lookup_prefix([1, 2, 3, 4, 5, 6])
        assert bm.prefix_queries == 2 and bm.prefix_hits == 1


# ---------------------------------------------------------------------------
# engine round trip
# ---------------------------------------------------------------------------

SHARED = list(range(2, 26))      # 24 tokens = 6 full blocks at block_size 4
PARAMS = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
# the token a probing request ends in.  Its six greedy tokens have to stand
# clear of a tie: a restored prefix runs the last prompt token through
# another program than a cold prefill does, and the two round bfloat16
# where the compiler lets them.  After 84 the closest runner-up is 0.043
# nats away; after 77 (what stood here to PR 44) the fifth token's was
# 0.0018, and the identity held only while both programs rounded alike.
PROBE = 84


def _churn(eng):
    """Unrelated prompts that exhaust the pool and evict the shared
    prefix out of HBM."""
    eng.generate(CHURN, PARAMS)


def test_demote_restore_token_identity(monkeypatch):
    """THE acceptance pin: after the shared prefix is evicted, demoted,
    and restored from the host tier, a request over it produces exactly
    the tokens a cold engine computes — with strict block+tier integrity
    checked every cycle."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    assert eng._kv_tiers is not None
    cold_twice(eng, [SHARED + [30 + i] for i in range(2)], PARAMS)
    assert eng.stats.kv_demoted_blocks > 0
    assert len(eng._kv_tiers) > 0
    tiered = eng.generate([SHARED + [PROBE]], PARAMS)[0]
    assert eng.stats.kv_restores >= 1
    assert eng.stats.kv_restored_blocks > 0
    cold = _mk_engine(False).generate([SHARED + [PROBE]], PARAMS)[0]
    assert tiered.output_token_ids == cold.output_token_ids


def test_spill_tier_restore_token_identity(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True, kv_host_bytes=3000, kv_spill_dir=str(tmp_path))
    cold_twice(eng, [SHARED + [30]], PARAMS)
    assert eng.stats.kv_spilled_blocks > 0
    tiered = eng.generate([SHARED + [PROBE]], PARAMS)[0]
    cold = _mk_engine(False).generate([SHARED + [PROBE]], PARAMS)[0]
    assert tiered.output_token_ids == cold.output_token_ids


def test_kv_tiers_env_kill_switch(monkeypatch):
    monkeypatch.setenv("TPUSERVE_KV_TIERS", "0")
    eng = _mk_engine(None)
    assert eng._kv_tiers is None
    assert not eng.block_manager.record_evictions
    # legacy behaviour: eviction destroys the prefix, nothing demotes
    eng.generate([SHARED + [30]], PARAMS)
    _churn(eng)
    assert eng.stats.kv_demoted_blocks == 0
    out = eng.generate([SHARED + [PROBE]], PARAMS)[0]
    cold = _mk_engine(False).generate([SHARED + [PROBE]], PARAMS)[0]
    assert out.output_token_ids == cold.output_token_ids


def test_recompute_supersedes_gapped_tier_entries(monkeypatch):
    """Exactly-one-tier under a GAP: when a mid-chain tier entry is lost
    (dropped/unreadable), the hashes past the gap can never be restored
    contiguously — the request recomputes and re-registers them in HBM,
    and the stale store copies must be dropped, or strict mode would
    flag a healthy workload as a two-tier violation (and the copies
    would squat on host budget forever)."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    cold_twice(eng, [SHARED + [30]], PARAMS)
    store = eng._kv_tiers
    assert len(store) >= 3
    # punch a gap: drop a MIDDLE entry of the shared chain from the store
    chain = eng.block_manager.prefix_chain(SHARED + [PROBE])
    resolvable = [h for h in chain if store.has(h)]
    assert len(resolvable) >= 3
    store.drop(resolvable[1])
    tiered = eng.generate([SHARED + [PROBE]], PARAMS)[0]   # strict-checked
    # every chain hash left the store (restored span taken, gap tail
    # superseded by the recompute)
    assert not any(store.has(h) for h in chain)
    cold = _mk_engine(False).generate([SHARED + [PROBE]], PARAMS)[0]
    assert tiered.output_token_ids == cold.output_token_ids


def test_exact_block_multiple_prompt_supersedes_store(monkeypatch):
    """Regression (found by live strict-mode verification): registration
    hashes len//block_size full blocks — ONE more than the lookup bound
    for an exact-block-multiple prompt — so the supersede-drop must use
    the REGISTRATION bound, or the extra hash ends up resolvable in HBM
    and the store at once."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    exact = list(range(2, 26))              # 24 tokens = exactly 6 blocks
    assert len(exact) % eng.cache_cfg.block_size == 0
    cold_twice(eng, [exact], PARAMS)       # registers all 6 block hashes; demoted
    assert all(eng._kv_tiers.has(h)
               for h in eng.block_manager.prefix_chain(exact + [0]))
    # re-admit the SAME exact-multiple prompt: lookup probes only 5
    # blocks, the 6th is recomputed + re-registered — strict mode checks
    # the store copy left (every step cross-checks tier_hashes)
    eng.generate([exact], PARAMS)
    eng.generate([exact + [50]], PARAMS)    # longer chain over the same prefix
    eng._check_block_integrity()


def test_same_cycle_shared_prefix_batch_demotes_once(monkeypatch):
    """Regression (live strict-mode verification): within ONE prefill
    batch, request A's allocation can evict a cached block whose hash
    request B's allocation then re-registers; the demote drain must skip
    hashes that became HBM-resolvable again or the hash lands in two
    tiers."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    shared = SHARED
    cold_twice(eng, [shared + [30]], PARAMS)
    # a BATCH of same-prefix requests admitted together: the first
    # allocation may evict, the second re-registers the same hashes
    for r in range(3):
        rids = [eng.add_request(prompt_token_ids=shared + [60 + r, i],
                                params=PARAMS) for i in range(3)]
        while eng.has_work():
            eng.step()                      # strict-checked every cycle
        for rid in rids:
            eng.requests.pop(rid, None)
        _churn(eng)
    eng._check_block_integrity()


def test_restore_aborted_request_still_commits(monkeypatch):
    """A request aborted mid-RESTORING must not strand restore-in-flight
    blocks: the commit publishes them to the cached pool regardless."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    cold_twice(eng, [SHARED + [30]], PARAMS)
    assert len(eng._kv_tiers) > 0
    rid = eng.add_request(prompt_token_ids=SHARED + [88], params=PARAMS)
    eng.step()                     # begins the restore, holds admission
    from tpuserve.runtime.request import RequestState
    req = eng.requests[rid]
    if req.state == RequestState.RESTORING:
        assert eng.abort_request(rid)
        while eng.has_work():
            eng.step()
        assert eng.block_manager.num_restoring_blocks == 0
        eng._check_block_integrity()


def test_int8_pages_demote_smaller_at_real_head_widths():
    """Pages demote in their stored dtype.  At the head widths models ship
    with (8 kv heads of 128) an int8 page — values plus its lane-padded f32
    scale rows (ops/attention.py pad_scale_lanes) — is 0.75x a bf16 one;
    the tiny test models' 16-wide heads would be all padding."""
    import dataclasses

    from tpuserve.models.config import get_model_config
    wide = dataclasses.replace(get_model_config("tiny-qwen3"), num_heads=8,
                               num_kv_heads=8, head_dim=128)

    def engine(dtype):
        return Engine(EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=24,
                              max_blocks_per_seq=16, dtype=dtype),
            scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                      min_prefill_bucket=8,
                                      min_decode_bucket=2),
            enable_prefix_caching=True, kv_tiers=True), model_cfg=wide)

    from tpuserve.runtime.kv_tiers import pages_nbytes
    nbytes = {}
    for dtype in ("int8", "bfloat16"):
        e = engine(dtype)
        cold_twice(e, [SHARED + [30]], PARAMS)
        assert e._kv_tiers.host_count > 0
        nbytes[dtype] = pages_nbytes(
            next(iter(e._kv_tiers._host.values()))[0])
    assert nbytes["int8"] == 0.75 * nbytes["bfloat16"]


# ---------------------------------------------------------------------------
# demotions in flight: the copy runs behind the chip's work
# ---------------------------------------------------------------------------

PAGE_DTYPES = ("bfloat16", "int8")


def _dtype_engine(dtype, tp=1):
    """A tiered engine whose cache pages hold a prompt's KV in ``dtype``
    (int8 pages carry their ``ks``/``vs`` scale pages along); under
    ``tp`` the pages are sharded over the kv-head axis."""
    from tpuserve.parallel import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(dp=1, tp=tp)) if tp > 1 else None
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=24, max_blocks_per_seq=16,
                          dtype=dtype),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        enable_prefix_caching=True, kv_tiers=True), mesh=mesh)
    eng.generate([SHARED + [30]], PARAMS)
    return eng


class _Gate:
    """A ``fetch`` for ``put_async`` that holds the copy back until it is
    opened: the batch stays in flight for as long as the test wants."""

    def __init__(self, gathered):
        import threading
        self.gathered = gathered
        self.open = threading.Event()

    def __call__(self):
        from tpuserve.runtime.kv_cache import fetch_block_pages
        assert self.open.wait(30), "the gate was never opened"
        return fetch_block_pages(self.gathered)

    def open_soon(self, seconds=0.05):
        import threading
        threading.Timer(seconds, self.open.set).start()


def _start(eng, blocks, hashes, opened=False):
    """Demote ``blocks`` under ``hashes`` behind a gate ("a byte a block")."""
    from tpuserve.runtime.kv_cache import enqueue_block_pages_gather
    gate = _Gate(enqueue_block_pages_gather(eng.kv_cache, blocks))
    if opened:
        gate.open.set()
    eng._kv_tiers.put_async(hashes, gate, nbytes=len(blocks))
    return gate


def _same_pages(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert set(g) == set(w)
        for k in w:
            assert g[k].dtype == w[k].dtype and g[k].shape == w[k].shape
            np.testing.assert_array_equal(np.asarray(g[k], np.float32),
                                          np.asarray(w[k], np.float32))


@pytest.mark.parametrize("dtype,tp", [(d, 1) for d in PAGE_DTYPES]
                         + [("bfloat16", 2)])
def test_in_flight_hash_is_resolvable_and_takes_the_gathered_pages(dtype, tp):
    from tpuserve.runtime.kv_cache import gather_block_pages
    eng = _dtype_engine(dtype, tp)
    store, blocks, hashes = eng._kv_tiers, [1, 2, 3], [901, 902, 903]
    want = gather_block_pages(eng.kv_cache, blocks)
    # a landed page is the whole block, whatever the device sharding
    assert want[0][0]["k"].shape == eng.kv_cache[0]["k"].shape[1:]
    assert eng._kv_block_bytes * tp == sum(
        a.nbytes for layer in want[0] for a in layer.values())
    assert any(np.asarray(a, np.float32).any() for a in want[0][0].values())
    if dtype == "int8":
        assert {"ks", "vs"} <= set(want[0][0])
    gate = _start(eng, blocks, hashes)
    for h in hashes:
        assert store.has(h) and store.where(h) == "host"
    assert set(hashes) <= set(store.hashes())
    assert len(store) == store.in_flight_count == 3
    assert store.host_count == 0 and store.host_bytes_used == 0
    gate.open_soon()
    _same_pages(store.take(902), want[1])      # waits for that one copy
    assert store.waited_blocks == 1 and not store.has(902)
    assert store.in_flight_count == 2
    store.land()                               # the copy is done: no wait
    assert store.in_flight_batches == 0 and store.host_count == 2
    assert store.waited_blocks == 1
    _same_pages(store.take(901), want[0])
    _same_pages(store.take(903), want[2])
    assert len(store) == 0 and store.host_bytes_used == 0


@pytest.mark.parametrize("dtype", PAGE_DTYPES)
def test_dropped_in_flight_hash_is_never_filed(dtype):
    eng = _dtype_engine(dtype)
    store = eng._kv_tiers
    store.put(900, _pages())
    used, dropped = store.host_bytes_used, store.dropped_blocks
    gate = _start(eng, [1, 2], [901, 902])
    store.drop(901)             # superseded while its copy is in flight
    store.drop(902)
    assert not store.has(901) and len(store) == 1
    gate.open.set()
    store.land(wait=True)
    assert store.in_flight_batches == 0
    assert not store.has(901) and not store.has(902)
    assert store.host_count == 1 and store.host_bytes_used == used
    assert store.dropped_blocks == dropped     # superseded is not lost


@pytest.mark.parametrize("dtype", PAGE_DTYPES)
def test_landed_pages_are_the_kv_from_before_the_next_dispatch(dtype):
    """The gather reads the evicted pages in device order: a dispatch
    enqueued after it (here a scatter that donates the cache and writes
    other pages over the same blocks) cannot change what lands."""
    from tpuserve.runtime.kv_cache import (gather_block_pages,
                                           scatter_block_pages)
    eng = _dtype_engine(dtype)
    store, blocks = eng._kv_tiers, [1, 2]
    before = gather_block_pages(eng.kv_cache, blocks)
    other = gather_block_pages(eng.kv_cache, [0, 0])    # block 0: no KV
    gate = _start(eng, blocks, [901, 902])
    eng.kv_cache = scatter_block_pages(eng.kv_cache, blocks, other)
    now = gather_block_pages(eng.kv_cache, blocks)
    _same_pages(now[0], other[0])               # the overwrite happened
    assert any((np.asarray(now[0][0][k], np.float32)
                != np.asarray(before[0][0][k], np.float32)).any()
               for k in before[0][0])
    gate.open.set()
    store.land(wait=True)
    _same_pages(store.take(901), before[0])
    _same_pages(store.take(902), before[1])


def test_third_batch_waits_for_the_oldest_and_is_counted():
    from tpuserve.runtime.kv_tiers import MAX_IN_FLIGHT
    eng = _dtype_engine("bfloat16")
    store = eng._kv_tiers
    assert MAX_IN_FLIGHT == 2
    first = _start(eng, [1, 2, 3], [901, 902, 903])
    store.reserve(1)                    # a second batch: nobody waits
    second = _start(eng, [4], [904])
    assert store.in_flight_batches == 2 and store.waited_blocks == 0
    second.open.set()
    first.open_soon()
    store.reserve(1)                    # a third batch: the oldest lands
    assert store.waited_blocks == 3
    assert store.host_count == 3 and store.where(904) == "host"
    # the device budget bounds the bytes in flight the same way
    store.land(wait=True)
    store.device_budget_bytes = 4
    third = _start(eng, [5, 6, 7], [905, 906, 907])      # "3 bytes"
    third.open_soon()
    store.reserve(2)                    # 3 + 2 > 4: it gives way
    assert store.in_flight_batches == 0 and store.waited_blocks == 6
    # and a batch the budget cannot hold at all is copied out at once
    _start(eng, [8, 9, 10, 11, 12], [908 + i for i in range(5)], opened=True)
    assert store.in_flight_batches == 0 and store.host_count == 12


def test_a_plain_run_waits_for_no_copy(monkeypatch):
    """Evictions cycle after cycle, no prompt coming back while its blocks
    are in the tier (so no restore takes a hash whose copy is still
    running; the rounds run twice, since a first eviction is declined):
    every copy is filed behind a later dispatch or when the loop goes
    idle, and none is waited for."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    eng.generate([SHARED + [30 + i] for i in range(2)], PARAMS)
    for r in 2 * list(range(3)):
        eng.generate([[100 + 10 * r + i] * 40 for i in range(3)], PARAMS)
    assert eng.stats.kv_demoted_blocks > 8 and eng.stats.kv_restores == 0
    assert eng.stats.kv_demote_waited_blocks == 0
    # no work left means nothing in flight: every demoted block is filed
    assert not eng.has_work() and eng._kv_tiers.in_flight_batches == 0


def test_a_batch_the_device_cannot_hold_is_copied_out_before_the_dispatch(
        monkeypatch):
    """The device budget is what no dispatch has touched (limit - peak,
    read once); a gathered batch over it must not ride through the next
    dispatch: the loop waits for its copy first, as it always used to."""
    import jax
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    store = eng._kv_tiers
    assert store.device_budget_bytes == float("inf")    # CPU: no statistics

    class Device:
        def memory_stats(self):
            return {"bytes_limit": 1000, "peak_bytes_in_use": 990,
                    "bytes_in_use": 500}
    monkeypatch.setattr(jax, "local_devices", lambda: [Device()])
    eng._read_demote_budget()
    assert store.device_budget_bytes == 10 < eng._kv_block_bytes
    left = []
    demote = eng._demote_evicted
    monkeypatch.setattr(eng, "_demote_evicted", lambda: (
        demote(), left.append(store.in_flight_batches))[0])
    cold_twice(eng, [SHARED + [30 + i] for i in range(2)], PARAMS)
    assert eng.stats.kv_demoted_blocks > 0 and set(left) == {0}
    tiered = eng.generate([SHARED + [PROBE]], PARAMS)[0]
    cold = _mk_engine(False).generate([SHARED + [PROBE]], PARAMS)[0]
    assert tiered.output_token_ids == cold.output_token_ids


def test_no_wait_between_the_gather_and_the_cycles_dispatch(monkeypatch):
    """Between ``_gather_pages`` and what the cycle dispatches next (its
    ``_exec_*``; for a restore, its scatter) the loop opens no ``sync.*``
    span and calls no ``device_get``: the demotion is dispatch-only."""
    import threading

    import jax

    from tpuserve.runtime import kv_cache
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    loop, log = threading.get_ident(), []

    def spy(obj, name, tag):
        real = getattr(obj, name)

        def wrapped(*a, **k):
            if threading.get_ident() == loop:
                log.append(tag if isinstance(tag, str) else tag(*a))
            return real(*a, **k)
        monkeypatch.setattr(obj, name, wrapped)

    spy(kv_cache, "_gather_pages", "gather")
    spy(kv_cache, "_scatter_pages", "dispatch")
    spy(jax, "device_get", "device_get")
    spy(eng.devprof, "sync", lambda kind: "sync." + kind)
    for name in dir(eng):
        if name.startswith("_exec_"):
            spy(eng, name, "dispatch")
    cold_twice(eng, [SHARED + [30 + i] for i in range(2)], PARAMS)
    eng.generate([SHARED + [PROBE]], PARAMS)       # restores, and demotes
    _churn(eng)
    after = [log[i + 1] for i, tag in enumerate(log[:-1]) if tag == "gather"]
    assert len(after) > 4 and eng.stats.kv_restores >= 1
    assert set(after) == {"dispatch"}, after


@pytest.mark.parametrize("copy", ["lands", "fails"])
def test_loop_shutdown_files_or_counts_what_is_in_flight(copy):
    """The engine loop's last act: a demotion whose copy is still running
    is filed, one whose copy failed is counted as dropped; neither is
    lost without a trace."""
    from tpuserve.server.runner import AsyncEngineRunner
    eng = _dtype_engine("bfloat16")
    store = eng._kv_tiers
    runner = AsyncEngineRunner(eng)
    if copy == "fails":
        def broken():
            raise RuntimeError("the copy failed")
        store.put_async([901, 902, 903], broken, nbytes=3)
    else:
        _start(eng, [1, 2, 3], [901, 902, 903]).open_soon()
    dropped = store.dropped_blocks
    runner._stop.set()
    runner._loop()                      # no cycle runs; the loop winds down
    assert store.in_flight_batches == 0
    if copy == "lands":
        assert store.host_count == 3 and store.dropped_blocks == dropped
    else:
        assert len(store) == 0 and store.dropped_blocks == dropped + 3
    assert store.waited_blocks == 0     # nothing was waiting behind it


def test_store_clear_counts_what_was_in_flight():
    eng = _dtype_engine("bfloat16")
    store = eng._kv_tiers
    gate = _start(eng, [1, 2], [901, 902])
    store.clear()
    gate.open.set()
    assert len(store) == 0 and store.in_flight_batches == 0
    assert store.dropped_blocks == 2


def test_demotion_runs_the_executables_the_blocking_gather_warms(monkeypatch):
    """The benchmark (and ``Engine.warmup``) warm the demotion's ladder by
    calling ``gather_block_pages(kv_cache, [0] * n)``: the engine's
    dispatch-only demotion must hit those very executables (the same
    jitted function at the same padded shapes), or the gather compiles
    inside a measured window."""
    from tpuserve.runtime import kv_cache
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    shapes = []
    real = kv_cache._gather_pages
    monkeypatch.setattr(
        kv_cache, "_gather_pages",
        lambda cache, idx: (shapes.append(int(idx.shape[0])),
                            real(cache, idx))[1])
    for n in (1, 2, 4, 8, 16, 32):
        kv_cache.gather_block_pages(eng.kv_cache, [0] * n)
    warmed, compiled = set(shapes), real._cache_size()
    del shapes[:]
    cold_twice(eng, [SHARED + [30 + i] for i in range(2)], PARAMS)
    assert eng.stats.kv_demoted_blocks > 0 and shapes
    assert set(shapes) <= warmed
    assert real._cache_size() == compiled, "a demotion compiled a new gather"


# ---------------------------------------------------------------------------
# cache-aware routing digest
# ---------------------------------------------------------------------------

def test_digest_tracker_roundtrip():
    from tpuserve.server.kv_digest import (PrefixDigestTracker, affinity_key,
                                           digest_has)
    tr = PrefixDigestTracker(capacity=8)
    key = affinity_key({"prompt": "shared system prompt | user 1"})
    assert key is not None
    tr.note(key)
    d = tr.digest_hex()
    assert digest_has(d, tr.bits, key)
    other = affinity_key({"prompt": "a completely different conversation"})
    assert not digest_has(d, tr.bits, other)
    # LRU bound: old keys age out of the window
    for i in range(20):
        tr.note(affinity_key({"prompt": f"filler {i}"}))
    assert len(tr) == 8
    assert not digest_has(tr.digest_hex(), tr.bits, key)
    # bloom width scales with the window (a tiered replica's thousands
    # of keys must not saturate a fixed 1024-bit digest) — and existing
    # membership survives the re-bitting
    tr.note(key)
    tr.resize(4096)
    assert tr.bits >= 8 * 4096
    assert digest_has(tr.digest_hex(), tr.bits, key)


def test_affinity_key_matches_gateway_derivation():
    """The gateway hashes the raw body; the server hashes the parsed one
    — both must land on the same key or the digest never matches."""
    import json
    from tpuserve.server.gateway import Gateway
    from tpuserve.server.kv_digest import affinity_key
    gw = Gateway(["http://stub"])
    body = {"prompt": "p" * 500, "max_tokens": 4}
    assert gw._prefix_key(json.dumps(body).encode()) == affinity_key(body)
    chat = {"messages": [{"role": "user", "content": "hi"}]}
    assert gw._prefix_key(json.dumps(chat).encode()) == affinity_key(chat)


def test_gateway_prefers_digest_hit_backend():
    import json
    from tpuserve.server.gateway import Gateway
    from tpuserve.server.kv_digest import (DIGEST_BITS, digest_bit)
    gw = Gateway(["http://b1", "http://b2", "http://b3"])
    body = json.dumps({"prompt": "conversation under test"}).encode()
    key = gw._prefix_key(body)
    ring = gw._rendezvous_target(key, gw.backends)
    # advertise the prefix on a NON-ring backend: the digest must win
    holder = next(b for b in gw.backends if b is not ring)
    holder.kv_digest = format(1 << digest_bit(key), f"0{DIGEST_BITS // 4}x")
    holder.kv_digest_bits = DIGEST_BITS
    chosen = gw.pick_backend(body)
    assert chosen is holder
    gw.release(chosen, ok=True)
    # no digest anywhere: plain rendezvous ring, deterministically
    holder.kv_digest = ""
    chosen = gw.pick_backend(body)
    assert chosen is ring
    gw.release(chosen, ok=True)


def test_healthz_advertises_digest_and_tiers():
    import json
    import urllib.request
    from tpuserve.server.openai_api import OpenAIServer, ServerConfig
    eng = _mk_engine(True)
    srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
    port = srv.start()
    url = f"http://127.0.0.1:{port}"
    try:
        req = urllib.request.Request(
            url + "/v1/completions",
            data=json.dumps({"prompt": "digest me", "max_tokens": 2,
                             "ignore_eos": True}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            info = json.loads(r.read())
        assert info["status"] == "ok"
        assert set(info["kv_tier_blocks"]) == {"hbm", "host", "spill"}
        assert int(info["kv_digest"], 16) != 0
        from tpuserve.server.kv_digest import affinity_key, digest_has
        assert digest_has(info["kv_digest"], info["kv_digest_bits"],
                          affinity_key({"prompt": "digest me"}))
    finally:
        srv.shutdown()
