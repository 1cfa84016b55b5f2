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

def _mk_engine(tiers, **kw):
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=24, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        enable_prefix_caching=True, kv_tiers=tiers, **kw)
    return Engine(cfg)


SHARED = list(range(2, 26))      # 24 tokens = 6 full blocks at block_size 4
PARAMS = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)


def _churn(eng):
    """Unrelated prompts that exhaust the pool and evict the shared
    prefix out of HBM."""
    eng.generate([[100 + i] * 40 for i in range(3)], PARAMS)


def test_demote_restore_token_identity(monkeypatch):
    """THE acceptance pin: after the shared prefix is evicted, demoted,
    and restored from the host tier, a request over it produces exactly
    the tokens a cold engine computes — with strict block+tier integrity
    checked every cycle."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True)
    assert eng._kv_tiers is not None
    eng.generate([SHARED + [30 + i] for i in range(2)], PARAMS)
    _churn(eng)
    assert eng.stats.kv_demoted_blocks > 0
    assert len(eng._kv_tiers) > 0
    tiered = eng.generate([SHARED + [77]], PARAMS)[0]
    assert eng.stats.kv_restores >= 1
    assert eng.stats.kv_restored_blocks > 0
    cold = _mk_engine(False).generate([SHARED + [77]], PARAMS)[0]
    assert tiered.output_token_ids == cold.output_token_ids


def test_spill_tier_restore_token_identity(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _mk_engine(True, kv_host_bytes=3000, kv_spill_dir=str(tmp_path))
    eng.generate([SHARED + [30]], PARAMS)
    _churn(eng)
    assert eng.stats.kv_spilled_blocks > 0
    tiered = eng.generate([SHARED + [77]], PARAMS)[0]
    cold = _mk_engine(False).generate([SHARED + [77]], PARAMS)[0]
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
    out = eng.generate([SHARED + [77]], PARAMS)[0]
    cold = _mk_engine(False).generate([SHARED + [77]], PARAMS)[0]
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
    eng.generate([SHARED + [30]], PARAMS)
    _churn(eng)
    store = eng._kv_tiers
    assert len(store) >= 3
    # punch a gap: drop a MIDDLE entry of the shared chain from the store
    chain = eng.block_manager.prefix_chain(SHARED + [77])
    resolvable = [h for h in chain if store.has(h)]
    assert len(resolvable) >= 3
    store.drop(resolvable[1])
    tiered = eng.generate([SHARED + [77]], PARAMS)[0]   # strict-checked
    # every chain hash left the store (restored span taken, gap tail
    # superseded by the recompute)
    assert not any(store.has(h) for h in chain)
    cold = _mk_engine(False).generate([SHARED + [77]], PARAMS)[0]
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
    eng.generate([exact], PARAMS)           # registers all 6 block hashes
    _churn(eng)                             # demotes them
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
    eng.generate([shared + [30]], PARAMS)
    _churn(eng)
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
    eng.generate([SHARED + [30]], PARAMS)
    _churn(eng)
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
        e.generate([SHARED + [30]], PARAMS)
        _churn(e)
        assert e._kv_tiers.host_count > 0
        nbytes[dtype] = pages_nbytes(
            next(iter(e._kv_tiers._host.values()))[0])
    assert nbytes["int8"] == 0.75 * nbytes["bfloat16"]


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
