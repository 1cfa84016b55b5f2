"""Device telemetry (runtime/devprof.py): per-dispatch attribution,
executable-ladder registry, HBM watermark reconciliation and profiler
capture.

One module-scoped server/engine serves every HTTP test (the tier-1
wall budget is tight — no per-test engine builds); the module arms
TPUSERVE_STRICT_BLOCKS so the block-manager view the HBM watermark
reconciles against is itself cross-checked every cycle."""

import json
import os
import urllib.error
import urllib.request

import pytest

from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SamplingParams, SchedulerConfig)
from tpuserve.server.openai_api import OpenAIServer, ServerConfig

PARAMS = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    flight_dir = str(tmp_path_factory.mktemp("devprof-flight"))
    old = {k: os.environ.get(k)
           for k in ("TPUSERVE_FLIGHT_DIR", "TPUSERVE_STRICT_BLOCKS")}
    os.environ["TPUSERVE_FLIGHT_DIR"] = flight_dir
    os.environ["TPUSERVE_STRICT_BLOCKS"] = "1"
    try:
        eng = Engine(EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=128,
                              max_blocks_per_seq=16),
            scheduler=SchedulerConfig(max_num_seqs=8, min_prefill_bucket=8,
                                      min_decode_bucket=2),
            multi_step=4, seed=0))
        srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
        port = srv.start()
        yield srv, f"http://127.0.0.1:{port}", flight_dir, eng
        srv.shutdown()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _get(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        return r.status, json.loads(r.read())


def _post(url, data=b""):
    req = urllib.request.Request(url, data=data, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, json.loads(r.read())


def _serve_one(url, prompt="devprof", max_tokens=6):
    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                         "temperature": 0, "ignore_eos": True}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


# ---- attribution + ladder on /debug/engine -----------------------------

def test_step_records_carry_device_attribution(server):
    """ACCEPTANCE: step records decompose into device ms vs host ms per
    dispatch kind — the `dev` field beside hostprof's `phase_ms` — and
    /debug/engine carries the full devprof snapshot."""
    srv, url, _, eng = server
    _serve_one(url)
    status, snap = _get(url + "/debug/engine")
    assert status == 200
    devs = [s["dev"] for s in snap["steps"] if s.get("dev")]
    assert devs, "no step record carries a dev attribution delta"
    # a window step's flush blocked on the device: device_ms is real
    assert any(d.get("device_ms", 0) > 0 for d in devs)
    dp = snap["devprof"]
    assert dp["cycles"] > 0
    assert dp["device_ms_per_cycle"] >= 0
    # per-kind split: the served request prefetched and flushed windows
    assert {"prefill", "decode_multi"} & set(dp["dispatch"])
    assert "window" in dp["device"] or "decode" in dp["device"]
    assert dp["hbm"]["limit_bytes"] > 0


def test_ladder_registry_correctness(server):
    """Every (kind, bucket) executable appears exactly once with ONE
    compile; a warm re-serve of the identical shape bumps hits, never
    compiles."""
    srv, url, _, eng = server
    _serve_one(url)
    dp = eng.devprof
    # one ladder entry per compile, by construction
    assert dp.compiles == len(dp.ladder) > 0
    assert dp.compile_s > 0
    compiles_before = dp.compiles
    hits_before = sum(ent[1] for ent in dp.ladder.values())
    _serve_one(url)                      # identical shapes: warm cache
    assert dp.compiles == compiles_before, \
        "warm re-serve of identical bucket shapes must not compile"
    assert sum(ent[1] for ent in dp.ladder.values()) > hits_before
    snap = dp.ladder_snapshot()
    assert snap["retained"] == len(dp.ladder)
    assert snap["truncated"] == 0
    rows = snap["executables"]
    assert len(rows) == snap["retained"]
    # hottest-first ordering, and every row is a real dispatch kind
    hits = [r["hits"] for r in rows]
    assert hits == sorted(hits, reverse=True)
    kinds = {r["kind"] for r in rows}
    assert kinds <= {"prefill", "prefill_chunk", "decode", "decode_multi",
                     "verify", "verify_sampled", "draft", "mixed", "sample"}
    assert all(r["compile_ms"] > 0 for r in rows)


def test_debug_engine_surfaces_compile_cache_stats(server):
    """Satellite fix: /debug/engine exposes grammar-FSM and
    bucket-ladder compile-cache hit/miss/size (compile churn without
    logs)."""
    srv, url, _, eng = server
    _serve_one(url)
    status, snap = _get(url + "/debug/engine")
    caches = snap["compile_caches"]
    assert set(caches) == {"fsm", "ladder"}
    for k in ("hits", "misses", "disk_hits", "size"):
        assert isinstance(caches["fsm"][k], int)
    lad = caches["ladder"]
    assert lad["misses"] == eng.devprof.compiles > 0
    assert lad["size"] == len(eng.devprof.ladder)
    # prior tests re-served warm shapes: hits outnumber compiles
    assert lad["hits"] > 0
    assert lad["compile_ms"] > 0


# ---- HBM watermark reconciliation --------------------------------------

def test_hbm_watermark_reconciles_block_manager_and_weights(server):
    """The watermark's KV reservation is EXACTLY the paged cache's
    static allocation (num_blocks * block_bytes == the kv tree's
    nbytes), weights are the loaded param bytes, and headroom closes
    the accounting under the detected limit.  TPUSERVE_STRICT_BLOCKS
    is armed module-wide, so the block-manager view being reconciled
    is itself refcount-checked every cycle."""
    import jax
    srv, url, _, eng = server
    hbm = eng.devprof.hbm_snapshot()
    kv_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.kv_cache))
    w_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    assert hbm["kv_reserved_bytes"] == kv_bytes
    assert hbm["num_blocks"] * hbm["block_bytes"] == kv_bytes
    assert hbm["num_blocks"] == eng.config.cache.num_blocks
    assert hbm["weights_bytes"] == w_bytes
    assert hbm["other_bytes"] >= 0
    assert hbm["headroom_bytes"] == (hbm["limit_bytes"] - w_bytes
                                     - kv_bytes - hbm["other_bytes"])
    # the budget is the SAME detector the cache auto-sizer uses
    assert hbm["limit_bytes"] == eng._device_hbm_limit()


# ---- profiler capture ---------------------------------------------------

def test_profile_capture_writes_artifact_referenced_from_bundle(server):
    """ACCEPTANCE: POST /debug/profile lands a TensorBoard-loadable
    trace under TPUSERVE_FLIGHT_DIR and the post-mortem bundle
    references it (devprof.captures)."""
    srv, url, flight_dir, eng = server
    status, out = _post(url + "/debug/profile?seconds=0.2")
    assert status == 200
    assert out["reason"] == "manual" and out["seconds"] == 0.2
    trace_dir = out["trace_dir"]
    assert trace_dir.startswith(flight_dir), \
        "trace must land beside the post-mortem bundles"
    assert os.path.isdir(trace_dir) and os.listdir(trace_dir), \
        "trace dir is empty — jax.profiler wrote nothing"
    assert eng.devprof.captures_total >= 1
    status, bundle = _get(url + "/debug/engine/dump")
    assert status == 200
    caps = bundle["devprof"]["captures"]
    assert any(c["trace_dir"] == trace_dir and c["reason"] == "manual"
               for c in caps)


def test_profile_capture_busy_is_409(server):
    """jax allows ONE trace per process: a capture racing another gets
    a clean 409, not a 500 from deep inside the profiler plugin."""
    from tpuserve.server import tracing
    srv, url, _, _ = server
    assert tracing._capture_lock.acquire(blocking=False)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(url + "/debug/profile?seconds=0.1",
                                       data=b"", method="POST"),
                timeout=60)
        assert ei.value.code == 409
    finally:
        tracing._capture_lock.release()
