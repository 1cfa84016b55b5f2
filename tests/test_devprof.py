"""Device telemetry (runtime/devprof.py): per-dispatch attribution,
executable-ladder registry, HBM watermark reconciliation and profiler
capture.

One module-scoped server/engine serves every HTTP test (the tier-1
wall budget is tight — no per-test engine builds); the module arms
TPUSERVE_STRICT_BLOCKS so the block-manager view the HBM watermark
reconciles against is itself cross-checked every cycle."""

import json
import os
import time
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
    # what each first dispatch was (PR 57): the compile ledger's stages
    # inside its bracket, zeros and "none" where no ledger listens
    for r in rows:
        assert r["cache"] in ("hit", "miss", "none")
        assert 0 <= r["trace_ms"] + r["lower_ms"] + r["backend_ms"] \
            <= r["compile_ms"] + 1.0
    assert snap["cache_hits"] + snap["cache_misses"] <= snap["compiles"]
    assert set(snap["unbracketed"]) == {
        "requests", "cache_hits", "cache_misses", "trace_ms", "lower_ms",
        "backend_ms"}


TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"
ANSWERS = {"hit": "/jax/compilation_cache/cache_hits",
           "miss": "/jax/compilation_cache/cache_misses"}


def _ready_a_program(led, answer, backend_s=0.25, small_jits=0):
    """JAX's events for one program, as jax 0.9 sends them: a trunk's
    trace with a layer body's inside it, the lowering, the backend's part
    with the cache's answer.  ``small_jits``: the ``jnp`` functions a
    trunk's trace goes through, each a jit that reports a trace of its
    own (31,709 in the Ling cell's start, PERF.md §6)."""
    led._on_open(TRACE, 0.0, fun_name="trunk")
    led._on_open(TRACE, 0.0, fun_name="layer")
    for _ in range(small_jits):                        # jnp's own jits
        led._on_open(TRACE, 0.0, fun_name="add")
        led._on_duration(TRACE, 0.0, fun_name="add")
    led._on_duration(TRACE, 0.25, fun_name="layer")
    led._on_duration(TRACE, 1.0, fun_name="trunk")     # 0.75 its own
    led._on_open(LOWER, 0.0, fun_name="trunk")
    led._on_duration(LOWER, 0.5, fun_name="trunk")
    led._on_open(BACKEND, 0.0, fun_name="trunk")
    if answer != "none":
        led._on_event(ASKED)
        led._on_event(ANSWERS[answer])
    if answer == "hit":
        led._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                         0.125)
    led._on_duration(BACKEND, backend_s, fun_name="trunk")


@pytest.mark.parametrize("answer", ["hit", "miss", "none"])
def test_a_first_dispatch_takes_the_ledgers_events_of_its_bracket(
        monkeypatch, answer):
    """The ladder row of a first dispatch holds the ledger's events that
    fell inside its bracket, a nested trace counted once; what came
    before any bracket stays ``unbracketed``; a later dispatch of the same
    executable never asks the ledger."""
    from tpuserve.runtime import devprof as devprof_mod
    from tpuserve.utils.compile_cache import CompileLedger
    led = CompileLedger()
    monkeypatch.setattr(devprof_mod, "LEDGER", led)
    _ready_a_program(led, "miss", backend_s=2.0)       # an initialiser
    dp = devprof_mod.DeviceProfiler()
    with dp.dispatch("decode", ((4,),)):
        # (an event is stamped where it ENDS, and a real one lasts longer
        # than the bracket's own close)
        time.sleep(0.002)
        _ready_a_program(led, answer, small_jits=2000)
    for _ in range(3):
        with dp.dispatch("decode", ((4,),)):
            pass
    assert led.lookups == 1
    snap = dp.ladder_snapshot()
    (row,) = snap["executables"]
    assert (row["trace_ms"], row["lower_ms"], row["backend_ms"],
            row["cache"], row["hits"]) == (1000.0, 500.0, 250.0, answer, 4)
    assert (snap["cache_hits"], snap["cache_misses"]) == (
        int(answer == "hit"), int(answer == "miss"))
    assert snap["unbracketed"] == {
        "requests": 1, "cache_hits": 0, "cache_misses": 1,
        "trace_ms": 1000.0, "lower_ms": 500.0, "backend_ms": 2000.0}
    totals = led.totals()
    # the bracket's program is ONE kept record a stage, however many
    # traces closed inside its trunk's: none fell off the kept records
    assert (totals["traces"], totals["lowers"], totals["requests"]) \
        == (2004, 2, 2)
    assert totals["trace_s"] == 2.0 and totals["backend_s"] == 2.25
    assert totals["cache_read_s"] == (0.125 if answer == "hit" else 0)


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
    # ``misses`` are first dispatches; these say how many were compiles
    assert 0 <= lad["cache_hits"] + lad["cache_misses"] <= lad["misses"]
    assert snap["startup"]["cold_start_s"] == snap["cold_start_s"] > 0


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
