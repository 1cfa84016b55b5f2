"""What a start is made of (PR 57): the process's compile ledger
(``utils/compile_cache.py`` ``LEDGER``: JAX's own monitoring events), the
start-up spans (``runtime/hostprof.py`` ``STARTUP``), what devprof's ladder
rows say of an executable's first dispatch, and where an operator reads
them (``/metrics``, ``/debug/engine`` ``startup``).

A fresh interpreter a start, as ``test_compile_cache.py`` has it: the
ledger, the spans and ``jax.config`` are a PROCESS's, and the point is what
a first process and a second one on the same cache directory read.  Both
run once a module; every test reads their reports."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One start of the real server on a tiny model, driven as the benchmark
# drives it (build_server, then the warm-up by hand), then a second engine
# on the (B, L) prefill route, so that all four warm-up families ran.
_START = r"""
import json, sys, time, urllib.request
from tpuserve.utils import compile_cache
compile_cache.configure()
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SchedulerConfig
from tpuserve.runtime.hostprof import STARTUP
from tpuserve.server.openai_api import build_server

LEDGER = compile_cache.LEDGER
server, _ = build_server([
    "--model", "tiny-qwen3", "--num-blocks", "64", "--block-size", "4",
    "--max-blocks-per-seq", "16", "--max-num-seqs", "4", "--multi-step", "4",
    "--no-warmup", "--host", "127.0.0.1", "--port", "0"])
url = "http://127.0.0.1:%d" % server.start(warmup=False)
engine = server.engine
engine.warmup(sample_modes=("greedy",), prefill_buckets=[8],
              decode_buckets=[2], chunk_buckets=[16])
once = dict(STARTUP.seconds)
engine.warmup(sample_modes=("greedy",), prefill_buckets=[8],
              decode_buckets=[2], chunk_buckets=[16])
twice = dict(STARTUP.seconds)
other = Engine(EngineConfig(
    model="tiny-qwen3",
    cache=CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8,
                      dtype="float32"),
    scheduler=SchedulerConfig(max_num_seqs=2, min_prefill_bucket=8,
                              min_decode_bucket=2), multi_step=1, seed=0))
other.warmup(sample_modes=("greedy",), prefill_buckets=[8],
             decode_buckets=[2])


def get(path):
    with urllib.request.urlopen(url + path, timeout=120) as r:
        return r.read().decode()


def page():
    out = {}
    for line in get("/metrics").splitlines():
        if line and line[0] != "#" and "_created" not in line:
            head, _, value = line.rpartition(" ")
            name = head.split("{", 1)[0]
            out.setdefault(name, []).append(float(value))
    return out


def serve(**extra):
    body = {"prompt": "start", "max_tokens": 6, "temperature": 0,
            "ignore_eos": True, **extra}
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    urllib.request.urlopen(req, timeout=120).read()


def settled():
    time.sleep(0.3)          # an idle tick of the loop exports the ledger
    return page(), json.loads(get("/debug/engine"))


before_page, before = settled()
serve()
first_page, first = settled()
lookups, ladder = LEDGER.lookups, len(engine.devprof.ladder)
serve()                                     # the same shapes again
again = (LEDGER.lookups - lookups, len(engine.devprof.ladder) - ladder)
serve(temperature=0.7, top_p=0.9, seed=3)   # a sampler nobody warmed
later_page, later = settled()
print("REPORT " + json.dumps({
    "once": once, "twice": twice, "before": before["startup"],
    "first": first["startup"], "later": later["startup"],
    "first_page": first_page, "later_page": later_page,
    "ladder": later["devprof"]["ladder"],
    "other_ladder": other.devprof.ladder_snapshot(),
    "caches": later["compile_caches"]["ladder"],
    "again": again, "totals": LEDGER.totals(),
    "lookups": LEDGER.lookups,
    "first_dispatches": len(engine.devprof.ladder)
    + len(other.devprof.ladder)}))
server.shutdown()
"""

SPANS = ("startup.build", "startup.backend", "startup.weights",
         "startup.pools", "startup.warmup", "startup.warmup.prefill",
         "startup.warmup.decode", "startup.warmup.chunk",
         "startup.warmup.ragged")
SERIES = ("tpuserve_jit_trace_seconds_total",
          "tpuserve_jit_lower_seconds_total",
          "tpuserve_backend_compile_seconds_total",
          "tpuserve_compile_cache_read_seconds_total",
          "tpuserve_compile_requests_total",
          "tpuserve_compile_cache_hits_total",
          "tpuserve_compile_cache_misses_total",
          "tpuserve_startup_build_seconds",
          "tpuserve_startup_warmup_seconds")


def _start(cache_dir: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    out = subprocess.run([sys.executable, "-c", _START], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


@pytest.fixture(scope="module")
def starts(tmp_path_factory):
    """A first process on an empty cache directory, then a second on the
    same one."""
    cache_dir = str(tmp_path_factory.mktemp("startup-cache"))
    return {"first": _start(cache_dir), "second": _start(cache_dir)}


@pytest.mark.parametrize("span", SPANS)
def test_every_span_of_the_table_has_seconds(starts, span):
    phases = starts["first"]["later"]["phases"]
    assert phases[span] > 0.0, phases


def test_the_spans_nest_as_the_table_says(starts):
    """The families' spans sum to no more than the warm-up's, the build's
    three parts to no more than the build's; a second ``warmup`` call adds
    to the same keys."""
    for run in starts.values():
        once, twice = run["once"], run["twice"]
        for held in (once, twice, run["later"]["phases"]):
            families = sum(v for k, v in held.items()
                           if k.startswith("startup.warmup."))
            assert 0 < families <= held["startup.warmup"]
        assert (once["startup.backend"] + once["startup.weights"]
                + once["startup.pools"]) <= once["startup.build"]
        assert twice["startup.warmup"] > once["startup.warmup"]
        assert twice["startup.warmup.decode"] > once["startup.warmup.decode"]
        assert twice["startup.build"] == once["startup.build"]


def test_the_ledger_counts_every_request_once(starts):
    """``requests`` (the backend events) = hits + misses + what did not ask
    the cache (with the cache's floors at zero nothing asked is declined);
    the stages are self times, so they sum to no more than the process
    lived."""
    for which, run in starts.items():
        t = run["totals"]
        assert t["requests"] == t["lowers"] > 0
        assert t["asked"] == t["hits"] + t["misses"]
        assert t["requests"] == t["hits"] + t["misses"] \
            + (t["requests"] - t["asked"])
        assert t["traces"] >= t["requests"]
        assert 0 < t["cache_read_s"] <= t["backend_s"] \
            or which == "first"
        spent = t["trace_s"] + t["lower_s"] + t["backend_s"]
        assert 0 < spent < run["later"]["cold_start_s"] + 60.0


def test_a_first_process_compiles_and_a_second_reads(starts):
    first, second = starts["first"]["totals"], starts["second"]["totals"]
    assert first["hits"] == 0 and first["misses"] == first["asked"] > 0
    assert second["misses"] == 0 and second["hits"] == second["asked"] > 0
    assert second["requests"] == first["requests"]
    assert first["cache_read_s"] == 0 < second["cache_read_s"]


@pytest.mark.parametrize("which, word", [("first", "miss"),
                                         ("second", "hit")])
def test_a_ladder_row_says_what_its_first_dispatch_was(starts, which, word):
    """Every row of both engines' ladders: the three stages' ms inside the
    bracket, no more than its wall, and the cache's answer (``none`` where
    nothing was asked); the totals beside ``misses`` say how many first
    dispatches were compiles."""
    run = starts[which]
    for ladder in (run["ladder"], run["other_ladder"]):
        rows = ladder["executables"]
        # the second engine's sampler was readied by the first, in this
        # process: a first dispatch that asked nobody for anything
        asked = [r for r in rows if r["cache"] != "none"]
        assert asked and all(r["cache"] == word for r in asked), rows
        if ladder is run["ladder"]:
            assert asked == rows
        for r in rows:
            stages = r["trace_ms"] + r["lower_ms"] + r["backend_ms"]
            assert stages <= r["compile_ms"] + 1.0
            if r in asked:
                assert min(r["trace_ms"], r["lower_ms"],
                           r["backend_ms"]) > 0
            else:
                assert stages == 0
        assert ladder["cache_misses" if word == "miss"
                      else "cache_hits"] == len(asked)
    caches = run["caches"]
    assert caches["misses"] == caches["size"] == len(
        run["ladder"]["executables"])
    assert caches["cache_misses" if word == "miss" else "cache_hits"] \
        == caches["misses"]
    assert caches["cache_hits" if word == "miss" else "cache_misses"] == 0


def test_unbracketed_holds_what_no_dispatch_saw(starts):
    """The weights' initialisers, the pools' fills, the token selects: the
    ledger saw them, no bracket did; brackets and the row sum to the
    process's totals."""
    run = starts["first"]
    loose = run["ladder"]["unbracketed"]
    assert 0 < loose["requests"] < run["totals"]["requests"]
    assert loose["cache_misses"] == loose["requests"]
    assert loose["backend_ms"] > 0
    bracketed = sum(r["backend_ms"] for ladder in (run["ladder"],
                                                   run["other_ladder"])
                    for r in ladder["executables"])
    assert bracketed + loose["backend_ms"] == pytest.approx(
        run["totals"]["backend_s"] * 1e3, abs=1.0)


def test_only_a_first_dispatch_asks_the_ledger(starts):
    """The engine loop's steady state gains no call: the ledger's lookup
    ran once an executable, and serving the same shapes again ran none."""
    for run in starts.values():
        assert run["again"] == [0, 0]
        assert run["lookups"] == run["first_dispatches"] > 0


def test_debug_engine_startup_stands_at_the_first_token(starts):
    """``startup.compile`` moves until the first served token and is what
    it was there ever after, while the ``_total`` series go on counting
    (the unwarmed sampler's compile); ``cold_start_s`` beside it."""
    run = starts["first"]
    before, first, later = run["before"], run["first"], run["later"]
    assert before["cold_start_s"] is None
    assert first["cold_start_s"] == later["cold_start_s"] > 0
    assert set(first) == {"cold_start_s", "phases", "compile"}
    assert first["compile"] == later["compile"]
    assert first["compile"]["requests"] >= before["compile"]["requests"]
    assert later["phases"] == first["phases"]
    moved = run["later_page"]["tpuserve_compile_requests_total"][0] \
        - run["first_page"]["tpuserve_compile_requests_total"][0]
    assert moved > 0
    assert run["later_page"]["tpuserve_compile_requests_total"][0] \
        == run["totals"]["requests"] > later["compile"]["requests"]


@pytest.mark.parametrize("series", SERIES)
def test_a_series_has_one_sample_and_the_ledgers_number(starts, series):
    """One sample each (the harness's scrape sums a name over its label
    sets), and the number the ledger or the span holds."""
    run = starts["second"]
    samples = run["later_page"][series]
    assert len(samples) == 1
    want = {
        "tpuserve_jit_trace_seconds_total": run["totals"]["trace_s"],
        "tpuserve_jit_lower_seconds_total": run["totals"]["lower_s"],
        "tpuserve_backend_compile_seconds_total":
            run["totals"]["backend_s"],
        "tpuserve_compile_cache_read_seconds_total":
            run["totals"]["cache_read_s"],
        "tpuserve_compile_requests_total": run["totals"]["requests"],
        "tpuserve_compile_cache_hits_total": run["totals"]["hits"],
        "tpuserve_compile_cache_misses_total": run["totals"]["misses"],
        "tpuserve_startup_build_seconds":
            run["later"]["phases"]["startup.build"],
        "tpuserve_startup_warmup_seconds":
            run["later"]["phases"]["startup.warmup"]}[series]
    assert samples[0] == pytest.approx(want, abs=1e-5)
