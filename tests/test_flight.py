"""Engine flight recorder (runtime/flight.py): per-request lifecycle
timelines over HTTP, post-mortem bundles, monotonic-clock discipline,
and the generated Grafana dashboard golden.

One module-scoped server/engine serves every HTTP test (tier-1 runs
near its wall budget — no per-test engine builds).  The chaos rules are
count-limited and rid-matched, so tests that don't name a matching
request id never trip them."""

import ast
import json
import os
import pathlib
import time
import urllib.error
import urllib.request

import pytest

from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SamplingParams, SchedulerConfig)
from tpuserve.server.openai_api import OpenAIServer, ServerConfig

REPO = pathlib.Path(__file__).resolve().parent.parent

PARAMS = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

# one-shot window-flush fault for rids containing "salv" (forces the
# crash-only salvage path: requeue + token-identical replay), plus a
# one-shot releasable hang for rids containing "hangme" (watchdog trip
# -> post-mortem bundle)
FAULTS = ("window_flush:raise:1.0:count=1:match=salv,"
          "decode_dispatch:hang:1.0:count=1:match=hangme:max_hang_s=60")


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    flight_dir = str(tmp_path_factory.mktemp("flight"))
    old = os.environ.get("TPUSERVE_FLIGHT_DIR")
    os.environ["TPUSERVE_FLIGHT_DIR"] = flight_dir
    try:
        eng = Engine(EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=128,
                              max_blocks_per_seq=16),
            scheduler=SchedulerConfig(max_num_seqs=8, min_prefill_bucket=8,
                                      min_decode_bucket=2),
            multi_step=4, faults=FAULTS, step_watchdog_s=0.5, seed=0))
        srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
        port = srv.start()
        yield srv, f"http://127.0.0.1:{port}", flight_dir
        srv.shutdown()
    finally:
        if old is None:
            os.environ.pop("TPUSERVE_FLIGHT_DIR", None)
        else:
            os.environ["TPUSERVE_FLIGHT_DIR"] = old


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, json.loads(r.read())


def _events_of(timeline):
    return [e["event"] for e in timeline["events"]]


def _assert_ordered(events, sequence):
    """Every name in ``sequence`` occurs, in that relative order."""
    idx = -1
    for name in sequence:
        assert name in events[idx + 1:], (name, events)
        idx = events.index(name, idx + 1)


def test_streamed_request_timeline_over_http(server):
    """ACCEPTANCE: a streamed HTTP request's full lifecycle is readable
    at /debug/requests/{id} with monotonic timestamps."""
    srv, url, _ = server
    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps({"prompt": "flight", "max_tokens": 6,
                         "stream": True, "temperature": 0,
                         "ignore_eos": True}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        raw = r.read().decode()
    assert "[DONE]" in raw
    # the engine rid is internal; /debug/engine lists recent ids
    status, snap = _get(url + "/debug/engine")
    assert status == 200 and snap["requests"]
    rid = snap["requests"][-1]
    status, tl = _get(url + f"/debug/requests/{rid}")
    assert status == 200
    events = _events_of(tl)
    _assert_ordered(events, ["QUEUED", "ADMITTED", "PREFILL", "WINDOW",
                             "FINISHED"])
    ts = [e["t"] for e in tl["events"]]
    assert ts == sorted(ts), "timeline timestamps must be monotonic"
    fin = [e for e in tl["events"] if e["event"] == "FINISHED"][-1]
    assert fin["detail"]["cause"] == "length"
    # step records carry the always-on hostprof phase breakdown
    assert any("phase_ms" in s for s in snap["steps"])
    kinds = {s["kind"] for s in snap["steps"]}
    assert {"prefill", "window"} & kinds


def test_salvaged_request_full_sequence(server):
    """ACCEPTANCE: a request hit by an injected fault shows the full
    QUEUED -> ADMITTED -> PREFILL -> WINDOW -> FAULT -> SALVAGED ->
    replay-PREFILL -> FINISHED sequence at /debug/requests/{id}, and the
    stream still completes token-complete (crash-only salvage)."""
    srv, url, _ = server
    rid, q = srv.runner.submit(prompt_token_ids=[5, 6, 7], params=PARAMS,
                               request_id="salv-1")
    toks = []
    while True:
        item = q.get(timeout=120)
        if item is None:
            break
        assert not isinstance(item, Exception), item
        toks.extend(item.new_token_ids)
    assert len(toks) == PARAMS.max_tokens
    status, tl = _get(url + "/debug/requests/salv-1")
    assert status == 200
    events = _events_of(tl)
    _assert_ordered(events, ["QUEUED", "ADMITTED", "PREFILL", "WINDOW",
                             "FAULT", "SALVAGED", "PREFILL", "FINISHED"])
    # the replay prefill is marked as such (re-prefill of prompt+output)
    replays = [e for e in tl["events"] if e["event"] == "PREFILL"
               and e.get("detail", {}).get("replay")]
    assert replays, events
    ts = [e["t"] for e in tl["events"]]
    assert ts == sorted(ts)


def test_watchdog_trip_writes_postmortem_bundle(server):
    """ACCEPTANCE: a watchdog trip produces a readable post-mortem
    bundle (last N cycles + affected request timelines) and counts it in
    stats (-> tpuserve_flight_postmortems_total)."""
    srv, url, flight_dir = server
    srv.runner.WATCHDOG_WARMUP_STEPS = 0      # past warmup: real threshold
    rid, q = srv.runner.submit(prompt_token_ids=[8, 9, 10], params=PARAMS,
                               request_id="hangme-1")
    while True:
        item = q.get(timeout=120)
        if item is None:
            break
        assert not isinstance(item, Exception), item
    eng = srv.engine
    assert eng.stats.watchdog_trips >= 1
    deadline = time.monotonic() + 10
    bundles = []
    while time.monotonic() < deadline:
        bundles = [f for f in os.listdir(flight_dir)
                   if f.startswith("flight-watchdog_trip")]
        if bundles:
            break
        time.sleep(0.05)
    assert bundles, "watchdog trip wrote no post-mortem bundle"
    with open(os.path.join(flight_dir, sorted(bundles)[0])) as f:
        bundle = json.load(f)
    assert bundle["reason"] == "watchdog_trip"
    assert bundle["steps"], "bundle must carry the last engine cycles"
    assert "hangme-1" in bundle["requests"]
    hung = [e["event"] for e in bundle["requests"]["hangme-1"]]
    assert "QUEUED" in hung and "ADMITTED" in hung
    assert eng.stats.flight_postmortems >= 1
    # /debug/engine points at the bundle
    status, snap = _get(url + "/debug/engine")
    assert snap["postmortems"] >= 1
    assert snap["last_postmortem"] and os.path.exists(
        snap["last_postmortem"])


def test_sli_histograms_and_debug_snapshot(server):
    """Client-observable per-class SLI families are fed (TTFT/e2e at
    minimum) and surface both on /metrics and in /debug/engine."""
    srv, url, _ = server
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        text = r.read().decode()
    for fam in ("tpuserve_ttft_seconds", "tpuserve_e2e_seconds",
                "tpuserve_itl_seconds"):
        assert fam + "_bucket" in text, fam
    assert ('tpuserve_ttft_seconds_count{model_name="tiny-qwen3",'
            'slo_class="standard"}') in text
    # prior tests served requests: the per-class counts are non-zero
    import re
    m = re.search(r'tpuserve_ttft_seconds_count\{[^}]*standard[^}]*\} '
                  r'(\d+\.\d+)', text)
    assert m and float(m.group(1)) > 0
    status, snap = _get(url + "/debug/engine")
    assert snap["sli"].get("standard", {}).get("ttft", {}).get("n", 0) > 0


def test_on_demand_dump_endpoint_is_replay_ready(server):
    """ISSUE 11 satellite: GET /debug/engine/dump exports a replay-ready
    schema-versioned bundle on demand (healthy engine, no watchdog or
    poison event needed), counts in tpuserve_replay_dumps_total, and
    extracts straight into a workload."""
    srv, url, _ = server
    # self-contained: serve one request so the rings are non-empty even
    # when this test runs in isolation (-k / sharding / reordering)
    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps({"prompt": "dumpme", "max_tokens": 2,
                         "temperature": 0, "ignore_eos": True}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        r.read()
    status, bundle = _get(url + "/debug/engine/dump")
    assert status == 200
    assert bundle["schema"] >= 2
    assert "rings" in bundle and "engine" in bundle
    assert bundle["requests"], "the served request's timeline is in it"
    import re
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        text = r.read().decode()
    m = re.search(r"tpuserve_replay_dumps_total\{[^}]*\} (\d+\.\d+)", text)
    assert m and float(m.group(1)) >= 1
    from tpuserve.replay import workload_from_bundle
    wl = workload_from_bundle(bundle)
    assert wl.requests and wl.schema_version >= 1


def test_unknown_request_404(server):
    srv, url, _ = server
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(url + "/debug/requests/nope-404", timeout=30)
    assert ei.value.code == 404


@pytest.fixture(scope="module")
def idle_server():
    """A server that has served nothing yet: the recorder is there from
    construction, not from the first request."""
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=32,
                          max_blocks_per_seq=8),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=2)))
    srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
    port = srv.start()
    yield f"http://127.0.0.1:{port}"
    srv.shutdown()


@pytest.mark.parametrize("endpoint,status", [
    ("/debug/engine", 200),
    ("/debug/engine/dump", 200),
    ("/debug/requests/never-sent", 404),
])
def test_debug_endpoints_always_answer(idle_server, endpoint, status):
    """There is no "recorder disabled" answer left: before the first
    request the snapshot and the dump are whole (empty rings, devprof's
    section, the engine's facts) and only an unknown request id is a
    404."""
    try:
        got, body = _get(idle_server + endpoint)
    except urllib.error.HTTPError as e:
        got, body = e.code, json.loads(e.read())
    assert got == status
    if endpoint == "/debug/engine":
        assert "enabled" not in body and "enabled" not in body["devprof"]
        assert body["requests"] == []
        assert {"steps", "sli", "control", "devprof",
                "compile_caches", "startup"} <= set(body)
        assert "tracked" not in body["compile_caches"]["ladder"]
        # no token served yet: the start-up block is whole all the same
        assert body["startup"]["cold_start_s"] is None
        assert {"trace_s", "lower_s", "backend_s", "requests", "hits",
                "misses"} <= set(body["startup"]["compile"])
        assert body["devprof"]["ladder"]["unbracketed"]["requests"] >= 0
    elif endpoint == "/debug/engine/dump":
        assert body["engine"]["model"] == "tiny-qwen3"
        assert body["requests"] == {} and "devprof" in body
        assert not body["rings"]["events"]["torn"]
    else:
        assert "never-sent" in body["error"]["message"]
        assert "disabled" not in body["error"]["message"]


def test_event_ring_bounded():
    from tpuserve.runtime.flight import FlightRecorder
    fr = FlightRecorder(events=16, steps=4)
    for i in range(100):
        fr.req_event(f"r{i}", "QUEUED")
    snap = fr.engine_snapshot()
    assert snap["events_recorded"] == 100
    # ring holds only the most recent 16
    assert fr.request_timeline("r0") == []
    assert fr.request_timeline("r99")
    assert len(fr.recent_request_ids(limit=64)) <= 16


# ---- monotonic-clock pin (ISSUE 9 satellite) ---------------------------

_CLOCK_PIN_FILES = [
    "tpuserve/runtime", "tpuserve/server/runner.py",
    "tpuserve/server/metrics.py", "tpuserve/server/kv_digest.py",
    "tpuserve/server/tenants.py", "tpuserve/server/tpu_metrics.py",
    # the SLO engine's latency math (ISSUE 13): burn-rate windows and
    # canary probe latencies are deltas, never wall timestamps
    "tpuserve/obs",
]


def test_no_wall_clock_deltas_engine_side():
    """Latency deltas engine-side (restore latency, queue delay, step
    timing, SLI observations) must use time.monotonic(); time.time() is
    wall-clock and steps under NTP slew.  The only allowed engine-side
    time.time() is the flight recorder's monotonic->wall export anchor,
    marked `wall-anchor-ok` on its source line."""
    offenders = []
    paths = []
    for rel in _CLOCK_PIN_FILES:
        p = REPO / rel
        paths.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    for path in paths:
        src = path.read_text(encoding="utf-8")
        lines = src.splitlines()
        tree = ast.parse(src)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "time"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "time"):
                line = lines[node.lineno - 1]
                if "wall-anchor-ok" in line:
                    continue
                offenders.append(f"{path.relative_to(REPO)}:{node.lineno}")
    assert not offenders, (
        "wall-clock time.time() in engine-side timing code (use "
        f"time.monotonic(), or tag a wall-clock EXPORT with "
        f"wall-anchor-ok): {offenders}")


# ---- generated Grafana dashboard golden (ISSUE 9 satellite) ------------

def test_grafana_dashboard_golden():
    """tools/gen_dashboard.py output is pinned: a metrics-registry change
    must regenerate tests/golden/grafana_dashboard.json
    (`python -m tools.gen_dashboard --out tests/golden/grafana_dashboard.json`)."""
    from tools.gen_dashboard import build_dashboard, render
    golden = (REPO / "tests/golden/grafana_dashboard.json").read_text(
        encoding="utf-8")
    assert render() == golden, (
        "generated dashboard drifted from the golden — regenerate with "
        "python -m tools.gen_dashboard --out "
        "tests/golden/grafana_dashboard.json")
    # every registry family appears in some panel expression (the
    # dashboard covers the whole registry, both directions like P5)
    import inspect
    from tpuserve.server import metrics as metrics_mod
    from tools.tpulint.metrics_consistency import registry_from_source
    dash = build_dashboard()
    exprs = " ".join(t["expr"] for p in dash["panels"]
                     for t in p["targets"])
    for met in registry_from_source(inspect.getsource(metrics_mod)):
        assert met.family in exprs or met.exported in exprs, met.family


def test_grafana_dashboard_configmap_validates():
    from tpuserve.provision import manifests
    from tpuserve.provision.config import DeployConfig
    from tpuserve.provision.observability import grafana_dashboard_manifests
    objs = grafana_dashboard_manifests(DeployConfig())
    text = manifests.render(*objs)     # vendored strict schema validation
    assert "grafana_dashboard" in text
    data = objs[0]["data"]["tpuserve-engine.json"]
    dash = json.loads(data)
    assert dash["uid"] == "tpuserve-engine" and dash["panels"]


# ---- traceparent propagation (ISSUE 9 satellite: gateway span) ---------

def test_gateway_forwards_traceparent():
    """The gateway forwards W3C trace context upstream even without the
    OTel SDK (pass-through), so the server can still parent its span to
    the caller's trace."""
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    seen = {}

    class Backend(BaseHTTPRequestHandler):
        def do_POST(self):
            seen["traceparent"] = self.headers.get("traceparent")
            n = int(self.headers.get("Content-Length") or 0)
            self.rfile.read(n)
            body = b'{"ok": true}'
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = HTTPServer(("127.0.0.1", 0), Backend)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    from tpuserve.server.gateway import Gateway, GatewayConfig
    gw = Gateway([f"http://127.0.0.1:{httpd.server_address[1]}"],
                 GatewayConfig(host="127.0.0.1", port=0))
    port = gw.start()
    try:
        tp = "00-0123456789abcdef0123456789abcdef-0123456789abcdef-01"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=b'{"prompt": "x"}',
            headers={"Content-Type": "application/json",
                     "traceparent": tp}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
        assert seen["traceparent"] == tp
    finally:
        gw.shutdown()
        httpd.shutdown()


def test_extract_context_degrades():
    from tpuserve.server.tracing import extract_context
    assert extract_context({}) is None
    # a valid header returns a context object when the otel API is
    # importable; never raises either way
    extract_context({"traceparent":
                     "00-0123456789abcdef0123456789abcdef-"
                     "0123456789abcdef-01"})
