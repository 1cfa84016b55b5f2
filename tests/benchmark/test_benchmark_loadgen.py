"""The load generator's child process against a stub of the server's
streaming endpoint: records, failures, the closed loop's cut, and that the
child never imports JAX."""

import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from benchmark.harness import plan, stats

LOADGEN = os.path.join(plan.BENCH_ROOT, "harness", "loadgen.py")


class Stub(BaseHTTPRequestHandler):
    """Streams ``max_tokens`` tokens in events of up to 4, like a fused
    window; ``mode`` makes it misbehave."""
    mode = "ok"
    delay = 0.002

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        assert body["stream"] and body["return_token_ids"]
        assert body["temperature"] == 0 and body["ignore_eos"]
        assert all(isinstance(t, int) for t in body["prompt"])
        if self.mode == "busy":
            self.send_response(503)
            self.send_header("Content-Length", "4")
            self.end_headers()
            self.wfile.write(b"busy")
            return
        n = body["max_tokens"] - (1 if self.mode == "short" else 0)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(data: bytes):
            self.wfile.write(hex(len(data))[2:].encode() + b"\r\n" + data
                             + b"\r\n")
            self.wfile.flush()
        sent = 0
        while sent < n:
            k = min(4, n - sent)
            time.sleep(self.delay)
            event = {"choices": [{"index": 0, "text": "x",
                                  "token_ids": list(range(k)),
                                  "finish_reason": None}]}
            chunk(b"data: " + json.dumps(event).encode() + b"\n\n")
            sent += k
        chunk(b"data: [DONE]\n\n")
        self.wfile.write(b"0\r\n\r\n")


@pytest.fixture
def stub():
    handler = type("H", (Stub,), {})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    httpd.daemon_threads = True
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield handler, httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()
    t.join(timeout=5)
    assert not t.is_alive()


def mix_file(tmp_path, loop):
    mix = {"loop": loop, "pool": 16, "pool_seed": 5, "preroll_s": 0.4,
           "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
           "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
           "end_to_end": []}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    return str(path), mix


def run_child(tmp_path, port, loop, seed=2**31 + 5, seconds=1.2, **kw):
    path, mix = mix_file(tmp_path, loop)
    out = str(tmp_path / "records.json")
    argv = [sys.executable, LOADGEN, "--port", str(port), "--mix", path,
            "--model", "m", "--vocab", "300", "--seed", str(seed),
            "--seconds", str(seconds), "--preroll", str(mix["preroll_s"]),
            "--rate", str(kw.get("rate", 25.0)),
            "--clients", str(kw.get("clients", 3)),
            "--ramp", str(kw.get("ramp", 0.0)), "--drain-s", "5",
            "--start-in", "0.1", "--out", out]
    done = subprocess.run(argv, capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
    times = json.loads(done.stdout.splitlines()[0])
    data = json.load(open(out))
    assert data["times"] == times
    return times, data["records"], mix


def test_open_loop_sends_on_schedule_and_records_every_event(stub, tmp_path):
    _, port = stub
    times, records, mix = run_child(tmp_path, port, "open")
    s = stats.summarize(records, "open", times["t_window"], times["t_end"])
    assert s["attempted"] == 30 and s["failed"] == 0     # 25/s * 1.2 s
    window = [r for r in records if r["phase"] == "window"]
    assert all(times["t_window"] <= r["due"] <= times["t_end"]
               for r in window)
    assert any(r["phase"] == "preroll" for r in records)
    for r in window:
        assert r["sent"] >= r["due"] and stats.tokens_of(r) == r["want"]
        assert max(n for _, n in r["events"]) <= 4
    assert stats.percentile(s["loadgen_late_ms"], 95) < 50


def test_closed_loop_keeps_clients_busy_and_cuts_at_the_end(stub, tmp_path):
    handler, port = stub
    handler.delay = 0.05
    times, records, _ = run_child(tmp_path, port, "closed", clients=3)
    s = stats.summarize(records, "closed", times["t_window"], times["t_end"])
    # whatever is in flight when the window closes is cut, never failed
    assert 1 <= s["cut"] <= 3 and s["failed"] == 0 and s["attempted"] > 0
    ended = [r for r in records if not r.get("cut")]
    assert s["cut"] + len(ended) == len(records)
    assert all(stats.tokens_of(r) == r["want"] for r in ended)
    assert s["tokens_in_window"] > 0


def test_closed_loop_clients_join_over_the_ramp(stub, tmp_path):
    handler, port = stub
    handler.delay = 0.5          # a request outlasts the ramp: one each
    times, records, _ = run_child(tmp_path, port, "closed", clients=4,
                                  ramp=0.4, seconds=0.3)
    joined = sorted(r["due"] - times["t_start"] for r in records)[:4]
    # the four clients' first requests are due 0.1 s apart, not together
    assert joined == pytest.approx([0.0, 0.1, 0.2, 0.3], abs=0.05)


@pytest.mark.parametrize("mode", ["short", "busy"])
def test_a_wrong_token_count_or_a_non_200_is_failed(stub, tmp_path, mode):
    handler, port = stub
    handler.mode = mode
    times, records, _ = run_child(tmp_path, port, "open", seconds=0.6)
    s = stats.summarize(records, "open", times["t_window"], times["t_end"])
    assert s["attempted"] == 15 and s["failed"] == 15


def test_same_seed_same_requests(stub, tmp_path):
    _, port = stub
    a = run_child(tmp_path, port, "open", seed=9, seconds=0.5)[1]
    b = run_child(tmp_path, port, "open", seed=9, seconds=0.5)[1]
    key = lambda rs: sorted((r["id"], r["prompt_tokens"], r["want"])
                            for r in rs)
    assert key(a) == key(b)


def test_the_child_never_imports_jax():
    code = ("import sys, runpy; sys.argv=['loadgen.py','--help']\n"
            "try:\n runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit: pass\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules"
            % LOADGEN)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0, done.stderr
