"""Serving-stack load test: N concurrent streaming clients (VERDICT r2 weak
#6 — correctness under contention, not just single-request correctness).

The engine server and gateway are Python ThreadingHTTPServers: per-request
handler threads write SSE tokens while the engine loop thread batches, so
stream corruption / interleaving / lost finals only show up under real
concurrency.  Every client asserts full stream integrity: well-formed SSE
framing, exactly max_tokens chunks, a finish_reason, and the [DONE]
terminator.  Greedy streams for the SAME prompt must also be identical
across clients — continuous batching must not leak tokens across requests.

Throughput is the benchmark's to measure (benchmark/run.py), not this
test's.
"""

import json
import threading
import urllib.request

import pytest

from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SchedulerConfig
from tpuserve.server.gateway import Gateway, GatewayConfig
from tpuserve.server.openai_api import OpenAIServer, ServerConfig

N_CLIENTS = 32
GEN_TOKENS = 6


def _mk_server():
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=256, max_blocks_per_seq=8),
        scheduler=SchedulerConfig(max_num_seqs=16, min_prefill_bucket=8,
                                  min_decode_bucket=2)))
    srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
    port = srv.start()
    return srv, f"http://127.0.0.1:{port}"


@pytest.fixture(scope="module")
def stack():
    srv1, url1 = _mk_server()
    srv2, url2 = _mk_server()
    gw = Gateway([url1, url2], GatewayConfig(host="127.0.0.1", port=0,
                                             health_interval_s=0.5))
    gport = gw.start()
    yield {"url": f"http://127.0.0.1:{gport}", "direct": url1}
    gw.shutdown()
    for s in (srv1, srv2):
        s.shutdown()


def _stream_one(base_url: str, prompt, out: dict, key):
    try:
        req = urllib.request.Request(
            base_url + "/v1/completions",
            data=json.dumps({"prompt": prompt, "max_tokens": GEN_TOKENS,
                             "stream": True, "temperature": 0,
                             "ignore_eos": True,
                             "return_token_ids": True}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=300) as r:
            assert "text/event-stream" in r.headers["Content-Type"]
            raw = r.read().decode()
        events = [ln[len("data: "):] for ln in raw.splitlines()
                  if ln.startswith("data: ")]
        assert events, "empty SSE stream"
        assert events[-1] == "[DONE]", f"missing [DONE]: {events[-3:]}"
        chunks = [json.loads(e) for e in events[:-1]]
        ids = [c["choices"][0]["token_ids"] for c in chunks]   # KeyError if
        n_tokens = sum(len(i) for i in ids)       # return_token_ids broke
        finals = [c for c in chunks if c["choices"][0]["finish_reason"]]
        assert finals, "no finish_reason in stream"
        assert finals[-1] is chunks[-1], "tokens after the final chunk"
        assert finals[-1]["choices"][0]["finish_reason"] == "length"
        out[key] = {"n_chunks": len(chunks), "n_tokens": n_tokens,
                    "ids": ids}
    except Exception as e:                       # pragma: no cover
        out[key] = e


def _run_clients(base_url: str, prompts) -> dict:
    out: dict = {}
    threads = [threading.Thread(target=_stream_one,
                                args=(base_url, p, out, i))
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert len(out) == len(prompts)
    errors = {k: v for k, v in out.items() if isinstance(v, Exception)}
    assert not errors, f"client failures: {errors}"
    return out


def test_concurrent_streaming_direct(stack):
    """32 concurrent streaming clients against one engine server: every
    stream complete and correctly framed."""
    prompts = [[2 + (i % 7), 3, 4 + (i % 5)] for i in range(N_CLIENTS)]
    out = _run_clients(stack["direct"], prompts)
    for i in range(N_CLIENTS):
        assert out[i]["n_tokens"] == GEN_TOKENS, (i, out[i])


def test_concurrent_streaming_through_gateway(stack):
    """The same load through the health-checked gateway (relay threads on
    top of engine pump threads)."""
    prompts = [[5, 6 + (i % 9)] for i in range(N_CLIENTS)]
    out = _run_clients(stack["url"], prompts)
    for i in range(N_CLIENTS):
        assert out[i]["n_tokens"] == GEN_TOKENS, (i, out[i])


def test_identical_prompts_identical_greedy_streams(stack):
    """Greedy decode of the same prompt across 16 concurrent clients must
    produce byte-identical token streams — batching must not cross wires."""
    prompts = [[7, 8, 9]] * 16
    out = _run_clients(stack["direct"], prompts)
    streams = [json.dumps(out[i]["ids"]) for i in range(16)]
    assert len(set(streams)) == 1, "greedy streams diverged across clients"


@pytest.fixture(scope="module")
def windowed_stack():
    """Engine server running the TPU-default decode shape — pipelined fused
    windows — so SSE bursts of S tokens from per-request pump threads are
    load-tested on CPU too."""
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=256, max_blocks_per_seq=8),
        scheduler=SchedulerConfig(max_num_seqs=16, min_prefill_bucket=8,
                                  min_decode_bucket=2),
        multi_step=3, pipeline_decode=True))
    srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
    port = srv.start()
    yield f"http://127.0.0.1:{port}"
    srv.shutdown()


def test_concurrent_streaming_pipelined_windows(windowed_stack):
    """32 concurrent clients against pipelined fused windows (S=3, GEN=6
    not a multiple-of-window edge is covered by max_tokens drops): every
    stream complete, token counts exact."""
    prompts = [[2 + (i % 7), 3, 4 + (i % 5)] for i in range(N_CLIENTS)]
    out = _run_clients(windowed_stack, prompts)
    for i in range(N_CLIENTS):
        assert out[i]["n_tokens"] == GEN_TOKENS, (i, out[i])


def test_identical_greedy_streams_pipelined_windows(windowed_stack):
    prompts = [[7, 8, 9]] * 16
    out = _run_clients(windowed_stack, prompts)
    streams = [json.dumps(out[i]["ids"]) for i in range(16)]
    assert len(set(streams)) == 1, "greedy streams diverged across clients"


def test_windowed_rolling_release_under_concurrency():
    """Sliding-window serving under real concurrent load: prompts longer
    than the window stream from a cache that full contexts would
    oversubscribe — the rolling buffer must recycle blocks across many
    live sequences without corrupting streams, and the pool must drain
    clean afterwards."""
    eng = Engine(EngineConfig(
        model="tiny-mistral",
        cache=CacheConfig(block_size=4, num_blocks=96,
                          max_blocks_per_seq=32),
        scheduler=SchedulerConfig(max_num_seqs=16, min_prefill_bucket=8,
                                  min_decode_bucket=2),
        enable_prefix_caching=False))
    srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
    url = f"http://127.0.0.1:{srv.start()}"
    try:
        results: dict[int, list] = {}

        def client(i):
            prompt = [(i % 5) + 2, (i % 7) + 3] * 10   # 20 tokens > window
            req = urllib.request.Request(
                url + "/v1/completions",
                data=json.dumps({"prompt": prompt, "max_tokens": 16,
                                 "temperature": 0, "ignore_eos": True,
                                 "stream": True,
                                 "return_token_ids": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                raw = r.read().decode()
            toks = [t for ln in raw.splitlines()
                    if ln.startswith("data: ") and not ln.endswith("[DONE]")
                    for t in json.loads(ln[6:])["choices"][0]["token_ids"]]
            results[i] = toks

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert len(results) == 24
        assert all(len(v) == 16 for v in results.values())
        # identical prompts -> identical greedy streams (i mod 35 groups)
        groups: dict[tuple, list] = {}
        for i, v in results.items():
            groups.setdefault((i % 5, i % 7), []).append(v)
        for vs in groups.values():
            assert all(v == vs[0] for v in vs)
        # pool drains completely: every released + freed block accounted
        assert eng.block_manager.num_seqs() == 0
        assert eng.block_manager.num_free_blocks == 96
    finally:
        srv.shutdown()
