#!/usr/bin/env python
"""Serving-grade benchmark: per-request TTFT / inter-token latency through
the real HTTP + SSE stack, under closed-loop (N concurrent clients) or
open-loop (Poisson arrivals at --rate req/s) load.

This measures what a *user* of the deployment sees — the reference's value
proposition is a working serving endpoint (`llm-d-test.yaml` smoke-tests
the gateway API), and `bench.py` measures the engine in-process; this tool
closes the gap by timing first-token and token-gap latencies as observed
by HTTP clients, including scheduler queueing, SSE framing, and the
per-request pump threads.

Usage:
  python tools/bench_serving.py [--model qwen3-0.6b] [--clients 32]
      [--rate 0] [--num-requests 64] [--prompt-len 128] [--gen-len 128]
      [--url http://host:port]   # benchmark an ALREADY-RUNNING server

Without --url an in-process OpenAIServer is started on the device JAX
gives this process (the one process that then owns the chip).  Prints one
JSON line and appends a section to bench_results.md.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1, int(len(sorted_vals) * q))]


def _stream_request(url, prompt_ids, gen, record):
    """POST a streaming completion; record first-token and gap times as the
    chunks ARRIVE (read incrementally — r.read() would hide all timing)."""
    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps({"prompt": prompt_ids, "max_tokens": gen,
                         "stream": True, "temperature": 0,
                         "ignore_eos": True,
                         "return_token_ids": True}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    t_sent = time.perf_counter()
    tok_times: list[float] = []
    n_tokens = 0
    with urllib.request.urlopen(req, timeout=1200) as resp:
        buf = b""
        while True:
            # read1: whatever bytes the kernel has — arrival-time fidelity
            # without a Python-level read() per byte (32 threads of
            # byte-wise reads would serialize on the GIL and the client
            # would distort the latencies it measures)
            chunk = resp.read1(65536)
            if not chunk:
                break
            now = time.perf_counter()
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                for ln in event.decode().splitlines():
                    if not ln.startswith("data: ") or ln.endswith("[DONE]"):
                        continue
                    choice = json.loads(ln[len("data: "):])["choices"][0]
                    ids = choice.get("token_ids")
                    if ids is None:
                        # plain OpenAI server without the return_token_ids
                        # extension: one chunk ~= one token — except the
                        # standard empty-text terminal chunk that only
                        # carries finish_reason
                        t = choice.get("text")
                        k = (0 if t is None
                             or (not t and choice.get("finish_reason"))
                             else 1)
                    else:
                        # one SSE chunk carries >=1 tokens under fused
                        # windows; attribute kernel-delivery time to each
                        k = len(ids)
                    tok_times.extend([now] * k)
                    n_tokens += k
    record["ttft_s"] = tok_times[0] - t_sent if tok_times else None
    record["gaps_s"] = [b - a for a, b in zip(tok_times, tok_times[1:])]
    record["n_tokens"] = n_tokens
    record["done_s"] = (tok_times[-1] - t_sent) if tok_times else None
    # written LAST: the main thread filters on this single atomic marker,
    # so a thread finishing just past the join timeout can never expose a
    # half-written record
    record["ok"] = bool(tok_times)


def run_load(url, prompts, gen, rate):
    """Fire every prompt (Poisson-spaced at ``rate`` req/s when > 0, all at
    once otherwise) and gather per-request records."""
    import numpy as np
    rng = np.random.default_rng(0)
    records = [dict() for _ in prompts]
    threads = []
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        if rate > 0 and i:
            time.sleep(float(rng.exponential(1.0 / rate)))
        th = threading.Thread(target=_stream_request,
                              args=(url, p, gen, records[i]),
                              daemon=True)   # a wedged stream must not
        th.start()                           # block interpreter shutdown
        threads.append(th)
    hung = 0
    for i, th in enumerate(threads):
        th.join(timeout=1800)
        if th.is_alive():
            # a stalled stream is exactly what this benchmark exists to
            # catch — surface it loudly, don't let it masquerade as a
            # quietly lost record
            records[i]["hung"] = True
            hung += 1
    wall = time.perf_counter() - t0
    return records, wall, hung


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--clients", type=int, default=32,
                    help="concurrent requests (closed-loop when --rate 0)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop Poisson arrival rate, req/s (0 = burst)")
    ap.add_argument("--num-requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--gen-len", type=int, default=None)
    ap.add_argument("--url", default=None,
                    help="benchmark an already-running server instead of "
                         "starting one in-process")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-model CPU smoke shapes")
    ap.add_argument("--gateway", action="store_true",
                    help="route through an in-process gateway (adds the "
                         "relay hop the K8s deployment has)")
    ap.add_argument("--no-md", action="store_true",
                    help="don't append the bench_results.md section (tests)")
    ap.add_argument("--multi-step", type=int, default=None, metavar="S",
                    help="fused decode window for the in-process engine "
                         "(default: engine auto).  The S=32 throughput "
                         "default delivers streamed tokens in ~S-token "
                         "bursts; this flag exists to measure that ITL "
                         "cost and pick the serving default from data")
    args = ap.parse_args(argv)
    if args.gateway and args.url:
        ap.error("--gateway only applies to the in-process server; an "
                 "external --url is measured as-is")
    if args.multi_step is not None and args.url:
        ap.error("--multi-step configures the in-process engine; an "
                 "external --url serves with whatever it was started with")

    import numpy as np

    # one derivation of the workload shape, shared by both branches
    n = args.num_requests or args.clients
    srv = gw = None
    multi_step_resolved = None
    if args.url:
        url = args.url
        backend = "external"
        vocab = 1000
        model = args.model
        plen = args.prompt_len or 128
        glen = args.gen_len or 128
        # nothing client-side caps concurrency against an external server
        concurrency_capped = False
    else:
        import jax
        from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                      SchedulerConfig)
        from tpuserve.server.openai_api import OpenAIServer, ServerConfig
        from tpuserve.utils import compile_cache
        compile_cache.configure()
        backend = jax.default_backend()
        if args.smoke or backend != "tpu":
            model, plen, glen = "tiny-qwen3", 16, 16
        else:
            model, plen, glen = args.model, 128, 128
        plen = args.prompt_len or plen
        glen = args.gen_len or glen
        max_len = plen + glen
        block = 32 if backend == "tpu" else 8
        bps = -(-max_len // block) + 1
        eng = Engine(EngineConfig(
            model=model,
            cache=CacheConfig(block_size=block,
                              num_blocks=args.clients * bps + 2 * args.clients,
                              max_blocks_per_seq=bps),
            scheduler=SchedulerConfig(max_num_seqs=args.clients,
                                      max_prefill_seqs=args.clients,
                                      max_prefill_tokens=max(
                                          8192, args.clients * plen)),
            multi_step=args.multi_step))
        srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
        multi_step_resolved = eng._multi_step   # record what actually ran,
        url = f"http://127.0.0.1:{srv.start()}"  # not the flag (None=auto)
        vocab = eng.model_cfg.vocab_size
        concurrency_capped = True             # max_num_seqs == clients
        if args.gateway:
            from tpuserve.server.gateway import Gateway, GatewayConfig
            gw = Gateway([url], GatewayConfig(host="127.0.0.1", port=0,
                                              health_interval_s=0.5))
            url = f"http://127.0.0.1:{gw.start()}"
            backend = backend + "+gateway"

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, vocab - 1, size=plen).tolist()
               for _ in range(n)]

    # Warm the full arrival bucket LADDER first (in-process server only):
    # staggered HTTP arrivals admit variable prefill batch sizes, so a
    # single warm burst leaves novel shapes to compile inside the timed
    # run — the round-4 "85-97% HTTP overhead" was exactly those compiles
    # (VERDICT r4 weak #5).  bench.py's arrival plan enumerates the
    # ladder; the warm burst after it covers the HTTP/SSE layer itself.
    if srv is not None:
        from bench import _warm
        _warm(srv.engine, args.clients, plen, arrivals=True)
    # warmup burst: compile any remaining bucket this concurrency hits —
    # using DISJOINT prompts, since replaying the measured prompts would
    # turn every timed prefill into a prefix-cache hit (the engine's
    # prefix cache is on by default) and understate TTFT
    warm_prompts = [np.random.default_rng(10_000 + i)
                    .integers(1, vocab - 1, size=plen).tolist()
                    for i in range(args.clients)]
    run_load(url, warm_prompts, glen, 0.0)
    records, wall, hung = run_load(url, prompts, glen, args.rate)

    good = [r for r in records if r.get("ok")]
    lost = len(records) - len(good)
    if lost == len(records):
        raise SystemExit(
            "every stream lost — server emitted no countable tokens "
            "(wrong --url contract?); refusing to report zeros")
    ttfts = sorted(1000.0 * r["ttft_s"] for r in good)
    gaps = sorted(1000.0 * g for r in good for g in r["gaps_s"])
    total_tokens = sum(r["n_tokens"] for r in good)
    out = {
        "metric": "serving_latency",
        "backend": backend,
        "model": model,
        "clients": args.clients,
        "concurrency_capped": concurrency_capped,
        "rate_req_s": args.rate,
        "num_requests": n,
        "prompt_len": plen,
        "gen_len": glen,
        "multi_step": multi_step_resolved,
        "lost_streams": lost,
        "hung_streams": hung,
        "throughput_tok_s": round(total_tokens / wall, 1),
        "ttft_ms": {"p50": round(_pct(ttfts, 0.50), 1),
                    "p90": round(_pct(ttfts, 0.90), 1),
                    "p99": round(_pct(ttfts, 0.99), 1)},
        "itl_ms": {"p50": round(_pct(gaps, 0.50), 2),
                   "p90": round(_pct(gaps, 0.90), 2),
                   "p99": round(_pct(gaps, 0.99), 2)},
    }
    print(json.dumps(out))
    if gw is not None:
        gw.shutdown()
    if srv is not None:
        srv.shutdown()
    if args.no_md:
        return out

    stamp = datetime.datetime.now().strftime("%Y-%m-%d %H:%M")
    mode = (f"open-loop {args.rate} req/s" if args.rate
            else f"closed-loop burst of {n}")
    cap = (f"{args.clients} max concurrent (server-enforced)"
           if concurrency_capped else "concurrency uncapped (external server)")
    with open(os.path.join(ROOT, "bench_results.md"), "a") as f:
        f.write(
            f"\n## Serving latency @ {stamp}\n\n"
            f"{mode}, {cap}, {model}, "
            f"{plen} in / {glen} out, backend={backend} "
            f"(tools/bench_serving.py — HTTP+SSE client-observed):\n\n"
            f"| metric | p50 | p90 | p99 |\n|---|---|---|---|\n"
            f"| TTFT ms | {out['ttft_ms']['p50']} | {out['ttft_ms']['p90']}"
            f" | {out['ttft_ms']['p99']} |\n"
            f"| inter-token ms | {out['itl_ms']['p50']} | "
            f"{out['itl_ms']['p90']} | {out['itl_ms']['p99']} |\n\n"
            f"Aggregate {out['throughput_tok_s']} tok/s through the server; "
            f"{lost} lost streams.\n")
    return out


if __name__ == "__main__":
    main()
