#!/usr/bin/env python
"""Does the system still start on the chip?  The quickest proof there is.

    python chip_smoke.py             one TPU chip
    python chip_smoke.py --chips 4   one four-chip host (run by hand)

One process that owns the chip: no probe in a child, no re-exec, no child
that needs the device.  With no arguments it drives the main path once at
the full width and depth of Qwen3-0.6B (random weights from a seed): the
real server, built from the same argv ``python -m tpuserve.server`` takes,
answers a handful of HTTP requests that together reach every dispatch kind
(batched prefill, fused decode windows, streaming, continuous batching, the
chunked-prefill window kernel, a prefix-cache hit), then the same process
runs the ragged mixed step and the int8-KV kernels, and compares greedy
decoding under ``attn_impl="pallas"`` with ``"reference"`` on the same
weights.  ``--chips 4`` runs only the sharded path and what it is compared
with: Llama-3.1-8B under ``--tp 4``, Pallas against reference on one mesh.

Every assertion reads the engine's own state, not its logs.  Any phase
that fails ends the run with a non-zero exit and no result line; so does a
machine where JAX finds no TPU.  The timings printed on the way are smoke
timings — cold compiles included — never benchmark results.  The last
line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.metadata
import json
import sys
import threading
import time
import urllib.request

# Stated tolerances of the Pallas-vs-reference comparison (bf16 weights and
# cache, f32 softmax and accumulation in both implementations).
KERNEL_ATOL = 3e-2     # attention outputs are convex mixes of N(0,1) values
LOGPROB_ATOL = 1e-1    # chosen-token logprob while the contexts still agree
TIE_ATOL = 1e-1        # how far behind the reference's top-1 a diverging
                       # token may be and still count as a near-tie
# (On a v5e the first run read 0.016 at most for the kernels, 0.031 for the
# logprobs and 0.016 for the ties: a wrong kernel is off by order 1.)


@dataclasses.dataclass(frozen=True)
class Plan:
    """The sizes of one smoke run.  The script runs ONE_CHIP or FOUR_CHIP;
    the CPU rehearsal (tests/test_chip_smoke.py) passes tiny ones."""
    model: str
    server_args: tuple           # argv of ``python -m tpuserve.server``
    multi_step: int              # the fused window the engine must run
    chunk: int                   # the engine's prefill chunk size
    long_prompt: int             # tokens; must exceed ``chunk``
    concurrent: int              # simultaneous requests
    max_tokens: int
    side_cache: dict             # CacheConfig of the in-process engines
    kernel_widths: tuple         # (Hq, Hkv, D) of the kernel comparison
    warmup: bool = True


ONE_CHIP = Plan(
    model="Qwen/Qwen3-0.6B",
    server_args=("--model", "Qwen/Qwen3-0.6B", "--num-blocks", "0",
                 "--max-blocks-per-seq", "128", "--attn-impl", "pallas",
                 "--host", "127.0.0.1", "--port", "0"),
    multi_step=32, chunk=2048, long_prompt=2300, concurrent=8,
    max_tokens=40,
    side_cache=dict(block_size=32, num_blocks=512, max_blocks_per_seq=16),
    kernel_widths=(16, 8, 128))

FOUR_CHIP = Plan(
    model="meta-llama/Llama-3.1-8B-Instruct",
    server_args=("--model", "meta-llama/Llama-3.1-8B-Instruct", "--tp", "4",
                 "--num-blocks", "0", "--max-blocks-per-seq", "128",
                 "--attn-impl", "pallas", "--host", "127.0.0.1",
                 "--port", "0"),
    multi_step=32, chunk=2048, long_prompt=0, concurrent=4, max_tokens=40,
    side_cache=dict(block_size=32, num_blocks=512, max_blocks_per_seq=16),
    kernel_widths=(32, 8, 128),
    # warmup is the same code on one chip, where it costs a quarter as much
    warmup=False)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, what: str) -> None:
    """A failed expectation ends the run: non-zero exit, no result line."""
    if not cond:
        raise SystemExit(f"[smoke] FAILED: {what}")


# --------------------------------------------------------------------------
# what each phase reports
# --------------------------------------------------------------------------

class Meter:
    """Per-phase counts: wall seconds, persistent-compile-cache hits and
    misses (the program's own compile ledger,
    ``tpuserve/utils/compile_cache.py``), cache entries on disk, and the
    device's peak bytes."""

    def __init__(self, cache_dir: str):
        from tpuserve.utils import compile_cache
        self.cache_dir = cache_dir
        self._ledger = compile_cache.LEDGER
        self._ledger.listen()

    @property
    def hits(self) -> int:
        return self._ledger.totals()["hits"]

    @property
    def misses(self) -> int:
        return self._ledger.totals()["misses"]

    @contextlib.contextmanager
    def phase(self, name: str, ladder: "Ladder | None" = None):
        import jax

        from tpuserve.utils import compile_cache
        t0, h0, m0 = time.perf_counter(), self.hits, self.misses
        c0 = ladder.compiled() if ladder else None
        rec = {"tokens": 0}
        yield rec
        line = [f"phase {name}: wall {time.perf_counter() - t0:.1f}s"]
        if ladder:
            c1 = ladder.compiled()
            rec["compiled"] = c1[0] - c0[0]
            line.append(f"compiled {rec['compiled']} executable(s) in "
                        f"{c1[1] - c0[1]:.1f}s")
        line.append(f"compile-cache hits {self.hits - h0} misses "
                    f"{self.misses - m0} entries "
                    f"{compile_cache.entries(self.cache_dir)}")
        stats = jax.local_devices()[0].memory_stats() or {}
        line.append(f"peak device bytes {stats.get('peak_bytes_in_use')}")
        line.append(f"tokens generated {rec['tokens']}")
        say(", ".join(line) + "  (smoke timing, not a benchmark)")


class Ladder:
    """An engine's devprof executable ladder, which records each
    executable's first call and the seconds it blocked (the compile).
    ``engine`` may be set after the phase that builds it has begun."""

    def __init__(self, engine=None):
        self.engine = engine

    def compiled(self) -> tuple:
        if self.engine is None:
            return 0, 0.0
        return self.engine.devprof.compiles, self.engine.devprof.compile_s


# --------------------------------------------------------------------------
# HTTP client side
# --------------------------------------------------------------------------

def http_json(url: str, payload=None, timeout: float = 900.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="GET" if payload is None else "POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        body = r.read()
    return json.loads(body)


def http_text(url: str, timeout: float = 60.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def http_stream_chat(url: str, payload: dict, timeout: float = 900.0) -> int:
    """POST a streamed chat completion; returns how many content chunks
    arrived before the [DONE] terminator."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    chunks, done = 0, False
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            body = line[len("data:"):].strip()
            if body == "[DONE]":
                done = True
                break
            delta = json.loads(body)["choices"][0]
            if delta.get("finish_reason") is None:
                chunks += 1
    check(done, "streamed chat ended without the [DONE] terminator")
    return chunks


def complete(url: str, plan: Plan, prompt) -> dict:
    body = http_json(url + "/v1/completions", {
        "model": plan.model, "prompt": prompt, "max_tokens": plan.max_tokens,
        "temperature": 0, "ignore_eos": True})
    got = body["usage"]["completion_tokens"]
    check(got == plan.max_tokens,
          f"completion finished with {got} tokens, wanted {plan.max_tokens}")
    return body


def metric(text: str, name: str) -> float:
    """Sum of a Prometheus family's samples in a /metrics page."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            head = line.split()[0]
            if head == name or head.startswith(name + "{"):
                total += float(line.split()[-1])
                seen = True
    check(seen, f"/metrics has no {name}")
    return total


# --------------------------------------------------------------------------
# the phases
# --------------------------------------------------------------------------

def build_native() -> None:
    """The block manager the engine gets must be the C++ one, compiled
    from the tracked sources whatever .so is already on disk."""
    from tpuserve.native import build_from_source
    path = build_from_source()
    say(f"native block manager built from native/*.cc -> {path}")


def check_engine(engine, plan: Plan) -> None:
    from tpuserve.native import NativeBlockManager
    say(f"engine: attn_impl={engine.attn_impl} "
        f"pipeline_decode={engine._pipeline_decode} "
        f"multi_step={engine._multi_step} "
        f"block_manager={type(engine.block_manager).__name__} "
        f"kv_blocks={engine.cache_cfg.num_blocks}")
    check(engine.attn_impl == "pallas", "engine.attn_impl is not pallas")
    check(engine._pipeline_decode, "pipelined decode is off")
    check(engine._multi_step == plan.multi_step,
          f"multi_step {engine._multi_step} != {plan.multi_step}")
    check(isinstance(engine.block_manager, NativeBlockManager),
          "the engine runs the pure-Python block manager")


def compare_kernels(plan: Plan, meter: Meter) -> None:
    """Each Pallas kernel against the repo's pure-JAX reference of the same
    semantics (ops/attention.py), on seeded random inputs at the model's
    head widths — the kernels the compiler refused before this smoke
    existed have never met silicon."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.ops import attention as ref
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    from tpuserve.ops.pallas_flash_attention import flash_prefill_attention
    from tpuserve.ops.pallas_paged_attention import paged_decode_attention
    from tpuserve.ops.pallas_ragged_attention import (ragged_block,
                                                      ragged_paged_attention)

    hq, hkv, d = plan.kernel_widths
    page, nb, mp = 32, 96, 12
    rng = np.random.default_rng(0)
    scale = d ** -0.5

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    # one program per reference, not one per primitive
    ref_decode = jax.jit(ref.paged_decode_attention)
    ref_prefill = jax.jit(ref.prefill_attention)
    ref_chunk = jax.jit(ref.chunked_prefill_attention)
    ref_ragged = jax.jit(ref.ragged_attention)

    kc, vc = normal(nb, page, hkv, d), normal(nb, page, hkv, d)
    kq, ks = ref.quantize_kv(kc)
    vq, vs = ref.quantize_kv(vc)
    ks, vs = ref.pad_scale_lanes(ks), ref.pad_scale_lanes(vs)
    int8 = dict(k_scale=ks, v_scale=vs)

    def close(name, out, want, rows=None):
        out = np.asarray(out, np.float32)
        want = np.asarray(want, np.float32)
        if rows is not None:
            out, want = out[rows], want[rows]
        check(np.isfinite(out).all(), f"{name}: non-finite output")
        err = float(np.abs(out - want).max())
        say(f"  {name}: max |pallas - reference| = {err:.4f}")
        check(err <= KERNEL_ATOL, f"{name}: {err:.4f} > atol {KERNEL_ATOL}")

    with meter.phase("kernels vs reference"):
        B = 8
        q = normal(B, hq, d)
        bt = jnp.asarray(rng.permutation(nb)[:B * mp].reshape(B, mp),
                         jnp.int32)
        sl = jnp.asarray(rng.integers(1, page * mp + 1, (B,)), jnp.int32)
        close("paged decode", paged_decode_attention(q, kc, vc, bt, sl, scale),
              ref_decode(q, kc, vc, bt, sl, scale))
        close("paged decode int8-KV",
              paged_decode_attention(q, kq, vq, bt, sl, scale, **int8),
              ref_decode(q, kq, vq, bt, sl, scale, **int8))

        T = 256
        qf, kf, vf = normal(2, T, hq, d), normal(2, T, hkv, d), \
            normal(2, T, hkv, d)
        lens = jnp.asarray([T, T - 37], jnp.int32)
        out = flash_prefill_attention(qf, kf, vf, lens, scale)
        want = ref_prefill(qf, kf, vf, lens, scale)
        for b in range(2):       # rows past the prompt are never read
            close(f"flash prefill seq {b}", out[b], want[b],
                  rows=slice(0, int(lens[b])))

        C = 256
        qw = normal(1, C, hq, d)
        ctx = jnp.asarray([page * 3 + 5], jnp.int32)
        chunk = jnp.asarray([C - 9], jnp.int32)
        rows = slice(0, int(chunk[0]))
        close("chunk window",
              paged_window_attention(qw, kc, vc, bt[:1], ctx, chunk,
                                     scale)[0],
              ref_chunk(qw, kc, vc, bt[:1], ctx, chunk, scale)[0],
              rows=rows)
        close("chunk window int8-KV",
              paged_window_attention(qw, kq, vq, bt[:1], ctx, chunk, scale,
                                     **int8)[0],
              ref_chunk(qw, kq, vq, bt[:1], ctx, chunk, scale, **int8)[0],
              rows=rows)

        # ragged: 3 decode rows, then one prefill chunk of 100 rows at the
        # next block boundary, laid out by the host contract
        blk = ragged_block()
        n_dec, n_pre, ctx_pre = 3, 100, 70
        T = blk + -(-n_pre // blk) * blk
        qr = normal(T, hq, d)
        kv_lens = np.zeros((B,), np.int32)
        kv_lens[:n_dec] = [40, 200, 333]
        kv_lens[n_dec] = ctx_pre + n_pre
        q_starts = np.zeros((B,), np.int32)
        q_starts[:n_dec] = np.arange(n_dec)
        q_starts[n_dec] = blk
        q_lens = np.zeros((B,), np.int32)
        q_lens[:n_dec] = 1
        q_lens[n_dec] = n_pre
        blk_seq = np.full((T // blk,), -1, np.int32)
        blk_seq[1:] = n_dec
        meta = np.asarray([n_dec, 1], np.int32)
        row_seq = np.zeros((T,), np.int32)
        row_len = np.zeros((T,), np.int32)       # row's position + 1
        row_seq[:n_dec] = np.arange(n_dec)
        row_len[:n_dec] = kv_lens[:n_dec]
        row_seq[blk:blk + n_pre] = n_dec
        row_len[blk:blk + n_pre] = ctx_pre + 1 + np.arange(n_pre)
        valid = np.r_[np.arange(n_dec), blk + np.arange(n_pre)]
        args = [jnp.asarray(x) for x in (kv_lens, q_starts, q_lens, meta,
                                         blk_seq)]
        want = ref_ragged(qr, kc, vc, bt[jnp.asarray(row_seq)],
                          jnp.asarray(row_len), scale)
        close("ragged mixed",
              ragged_paged_attention(qr, kc, vc, bt, *args, scale),
              want, rows=valid)
        want8 = ref_ragged(qr, kq, vq, bt[jnp.asarray(row_seq)],
                           jnp.asarray(row_len), scale, **int8)
        close("ragged mixed int8-KV",
              ragged_paged_attention(qr, kq, vq, bt, *args, scale, **int8),
              want8, rows=valid)


def serve(plan: Plan, meter: Meter):
    """The real server over HTTP.  Returns the engine once the server is
    shut down, for the in-process comparisons that follow."""
    import numpy as np

    from tpuserve.server.openai_api import build_server

    compiled = Ladder()
    with meter.phase("server start" + " + warmup" * plan.warmup, compiled):
        server, _args = build_server(list(plan.server_args))
        engine = compiled.engine = server.engine
        port = server.start(warmup=plan.warmup)
    url = f"http://127.0.0.1:{port}"
    check_engine(engine, plan)
    check(engine._attn_mesh is not None or "--tp" not in plan.server_args,
          "Pallas-under-tp is off: _attn_mesh is None")
    rng = np.random.default_rng(1)
    vocab = engine.model_cfg.vocab_size

    def short_requests(rec):
        for prompt in ("Who are you?", "The capital of France is"):
            complete(url, plan, prompt)
            rec["tokens"] += plan.max_tokens

    try:
        health = http_json(url + "/healthz")
        check(health.get("status") == "ok", f"/healthz says {health}")
        with meter.phase("short completions", compiled) as rec:
            short_requests(rec)

        with meter.phase("streamed chat", compiled) as rec:
            chunks = http_stream_chat(url + "/v1/chat/completions", {
                "model": plan.model, "stream": True, "temperature": 0,
                "max_tokens": plan.max_tokens, "ignore_eos": True,
                "messages": [{"role": "user", "content": "Hi"}]})
            check(chunks > 0, "streamed chat delivered no content chunk")
            rec["tokens"] += plan.max_tokens

        with meter.phase(f"{plan.concurrent} concurrent", compiled) as rec:
            errors = []

            def one(i):
                try:
                    complete(url, plan, f"Request number {i}: tell me")
                except BaseException as e:       # re-raised below
                    errors.append(e)
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(plan.concurrent)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
                check(not t.is_alive(), "a concurrent request never ended")
            if errors:
                raise errors[0]
            rec["tokens"] += plan.concurrent * plan.max_tokens

        if plan.long_prompt:
            check(plan.long_prompt > plan.chunk,
                  "the long prompt does not exceed the prefill chunk")
            ids = rng.integers(1, vocab - 1, size=plan.long_prompt).tolist()
            with meter.phase(f"{plan.long_prompt}-token prompt (chunked "
                             "prefill window kernel)", compiled) as rec:
                first = complete(url, plan, ids)
                rec["tokens"] += plan.max_tokens
            hits0 = metric(http_text(url + "/metrics"),
                           "tpuserve_prefix_cache_hits_total")
            with meter.phase("the same prompt again (prefix-hit prefill)",
                             compiled) as rec:
                again = complete(url, plan, ids)
                rec["tokens"] += plan.max_tokens
            check(first["usage"]["prompt_tokens"] == plan.long_prompt
                  == again["usage"]["prompt_tokens"], "prompt token count")
            deadline = time.monotonic() + 10
            while (metric(http_text(url + "/metrics"),
                          "tpuserve_prefix_cache_hits_total") <= hits0):
                check(time.monotonic() < deadline,
                      "the repeated prompt hit no cached prefix block")
                time.sleep(0.2)

        with meter.phase("short completions again", compiled) as rec:
            short_requests(rec)
        check(rec["compiled"] == 0, "a repeated short request compiled "
              f"{rec['compiled']} executable(s): shapes are not stable")

        page = http_text(url + "/metrics")
        check(metric(page, "vllm_generation_tokens_total") > 0,
              "/metrics counted no generated token")
        snap = http_json(url + "/debug/engine")
        ladder = snap["devprof"]["ladder"]
        kinds = {row["kind"] for row in ladder["executables"]
                 if row["hits"] > 0}
        say(f"executable ladder: {ladder['retained']} retained, "
            f"{ladder['compiles']} compiled in {ladder['compile_ms']} ms; "
            f"kinds called: {sorted(kinds)}")
        want = {"prefill", "decode_multi", "sample"}
        if plan.long_prompt:
            want.add("prefill_chunk")
        check(want <= kinds, f"never dispatched: {sorted(want - kinds)}")
        check(any(row["kind"] == "decode_multi"
                  and f", {plan.multi_step}, " in row["bucket"]
                  for row in ladder["executables"]),
              f"no {plan.multi_step}-step decode window ran")
    finally:
        server.shutdown()
    check(not server.runner._thread.is_alive(),
          "the engine loop thread outlived the server's shutdown")
    return engine


def greedy(engine, prompts, max_tokens: int):
    from tpuserve.runtime.request import SamplingParams
    return engine.generate(prompts, SamplingParams(
        max_tokens=max_tokens, temperature=0.0, ignore_eos=True, logprobs=5))


def compare_greedy(name: str, got, want) -> None:
    """Greedy streams of two attention implementations on the same
    weights.  bf16 makes bit equality the wrong test: while the contexts
    still agree the chosen token's logprob must agree within
    LOGPROB_ATOL, and where the streams first part the two candidates
    must be a near-tie (within TIE_ATOL) under the reference."""
    for i, (a, b) in enumerate(zip(got, want)):
        n = len(b.output_token_ids)
        check(len(a.output_token_ids) == n, f"{name}: stream lengths differ")
        first = next((j for j in range(n)
                      if a.output_token_ids[j] != b.output_token_ids[j]), n)
        worst = max((abs(a.logprobs[j]["logprob"] - b.logprobs[j]["logprob"])
                     for j in range(first)), default=0.0)
        check(worst <= LOGPROB_ATOL,
              f"{name} prompt {i}: logprob differs by {worst:.4f} "
              f"> {LOGPROB_ATOL} before any divergence")
        note = f"agree on all {n} tokens"
        if first < n:
            top = dict(b.logprobs[first]["top"])
            theirs = top.get(a.output_token_ids[first])
            check(theirs is not None,
                  f"{name} prompt {i}: diverges at token {first} to a token "
                  "outside the reference's top 5")
            gap = b.logprobs[first]["logprob"] - theirs
            check(gap <= TIE_ATOL,
                  f"{name} prompt {i}: diverges at token {first} by a "
                  f"logprob gap {gap:.4f} > {TIE_ATOL}")
            note = (f"first divergence at token {first}/{n}, a near-tie "
                    f"(gap {gap:.4f})")
        say(f"  {name} prompt {i}: {note}; max logprob diff before it "
            f"{worst:.4f}")


def side_engine(plan: Plan, params, mesh=None, **cfg):
    """A second engine over the SAME device-resident weights."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SchedulerConfig)
    cache = dict(plan.side_cache, **cfg.pop("cache", {}))
    return Engine(EngineConfig(
        model=plan.model, cache=CacheConfig(**cache),
        scheduler=SchedulerConfig(**cfg.pop("scheduler", {})), **cfg),
        params=params, mesh=mesh)


def prompts_for(engine, n: int, length: int = 24):
    import numpy as np
    rng = np.random.default_rng(2)
    vocab = engine.model_cfg.vocab_size
    return [rng.integers(1, vocab - 1, size=length).tolist()
            for _ in range(n)]


def greedy_phase(name: str, meter: Meter, engine, prompts, n: int):
    with meter.phase(name, Ladder(engine)) as rec:
        out = greedy(engine, prompts, n)
        rec["tokens"] += n * len(prompts)
    return out


def mixed_and_int8(plan: Plan, meter: Meter, params) -> None:
    from tpuserve.runtime.request import SamplingParams
    sp = SamplingParams(max_tokens=plan.max_tokens, temperature=0.0,
                        ignore_eos=True)

    eng = side_engine(plan, params, attn_impl="pallas",
                      scheduler=dict(mixed_batching=True))
    check(eng._ragged_attn == "pallas", "ragged attention is not pallas")
    prompts = prompts_for(eng, 4, length=40)
    with meter.phase("mixed ragged batching", Ladder(eng)) as rec:
        # two waves, so the second wave's prefill chunks share mixed steps
        # with the first wave's decode rows
        rids = [eng.add_request(prompt_token_ids=p, params=sp)
                for p in prompts[:2]]
        for _ in range(3):
            eng.step()
        rids += [eng.add_request(prompt_token_ids=p, params=sp)
                 for p in prompts[2:]]
        while eng.has_work():
            eng.step()
        for rid in rids:
            n = len(eng.requests.pop(rid).output_token_ids)
            check(n == plan.max_tokens, f"mixed: {n} tokens")
            rec["tokens"] += n
    check(eng.stats.num_mixed_steps > 0, "no mixed step ran")
    check(any(kind == "mixed" for kind, _ in eng.devprof.ladder),
          "the ragged executable was never dispatched")
    say(f"  mixed steps {eng.stats.num_mixed_steps}, decode steps "
        f"{eng.stats.num_decode_steps}")
    del eng
    gc.collect()

    eng = side_engine(plan, params, attn_impl="pallas",
                      cache=dict(dtype="int8"))
    check(eng.kv_cache[0]["k"].dtype.name == "int8", "the cache is not int8")
    prompts = prompts_for(eng, 3)
    n = plan.max_tokens // 2
    got = greedy_phase("int8 KV under pallas", meter, eng, prompts, n)
    del eng
    gc.collect()
    ref_eng = side_engine(plan, params, attn_impl="reference",
                          cache=dict(dtype="int8"))
    want = greedy_phase("int8 KV under reference", meter, ref_eng, prompts, n)
    compare_greedy("int8 KV pallas vs reference", got, want)


def check_sharding(engine) -> None:
    """No chip holds the whole model: each holds about a quarter of the
    weights and of the KV pages, and only small tensors are replicated."""
    import jax
    n = engine.mesh.size

    def per_device(tree):
        held: dict = {}
        for leaf in jax.tree.leaves(tree):
            for shard in leaf.addressable_shards:
                held[shard.device.id] = (held.get(shard.device.id, 0)
                                         + shard.data.nbytes)
        return held

    for name, tree in (("weights", engine.params), ("kv", engine.kv_cache)):
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        held = per_device(tree)
        say(f"{name}: {total / 2**20:.1f} MiB in all; per device "
            f"{ {d: round(b / 2**20, 1) for d, b in sorted(held.items())} }")
        check(len(held) == n, f"{name} live on {len(held)} of {n} devices")
        for dev, b in held.items():
            check(b <= 1.1 * total / n,
                  f"device {dev} holds {b / 2**20:.1f} MiB of {name}: more "
                  f"than its {n}th of {total / 2**20:.1f} MiB")
    replicated = [leaf for leaf in jax.tree.leaves(engine.params)
                  if leaf.sharding.is_fully_replicated]
    big = max((leaf.nbytes for leaf in replicated), default=0)
    check(big < (8 << 20), f"a {big / 2**20:.0f} MiB tensor is replicated")


def check_collectives(engine) -> None:
    """The compiled decode window must carry the tensor-parallel
    all-reduces (one after attention and one after the MLP per layer) and
    the Pallas kernel under shard_map."""
    import jax.numpy as jnp

    from tpuserve.models import transformer
    B, mb = 4, engine.cache_cfg.max_blocks_per_seq
    text = transformer.decode_multi.lower(
        engine.params, engine.model_cfg,
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, mb), jnp.int32), jnp.ones((B,), jnp.int32),
        jnp.zeros((B,), bool), jnp.zeros((B, 2), jnp.uint32),
        jnp.zeros((B,), jnp.float32), engine.kv_cache, None,
        steps=engine._multi_step, mode="greedy", attn_impl=engine.attn_impl,
        mesh=engine._attn_mesh, out_mesh=engine.mesh).compile().as_text()
    reduces = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    layers = engine.model_cfg.num_layers
    say(f"decode window: {reduces} all-reduce over {layers} layers")
    check(reduces >= 2 * layers, "fewer all-reduces than 2 per layer")
    check_kernels_in(text, layers)


def check_kernels_in(text: str, layers: int) -> None:
    kernels = text.count("tpu_custom_call")
    say(f"decode window: {kernels} Pallas custom call(s)")
    check(kernels >= layers, "the Pallas kernel is missing from the step")


def pallas_vs_reference(plan: Plan, meter: Meter, engine, mesh_checks=()):
    """The engine that just served HTTP against a reference-attention
    engine over the same device-resident weights.  Takes the only
    reference to ``engine`` (a one-item list) so its auto-sized cache is
    gone before the next one is built; returns the weights."""
    (eng,) = engine
    prompts = prompts_for(eng, 3)
    n = plan.max_tokens // 2
    got = greedy_phase("greedy under pallas", meter, eng, prompts, n)
    for fn in mesh_checks:
        fn(eng)
    params, mesh = eng.params, eng.mesh
    del eng
    engine.clear()
    gc.collect()
    ref_eng = side_engine(plan, params, mesh, attn_impl="reference")
    want = greedy_phase("greedy under reference", meter, ref_eng, prompts, n)
    compare_greedy("pallas vs reference", got, want)
    return params


def run_one_chip(plan: Plan, meter: Meter) -> None:
    build_native()
    compare_kernels(plan, meter)
    params = pallas_vs_reference(plan, meter, [serve(plan, meter)])
    gc.collect()
    mixed_and_int8(plan, meter, params)


def run_four_chip(plan: Plan, meter: Meter) -> None:
    build_native()
    pallas_vs_reference(plan, meter, [serve(plan, meter)],
                        mesh_checks=(check_sharding, check_collectives))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and what it is "
                         "compared with (default: 1)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device {device}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}")
    if device["platform"] != "tpu":
        say("FAILED: JAX found no TPU; this smoke runs on the chip only")
        return 1
    if device["count"] != args.chips:
        say(f"FAILED: --chips {args.chips} on a machine with "
            f"{device['count']} device(s)")
        return 1

    from tpuserve.utils import compile_cache
    cache_dir = compile_cache.configure()
    say(f"compile cache at {cache_dir}: "
        f"{compile_cache.entries(cache_dir)} entries before")
    meter = Meter(cache_dir)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chip(FOUR_CHIP, meter)
    else:
        run_one_chip(ONE_CHIP, meter)
    say(f"compile cache: {compile_cache.entries(cache_dir)} entries after, "
        f"{meter.hits} hits and {meter.misses} misses in this run; "
        f"{time.perf_counter() - t0:.0f}s in all")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
