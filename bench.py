#!/usr/bin/env python
"""Headline benchmark: continuous-batching decode throughput on one chip.

Runs the full serving engine path (scheduler -> paged KV cache -> jitted
bucketed prefill/decode -> on-device sampling; Pallas attention kernels on
TPU) on the flagship model Qwen3-0.6B — the reference's default served model
(reference: llm-d-deploy.yaml:118, llm-d-test.yaml:7) — and prints ONE JSON
line.  The baseline is the driver-defined north-star target of 2,000
tok/s/chip on v5e (BASELINE.md); the reference itself publishes no numbers
(SURVEY.md §6).

The bench runs in ONE process on the device JAX gives it and names that
device (platform, kind, count) in its JSON line.  A run without ``--smoke``
that finds no TPU exits non-zero and prints no result: a throughput taken
on the CPU backend is not a slower TPU number, it is a different
measurement.  ``--smoke`` is the CPU tiny path (control flow and counts
only).

Variants (all optional, main line unchanged without them):
  --spec K          speculative decoding (n-gram prompt lookup, k=K) on a
                    repetitive-prompt workload; adds a "spec" sub-object
  --compare-disagg  also run the same workload through the disaggregated
                    prefill/decode engine; adds a "disagg" sub-object
  --attn IMPL       force attention impl (auto|pallas|reference)
  --no-pipeline     disable pipelined decode (A/B the overlap win)

Usage: python bench.py [--batch N] [--prompt-len N] [--gen-len N] [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

TARGET_TOK_S_PER_CHIP = 2000.0  # BASELINE.md north-star target

# HBM bandwidth peaks by ``device_kind`` as JAX reports it, for the
# roofline fraction.  A device that is not here is an error, not a default.
HBM_GBS = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s per chip
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
}


def _hbm_gbs(device_kind: str) -> float:
    try:
        return HBM_GBS[device_kind]
    except KeyError:
        raise SystemExit(
            f"no HBM peak recorded for device kind {device_kind!r}; add it "
            "to bench.HBM_GBS with its source") from None


def _git_commit() -> str:
    """Short HEAD hash, stamped into every result row so a row says which
    code it measured."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() if out.returncode == 0 else "unknown"
    except Exception:
        return "unknown"


def _build_engine(model, batch, prompt_len, gen_len, *, attn_impl,
                  pipeline=None, spec_k=0, disagg=False,
                  prefix_caching=False, multi_step=None, quantization=None,
                  prefill_split=1, kv_quant=None, interleave=False,
                  adaptive_window=True, block_size=32, mixed=False,
                  mixed_budget=None, faults=None, num_blocks=None,
                  kv_tiers=None, max_num_seqs=None, flight=None):
    from tpuserve.runtime.engine import Engine, EngineConfig
    from tpuserve.runtime.kv_cache import CacheConfig
    from tpuserve.runtime.scheduler import SchedulerConfig

    max_len = prompt_len + gen_len
    blocks_per_seq = -(-max_len // block_size) + 1
    cache = CacheConfig(block_size=block_size,
                        num_blocks=(num_blocks if num_blocks is not None
                                    else batch * blocks_per_seq + 2 * batch),
                        max_blocks_per_seq=blocks_per_seq,
                        dtype=kv_quant or "bfloat16")
    # Admit the whole batch in ONE prefill step by default: queueing behind
    # 8-seq prefill batches is what dominates mean TTFT when all requests
    # arrive at once (and one big batch keeps the MXU busier than eight
    # small ones).  --prefill-split N trades that for p50: the first
    # batch's requests see first tokens ~N× sooner while the last batch
    # pays an extra dispatch round-trip.
    seqs_per_batch = max(1, batch // max(1, prefill_split))
    sched = SchedulerConfig(max_num_seqs=max_num_seqs or batch,
                            max_prefill_seqs=seqs_per_batch,
                            max_prefill_tokens=max(
                                8192 // max(1, prefill_split),
                                seqs_per_batch * prompt_len),
                            interleave_batched_prefill=interleave,
                            mixed_batching=mixed,
                            **({"mixed_token_budget": mixed_budget}
                               if mixed_budget else {}))
    spec = None
    if spec_k:
        from tpuserve.runtime.spec import SpecConfig
        spec = SpecConfig(num_draft_tokens=spec_k)
    cfg = EngineConfig(model=model, cache=cache, scheduler=sched,
                       attn_impl=attn_impl, enable_prefix_caching=prefix_caching,
                       pipeline_decode=pipeline, speculative=spec,
                       multi_step=multi_step, quantization=quantization,
                       adaptive_multi_step=adaptive_window,
                       kv_tiers=kv_tiers, faults=faults, flight=flight)
    if disagg:
        from tpuserve.parallel.disagg import DisaggregatedEngine
        return DisaggregatedEngine(cfg, cfg)
    return Engine(cfg)


def _warm_plan_arrivals(eng, batch, prompt_len):
    """Warmup plan for staggered (Poisson) arrivals: prefill batches can be
    any size from 1 up to the admission limit (arrivals trickle in), and
    the decode batch grows/shrinks through every bucket, so warm the full
    power-of-two ladder of both — up to and INCLUDING the padded bucket of
    a full admission batch (the engine pads the picked count to a power of
    two, which can exceed the admission limit itself).  A handful of extra
    tiny compiles at startup beats a recompile landing inside a measured
    TTFT."""
    from tpuserve.utils import next_power_of_2
    cfg = eng.scheduler.cfg
    if prompt_len > cfg.prefill_chunk_size:
        # chunked-prefill route: the burst plan already warms every chunk
        # bucket and the full 1..batch decode ladder; no batched-prefill
        # shape ever dispatches
        return _warm_plan(eng, batch, prompt_len)
    L = eng.scheduler.prefill_bucket(prompt_len)
    per = min(batch, cfg.max_prefill_seqs,
              max(1, cfg.max_prefill_tokens // L))
    buckets, b = [], 1
    while b <= next_power_of_2(per):
        buckets.append((b, L))
        b *= 2
    decode = sorted({eng.scheduler.decode_bucket(n)
                     for n in range(1, batch + 1)})
    return dict(prefill_buckets=buckets, decode_buckets=decode)


def _warm_plan(eng, batch, prompt_len):
    """Every executable shape the scheduler will actually dispatch for this
    uniform-prompt workload, derived with the scheduler's own admission
    arithmetic — any shape missed here recompiles inside the timed region
    (the 53 s phantom-TTFT failure mode).  Returns a dict of warmup
    kwargs.

    Short prompts: batched prefill in admission-sized batches (bucketed
    per-seq token charge against max_prefill_tokens / max_prefill_seqs),
    including the leftover batch of a non-dividing split; one decode
    bucket (prefill-priority admits the whole burst before decode starts).

    Long prompts (> prefill_chunk_size): NO batched-prefill shape (the
    chunked path never dispatches one) but every chunk bucket including
    the padded tail of a non-multiple length, and every decode bucket from
    1..batch — the scheduler interleaves decode steps between chunks while
    the running set grows."""
    from tpuserve.utils import next_power_of_2
    cfg = eng.scheduler.cfg
    if prompt_len > cfg.prefill_chunk_size:
        chunks, remaining = set(), prompt_len
        while remaining > 0:
            # the scheduler's own padding policy — one source of truth
            b = eng.scheduler._chunk_bucket(remaining)
            chunks.add(b)
            remaining -= min(remaining, b)
        decode = sorted({eng.scheduler.decode_bucket(n)
                         for n in range(1, batch + 1)})
        return dict(prefill_buckets=[], chunk_buckets=sorted(chunks),
                    decode_buckets=decode)
    L = eng.scheduler.prefill_bucket(prompt_len)
    per = min(batch, cfg.max_prefill_seqs,
              max(1, cfg.max_prefill_tokens // L))
    buckets = {next_power_of_2(per)}
    if batch % per:
        buckets.add(next_power_of_2(batch % per))
    if cfg.interleave_batched_prefill:
        # decode steps run BETWEEN admission batches at partial running
        # sizes — warm the whole ladder or those shapes compile inside
        # the timed region
        decode = sorted({eng.scheduler.decode_bucket(n)
                         for n in range(1, batch + 1)})
    else:
        # prefill-priority admits the whole burst before decode starts
        decode = [eng.scheduler.decode_bucket(batch)]
    return dict(prefill_buckets=[(b, L) for b in sorted(buckets)],
                decode_buckets=decode)


def _warm(engine, batch, prompt_len, arrivals=False,
          modes=("greedy",)):
    """Pre-compile the exact bucket set the measured run will hit
    (SURVEY.md §7: TTFT budget requires AOT warmup).  ``modes``: the
    sampler executables to warm — a sampled bench (--temperature /
    --top-p) dispatches temperature/full windows, not greedy ones."""
    plan = _warm_plan_arrivals if arrivals else _warm_plan
    eng = getattr(engine, "prefill", engine)      # disagg: warm both halves
    kw = plan(eng, batch, prompt_len)
    if eng.scheduler.cfg.mixed_batching:
        # Engine.warmup auto-derives the mixed flat-token ladder AND the
        # full decode ladder (staggered admission staggers finishes into
        # partial tail buckets) when these are left unpinned — so drop
        # the plan's single decode bucket and let the engine own it
        kw.pop("decode_buckets", None)
    eng.warmup(sample_modes=modes, **kw)
    if eng is not engine:
        engine.decode.warmup(sample_modes=modes,
                             **plan(engine.decode, batch, prompt_len))


def _run_workload(engine, prompts, params, arrival_offsets=None):
    """Feed all prompts, drain, and split wall time into prefill/decode.
    Token counts are deltas from the engine's counters at entry, so the
    workload can be repeated on one engine (``--repeat``/median runs).

    ``arrival_offsets`` (seconds from workload start, one per prompt,
    ascending) switches from the all-at-once burst — the worst case for
    p50 TTFT, since every request queues behind a full batch of prefill —
    to a timed arrival process: each request is added when its offset
    passes, so TTFT measures what a client arriving into a *busy* engine
    sees rather than what the last member of a stampede sees."""
    stats = getattr(engine, "decode", engine).stats  # disagg: decode engine
    pstats = getattr(engine, "prefill", engine).stats
    gen0 = stats.generated_tokens + (pstats.generated_tokens
                                     if pstats is not stats else 0)
    before = {k: getattr(stats, k) for k in
              ("num_decode_steps", "spec_steps", "spec_proposed",
               "spec_accepted", "latency_windows")}
    rids = []
    pending = None
    # rid -> intended arrival on the monotonic clock.  Arrivals are only
    # admitted between engine steps (a fused window blocks for its whole
    # duration), so add_request can run a full window AFTER the offset
    # passed — TTFT must count that queueing delay from the INTENDED
    # arrival, or multi-step serving systematically understates it.
    intended: dict = {}
    if arrival_offsets is None:
        rids = [engine.add_request(prompt_token_ids=p, params=params)
                for p in prompts]
    else:
        pending = list(zip(arrival_offsets, prompts))
    t_start = time.perf_counter()
    t_start_mono = time.monotonic()
    prefill_time = decode_time = 0.0
    # client-observed inter-token latency: wall gap between consecutive
    # token emissions per stream (the p99 of this is what mixed batching
    # exists to bound — strict prefill-priority stalls every stream for a
    # whole admission burst).  A re-prefill after preemption resets the
    # clock (its gap is queue+recompute, not ITL — RequestOutput doc).
    last_tok: dict = {}
    itls: list = []
    while True:
        if pending:
            now = time.perf_counter() - t_start
            while pending and pending[0][0] <= now:
                off, p = pending.pop(0)
                rid = engine.add_request(prompt_token_ids=p, params=params)
                rids.append(rid)
                intended[rid] = t_start_mono + off
        if not engine.has_work():
            if not pending:
                break
            # idle until the next arrival — wall time the engine spends
            # waiting for offered load, not engine cost
            time.sleep(max(0.0, pending[0][0]
                           - (time.perf_counter() - t_start)))
            continue
        d0 = stats.num_decode_steps
        t0 = time.perf_counter()
        outs = engine.step()
        dt = time.perf_counter() - t0
        t_emit = time.perf_counter()
        for o in outs:
            if o.from_prefill and o.num_output_tokens > 1:
                last_tok[o.request_id] = t_emit      # re-prefill: reset
                continue
            prev = last_tok.get(o.request_id)
            if prev is not None:
                itls.append(t_emit - prev)
            last_tok[o.request_id] = t_emit
        # A drain step that only flushes the last pipelined window runs no
        # NEW decode steps (d0 unchanged) but blocks on a full window of
        # decode compute — classify by what the step emitted, not just by
        # the dispatch counter, or the final window lands in prefill_time
        # and inflates decode tok/s.
        if (stats.num_decode_steps > d0
                or any(not o.from_prefill for o in outs)):
            decode_time += dt
        else:
            prefill_time += dt
    total = time.perf_counter() - t_start
    gen = stats.generated_tokens + (pstats.generated_tokens
                                    if pstats is not stats else 0) - gen0
    reqs = getattr(engine, "requests", {})
    ttfts_ms = sorted(
        1000.0 * (rq.first_token_time
                  - intended.get(rid, rq.arrival_time))
        for rid, rq in ((rid, reqs.get(rid)) for rid in rids)
        if rq is not None and rq.first_token_time is not None)
    deltas = {k: getattr(stats, k) - v for k, v in before.items()}
    return {"total_s": total, "prefill_s": prefill_time,
            "decode_s": decode_time, "gen_tokens": gen,
            "ttfts_ms": ttfts_ms,
            "itls_ms": sorted(1000.0 * x for x in itls),
            "stats": stats, "pstats": pstats,
            **deltas}


def _runner_workload(engine, prompts, params, timeout=600.0):
    """Drive the workload through AsyncEngineRunner — the crash-only
    salvage path lives in the runner, so a faulted engine must be measured
    behind it, not via bare engine.step() (where an injected fault would
    just crash the bench).  Returns (wall_s, failed_requests)."""
    from tpuserve.server.runner import AsyncEngineRunner
    runner = AsyncEngineRunner(engine)
    runner.start()
    t0 = time.perf_counter()
    subs = [runner.submit(prompt_token_ids=p, params=params)
            for p in prompts]
    failed = 0
    for rid, q in subs:
        while True:
            item = q.get(timeout=timeout)
            if item is None:
                break
            if isinstance(item, Exception):
                failed += 1
        getattr(engine, "requests", {}).pop(rid, None)
    wall = time.perf_counter() - t0
    runner.shutdown()
    return wall, failed


def _canary_runner_workload(engine, prompts, params, interval_s=0.25,
                            timeout=600.0):
    """ON arm of --canary-ab: the identical soak behind AsyncEngineRunner,
    but with the in-process SLO burn-rate evaluator armed and a
    prober-equivalent thread injecting tagged tiny canary requests
    through the same intake at ``interval_s`` — the full per-request
    cost of the canary feature (exclusion checks, evaluator feed, probe
    traffic) measured against the plain soak.  Returns (wall_s,
    failed, canaries_served)."""
    import threading as _threading

    from tpuserve.obs import BurnRateEvaluator, DEFAULT_OBJECTIVES
    from tpuserve.server.runner import AsyncEngineRunner
    from tpuserve.runtime.request import SamplingParams as _SP
    runner = AsyncEngineRunner(engine)
    runner.slo_eval = BurnRateEvaluator(DEFAULT_OBJECTIVES,
                                        clock=runner._clock)
    runner.start()
    stop = _threading.Event()
    served = [0]

    def prober():
        classes = ("interactive", "standard", "batch")
        i = 0
        while not stop.wait(interval_s):
            cp = _SP(max_tokens=2, temperature=0.0, ignore_eos=True,
                     slo_class=classes[i % 3], canary=True)
            i += 1
            try:
                rid, q = runner.submit(prompt_token_ids=[1, 2, 3, 4],
                                       params=cp)
                while True:
                    item = q.get(timeout=timeout)
                    if item is None or isinstance(item, Exception):
                        break
                getattr(engine, "requests", {}).pop(rid, None)
                served[0] += 1
            except Exception:
                pass

    thread = _threading.Thread(target=prober, daemon=True)
    thread.start()
    t0 = time.perf_counter()
    subs = [runner.submit(prompt_token_ids=p, params=params)
            for p in prompts]
    failed = 0
    for rid, q in subs:
        while True:
            item = q.get(timeout=timeout)
            if item is None:
                break
            if isinstance(item, Exception):
                failed += 1
        getattr(engine, "requests", {}).pop(rid, None)
    wall = time.perf_counter() - t0
    stop.set()
    thread.join(timeout=10)
    runner.shutdown()
    return wall, failed, served[0]


def _pct(sorted_ms, q):
    if not sorted_ms:
        return 0.0
    return sorted_ms[min(len(sorted_ms) - 1, int(len(sorted_ms) * q))]


def _compare_mixed(args, model, batch, prompt_len, gen_len, on_tpu, *,
                   attn_impl, pipeline, vocab, warm_modes):
    """A/B: phase-split vs mixed ragged batching (ISSUE 3 acceptance).

    Rows sweep the prefill:decode ratio under the SAME fixed-seed Poisson
    arrival sample path, reporting client-observed p50/p99 inter-token
    latency — the quantity strict prefill-priority lets admission bursts
    blow up and mixed batching bounds at one step.  A pure-decode burst
    row guards the trade: with no admissible prefill, mixed mode must
    fall through to the plain decode path (fused windows intact), so its
    throughput must stay ~1.0x of phase-split."""
    import numpy as np

    from tpuserve.runtime.request import SamplingParams

    # ratio sweep shapes scale with the main workload's sizes; arrivals
    # must keep landing while early streams decode (sustained admission),
    # so the CPU rate is far higher than the TPU default — tiny-model CPU
    # steps are ~5 ms, and an arrival every 60 ms would never contend
    rate = args.arrival_rate if on_tpu else max(args.arrival_rate, 150.0)
    n_req = batch if on_tpu else max(batch, 32)
    budget = args.mixed_budget or 256
    ratios = [(prompt_len * 2, max(gen_len // 2, 4)),
              (prompt_len, gen_len),
              (max(prompt_len // 2, 4), gen_len * 2)]
    rows = []

    def run_one(mixed, prompts, params, offsets, pl_, repeat=1):
        eng = _build_engine(model, n_req, pl_, params.max_tokens,
                            attn_impl=attn_impl, pipeline=pipeline,
                            multi_step=args.multi_step,
                            quantization=args.quant,
                            kv_quant=args.kv_quant,
                            block_size=args.block_size, mixed=mixed,
                            mixed_budget=budget)
        _warm(eng, n_req, pl_, arrivals=offsets is not None,
              modes=warm_modes)
        runs = [_run_workload(eng, prompts, params,
                              arrival_offsets=offsets)
                for _ in range(repeat)]

        def _rate(x):
            return ((x["gen_tokens"] - len(prompts)) / x["decode_s"]
                    if x["decode_s"] else 0.0)

        r = sorted(runs, key=_rate)[len(runs) // 2]
        return {
            "p50_itl_ms": round(_pct(r["itls_ms"], 0.50), 2),
            "p99_itl_ms": round(_pct(r["itls_ms"], 0.99), 2),
            "decode_tok_s": round(_rate(r), 1),
            "e2e_tok_s": round(r["gen_tokens"] / r["total_s"], 1),
            "ttft_p50_ms": round(_pct(r["ttfts_ms"], 0.50), 1),
            "padding_efficiency": round(
                eng.stats.actual_tokens_total
                / max(eng.stats.padded_tokens_total, 1), 3),
            "mixed_steps": eng.stats.num_mixed_steps,
        }

    for pl_, gl_ in ratios:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, vocab - 1, size=pl_).tolist()
                   for _ in range(n_req)]
        offsets = np.cumsum(np.random.default_rng(7).exponential(
            1.0 / rate, size=n_req)).tolist()
        params = SamplingParams(max_tokens=gl_, temperature=0.0, seed=0,
                                ignore_eos=True)
        base = run_one(False, prompts, params, offsets, pl_)
        mix = run_one(True, prompts, params, offsets, pl_)
        rows.append({
            "prompt_len": pl_, "gen_len": gl_,
            "phase_split": base, "mixed": mix,
            "p99_itl_improvement": round(
                base["p99_itl_ms"] / mix["p99_itl_ms"], 2)
                if mix["p99_itl_ms"] else 0.0,
        })

    # pure-decode guard: short-prompt burst + long generation, so
    # admission is over within a step or two and >95% of decode-
    # classified time is TRUE decode steps for both engines (with no
    # admissible prefill, mixed mode falls through to the plain decode
    # path — fused windows and all).  A long-prompt burst would instead
    # measure mixed ADMISSION against batched prefill: mixed admission
    # steps carry decode rows, get classified as decode time, and would
    # masquerade as a decode regression.  Median-of-5: shared-host CPU
    # step-time noise is ~±7%, well above the ~2% structural cost
    # (mixed's budget-staggered admission staggers finishes, adding a
    # couple of partial-bucket tail steps).
    pl_p, gl_p = min(prompt_len, 16), max(2 * gen_len, 128)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, vocab - 1, size=pl_p).tolist()
               for _ in range(n_req)]
    params = SamplingParams(max_tokens=gl_p, temperature=0.0, seed=0,
                            ignore_eos=True)
    base = run_one(False, prompts, params, None, pl_p, repeat=5)
    mix = run_one(True, prompts, params, None, pl_p, repeat=5)
    return {
        "arrival_rate_req_s": rate,
        "num_requests": n_req,
        "mixed_token_budget": budget,
        "rows": rows,
        "pure_decode": {
            "phase_split_tok_s": base["decode_tok_s"],
            "mixed_tok_s": mix["decode_tok_s"],
            "ratio": round(mix["decode_tok_s"]
                           / max(base["decode_tok_s"], 1e-9), 3),
        },
    }


def _host_overhead_sweep(args, model, prompt_len, gen_len, *,
                         attn_impl, pipeline, warm_modes):
    """Client-count-scaled host-overhead rows (ROADMAP open item 3 /
    DeepServe's host-side scaling wall): one engine per stream count, the
    same burst workload, with the host phase profiler armed — reporting
    decode tok/s AND host-ms-per-cycle (schedule + block accounting +
    detokenize/emit, the phases this repo moved off per-request Python)
    per count.  Host overhead grows with concurrent streams while device
    time per cycle stays ~flat, so this is the number that says whether
    the host loop is back on the critical path."""
    import numpy as np

    from tpuserve.runtime.hostprof import PROF
    from tpuserve.runtime.request import SamplingParams
    counts = [int(c) for c in args.clients_sweep.split(",") if c.strip()]
    rows = []
    bm_name = ""
    host_batched = True
    for n in counts:
        eng = _build_engine(model, n, prompt_len, gen_len,
                            attn_impl=attn_impl, pipeline=pipeline,
                            multi_step=args.multi_step,
                            quantization=args.quant,
                            kv_quant=args.kv_quant,
                            block_size=args.block_size)
        bm_name = type(eng.block_manager).__name__
        host_batched = eng._host_batched   # the engine's own resolved mode
        _warm(eng, n, prompt_len, modes=warm_modes)
        rng = np.random.default_rng(0)
        vocab = eng.model_cfg.vocab_size
        prompts = [rng.integers(1, vocab - 1, size=prompt_len).tolist()
                   for _ in range(n)]
        params = SamplingParams(max_tokens=gen_len,
                                temperature=args.temperature,
                                top_p=args.top_p, seed=0, ignore_eos=True)
        PROF.reset()
        PROF.enabled = True
        try:
            r = _run_workload(eng, prompts, params)
        finally:
            PROF.enabled = False
        rep = PROF.report()
        phases = {k: v["ms_per_cycle"] for k, v in rep["phases"].items()}
        dec = r["gen_tokens"] - n
        rows.append({
            "clients": n,
            "decode_tok_s": round(dec / r["decode_s"], 1)
                            if r["decode_s"] else 0.0,
            # pure-host phases only (dispatch/flush include device wait)
            "host_ms_per_cycle": rep["host_ms_per_cycle"],
            "phases_ms_per_cycle": phases,
            "cycles": rep["cycles"],
        })
    return {
        "block_manager": bm_name,
        "host_batched": host_batched,
        "rows": rows,
    }


def _multiturn_workload(engine, sys_ids, user_ids, turns, gen_per_turn,
                        rate, think_s, seed=7):
    """Shared-system-prompt Poisson conversation mix (ISSUE 7 workload):
    every conversation opens with the SAME system prompt, then alternates
    user turns and generations; a conversation's next turn arrives an
    exponential think time after its previous turn completes.  Between a
    conversation's turns its KV goes cold — at an HBM budget below the
    working set it gets EVICTED, and turn>=2 TTFT measures what the
    tiered cache (demote + async restore) saves vs re-prefilling the
    whole history.

    Returns per-turn TTFT percentiles, ITL percentiles, the engine's
    prefix-hit rate over the run, and the tier flow counters."""
    import bisect

    import numpy as np

    from tpuserve.runtime.request import SamplingParams
    rng = np.random.default_rng(seed)
    C = len(user_ids)
    params = SamplingParams(max_tokens=gen_per_turn, temperature=0.0,
                            seed=0, ignore_eos=True)
    hist = [list(sys_ids) + list(user_ids[c][0]) for c in range(C)]
    pending = sorted(
        (float(t), c) for c, t in
        enumerate(np.cumsum(rng.exponential(1.0 / rate, size=C))))
    turn_idx = [0] * C
    live: dict = {}            # rid -> (conv, intended_mono, turn)
    ttfts = [[] for _ in range(turns)]
    itls: list = []
    last_tok: dict = {}
    bm = engine.block_manager
    q0, h0 = bm.prefix_queries, bm.prefix_hits
    stats = engine.stats
    gen0, d0 = stats.generated_tokens, stats.num_decode_steps
    t_start = time.perf_counter()
    t_mono = time.monotonic()
    decode_time = 0.0
    done = 0
    while done < C * turns:
        now = time.perf_counter() - t_start
        while pending and pending[0][0] <= now:
            off, c = pending.pop(0)
            rid = engine.add_request(prompt_token_ids=list(hist[c]),
                                     params=params)
            live[rid] = (c, t_mono + off, turn_idx[c])
        if not engine.has_work():
            if not pending:
                break          # stragglers only finish via step outputs
            time.sleep(max(0.0, pending[0][0]
                           - (time.perf_counter() - t_start)))
            continue
        dsteps = stats.num_decode_steps
        t0 = time.perf_counter()
        outs = engine.step()
        dt = time.perf_counter() - t0
        t_emit = time.perf_counter()
        if (stats.num_decode_steps > dsteps
                or any(not o.from_prefill for o in outs)):
            decode_time += dt
        for o in outs:
            if o.from_prefill and o.num_output_tokens > 1:
                last_tok[o.request_id] = t_emit      # re-prefill: reset
            else:
                prev = last_tok.get(o.request_id)
                if prev is not None:
                    itls.append(t_emit - prev)
                last_tok[o.request_id] = t_emit
            if o.finished and o.request_id in live:
                c, intended, ti = live.pop(o.request_id)
                req = engine.requests.pop(o.request_id)
                last_tok.pop(o.request_id, None)
                if req.first_token_time is not None:
                    ttfts[ti].append(
                        1000.0 * (req.first_token_time - intended))
                hist[c].extend(req.output_token_ids)
                turn_idx[c] += 1
                done += 1
                if turn_idx[c] < turns:
                    hist[c].extend(user_ids[c][turn_idx[c]])
                    # exponential THINK time before the next turn: the
                    # cold gap in which this conversation's KV is at the
                    # mercy of other conversations' HBM pressure
                    nxt = (time.perf_counter() - t_start
                           + float(rng.exponential(think_s)))
                    bisect.insort(pending, (nxt, c))
    total = time.perf_counter() - t_start
    queries = bm.prefix_queries - q0
    gen = stats.generated_tokens - gen0
    return {
        "total_s": round(total, 3),
        "turns_completed": done,
        "ttft_by_turn": [
            {"turn": i + 1, "n": len(t),
             "p50_ms": round(_pct(sorted(t), 0.50), 1),
             "p95_ms": round(_pct(sorted(t), 0.95), 1)}
            for i, t in enumerate(ttfts)],
        "itl_p50_ms": round(_pct(sorted(1000.0 * x for x in itls), 0.50), 2),
        "itl_p99_ms": round(_pct(sorted(1000.0 * x for x in itls), 0.99), 2),
        "prefix_hit_rate": round((bm.prefix_hits - h0) / queries, 3)
                           if queries else 0.0,
        "prefix_queries": queries,
        "decode_tok_s": round((gen - done) / decode_time, 1)
                        if decode_time else 0.0,
        "kv": {"demoted": stats.kv_demoted_blocks,
               "restored": stats.kv_restored_blocks,
               "restores": stats.kv_restores,
               "spilled": stats.kv_spilled_blocks,
               "dropped": stats.kv_tier_dropped_blocks,
               "preemptions": stats.preemptions},
    }


def _multiturn_ab(args, model, on_tpu, *, attn_impl, pipeline, vocab):
    """Tiered-vs-HBM-only A/B on the multi-turn shared-prefix workload
    (ISSUE 7 acceptance): both engines run the SAME fixed-seed
    conversation mix at an HBM block budget ~40% of the conversation
    working set, so cold prefixes must leave HBM — the tiered engine
    demotes and restores them, the legacy engine re-prefills.  Rows under
    TPUSERVE_KV_TIERS=0 (the kv-tiers-legacy sweep variant) skip the
    tiered half: the env kill switch would silently neuter it."""
    import numpy as np

    from tpuserve.utils import env_flag, next_power_of_2

    turns = args.turns
    if on_tpu:
        C, sys_len, user_len, gen_per = 32, 512, 128, 64
        rate = args.arrival_rate
    else:
        C, sys_len, user_len, gen_per = 16, 128, 48, 16
        rate = max(args.arrival_rate, 50.0)
    rng = np.random.default_rng(11)
    sys_ids = rng.integers(1, vocab - 1, size=sys_len).tolist()
    user_ids = [[rng.integers(1, vocab - 1, size=user_len).tolist()
                 for _ in range(turns)] for _ in range(C)]
    conv_len = sys_len + turns * (user_len + gen_per)
    block = args.block_size
    blocks_per_conv = -(-conv_len // block) + 2
    seqs = min(C, 8 if on_tpu else 4)
    # HBM forced under the working set: every concurrent conversation
    # fits (serving stays correct), but the UNIQUE hashed working set —
    # the shared system prompt counts once, each conversation's own
    # full history blocks once — does not, so cold conversations'
    # prefixes must leave HBM between turns
    sys_blocks = sys_len // block
    unique_ws = sys_blocks + C * (conv_len // block - sys_blocks)
    num_blocks = max(seqs * blocks_per_conv + 4, int(0.5 * unique_ws))

    def build(tiers):
        eng = _build_engine(
            model, seqs, conv_len, gen_per, attn_impl=attn_impl,
            pipeline=pipeline, multi_step=args.multi_step,
            quantization=args.quant, kv_quant=args.kv_quant,
            block_size=block, prefix_caching=True, kv_tiers=tiers,
            num_blocks=num_blocks, max_num_seqs=seqs)
        # staggered-arrival bucket ladder over the GROWING conversation
        # lengths: power-of-two prompt buckets from the first turn up to
        # the chunk size (longer prompts route through chunked prefill),
        # small admission batches, the full decode ladder
        cfg = eng.scheduler.cfg
        L = eng.scheduler.prefill_bucket(sys_len + user_len)
        top = next_power_of_2(min(conv_len, cfg.prefill_chunk_size))
        admit = next_power_of_2(min(seqs, cfg.max_prefill_seqs))
        buckets = []
        while L <= top:
            b = 1
            while b <= admit:        # clustered turn arrivals batch up to
                buckets.append((b, L))   # the admission limit — warm the
                b *= 2                   # whole (batch, len) grid
            L *= 2
        # later turns carry a SUBSTANTIAL cached prefix and route through
        # chunk-by-choice prefill (scheduler._schedule_prefill), whose
        # padded suffix buckets are small powers of two — left cold, the
        # first turn-2 request stalls the whole arrival cluster on an
        # _exec_prefill_chunk compile
        chunked, cb = [], cfg.min_prefill_bucket
        while cb <= min(next_power_of_2(conv_len), cfg.prefill_chunk_size):
            chunked.append(cb)
            cb *= 2
        eng.warmup(prefill_buckets=buckets,
                   decode_buckets=sorted(
                       {eng.scheduler.decode_bucket(n)
                        for n in range(1, seqs + 1)}),
                   chunk_buckets=chunked, sample_modes=("greedy",))
        return eng

    # mean think time between a conversation's turns: the whole herd
    # cycles while one conversation is cold, so its prefix experiences
    # the full fleet's HBM pressure — the reuse pattern the tier exists
    # for (20 ms think times never let anything go cold)
    think_s = C / rate
    out = {"conversations": C, "turns": turns, "system_prompt_len": sys_len,
           "user_turn_len": user_len, "gen_per_turn": gen_per,
           "conv_len": conv_len, "num_blocks": num_blocks,
           "working_set_blocks": unique_ws,
           "arrival_rate_req_s": rate, "think_mean_s": round(think_s, 3)}
    legacy_env = not env_flag("TPUSERVE_KV_TIERS")
    if legacy_env:
        out["legacy_only"] = ("TPUSERVE_KV_TIERS=0 in the environment: "
                              "tiered half skipped")
    else:
        eng_t = build(True)
        out["tiered"] = _multiturn_workload(eng_t, sys_ids, user_ids,
                                            turns, gen_per, rate, think_s)
    eng_l = build(False)
    out["hbm_only"] = _multiturn_workload(eng_l, sys_ids, user_ids,
                                          turns, gen_per, rate, think_s)
    if "tiered" in out:
        def p50_reused(r):
            vals = [t["p50_ms"] for t in r["ttft_by_turn"][1:] if t["n"]]
            return sum(vals) / len(vals) if vals else 0.0
        base, tier = p50_reused(out["hbm_only"]), p50_reused(out["tiered"])
        out["ttft_turn2plus_improvement"] = (round(base / tier, 2)
                                             if tier else 0.0)
    return out


def _model_mix_ab(args, on_tpu, *, attn_impl, pipeline):
    """Model-pool hot-swap A/B (ISSUE 17 acceptance): N=3 tiny models
    share ONE replica's HBM budget while a fixed-seed Poisson request
    stream names models from a skewed mix.  Consecutive same-model
    requests serve as one burst; each model change point is a pool
    hot-swap at the idle boundary (drain -> demote streamed to the host
    tier -> restore -> rebuild the ladder), and the change-point
    request's swap-to-first-token is recorded split by source tier: the
    FIRST visit to a model is a cold checkpoint load + XLA compile,
    every revisit restores from the host weight tier into warm jit
    caches.  The tail collapses the mix to one model and measures
    steady-state decode throughput through the pool-carrying engine vs
    a plain engine built without any pool — the pool must cost nothing
    when only one model is in play.  Under TPUSERVE_MODELPOOL=0 (the
    model-mix-static sweep row) the pooled half is skipped: the static
    fleet's only model-change move — a full engine rebuild + warmup,
    the reference's one-model-per-Deployment redeploy
    (kubernetes-single-node.yaml:14) — is what the static half times."""
    import numpy as np

    from tpuserve.modelpool import ModelPool, ModelPoolConfig, pool_enabled
    from tpuserve.runtime.request import SamplingParams

    models = ["tiny-qwen3", "tiny-llama", "tiny-opt"]
    mix = [0.5, 0.3, 0.2]
    R = 36
    batch, prompt_len, gen_len = (8, 64, 32) if on_tpu else (4, 32, 16)
    rng = np.random.default_rng(17)
    # arrival ORDER of a Poisson process thinned per model: each request
    # independently names a model from the skewed mix; runs of equal
    # draws serve as one burst, so the number and spacing of change
    # points (= swaps) is itself workload-random
    draws = rng.choice(len(models), size=R, p=mix)
    groups: list = []
    for d in draws:
        if groups and groups[-1][0] == int(d):
            groups[-1][1] += 1
        else:
            groups.append([int(d), 1])
    params = SamplingParams(max_tokens=gen_len, temperature=0.0,
                            seed=0, ignore_eos=True)

    def build(name):
        eng = _build_engine(name, batch, prompt_len, gen_len,
                            attn_impl=attn_impl, pipeline=pipeline,
                            multi_step=args.multi_step,
                            block_size=args.block_size)
        _warm(eng, batch, prompt_len)
        return eng

    def drain(eng, rids, t0=None):
        """Step until idle; return the first-token wall time of this
        burst (None if t0 is None)."""
        first = None
        while eng.has_work():
            for o in eng.step():
                if first is None and o.num_output_tokens:
                    first = time.perf_counter()
                if o.finished:
                    eng.requests.pop(o.request_id, None)
        return None if t0 is None else first

    def submit(eng, n):
        # tiny-model vocab is 256; ids in [1, 200) are valid everywhere
        return [eng.add_request(
            prompt_token_ids=rng.integers(
                1, 200, size=prompt_len).tolist(),
            params=params) for _ in range(n)]

    def tput(eng):
        """Steady-state decode tok/s of one full burst (prefill's first
        tokens excluded from the numerator)."""
        submit(eng, batch)
        g0 = eng.stats.generated_tokens
        t0 = time.perf_counter()
        drain(eng, None)
        dt = time.perf_counter() - t0
        return (eng.stats.generated_tokens - g0 - batch) / dt if dt else 0.0

    out = {"models": models, "mix": mix, "requests": R,
           "burst_size": batch, "prompt_len": prompt_len,
           "gen_len": gen_len,
           "change_points": sum(1 for i in range(1, len(groups))
                                if groups[i][0] != groups[i - 1][0])}
    static_env = not pool_enabled()
    if static_env:
        out["static_only"] = ("TPUSERVE_MODELPOOL=0 in the environment: "
                              "pooled half skipped")
    else:
        eng = build(models[0])
        pool = ModelPool(eng.config, ModelPoolConfig(
            catalog={m: None for m in models}))
        swap_ms: list = []                  # (source tier, ms to token)
        for midx, n in groups:
            name = models[midx]
            t0 = time.perf_counter()
            outcome = None
            if name != pool.current:
                pool.request_swap(name)
                outcome = pool.maybe_swap(eng)
            submit(eng, n)
            first = drain(eng, None, t0)
            if outcome is not None and first is not None:
                swap_ms.append((outcome, 1000.0 * (first - t0)))

        def pcts(kinds):
            sel = sorted(ms for k, ms in swap_ms if k in kinds)
            return {"n": len(sel), "p50_ms": round(_pct(sel, 0.50), 1),
                    "p95_ms": round(_pct(sel, 0.95), 1)}
        # collapse the mix to the base model: one unmeasured burst
        # re-warms post-swap state, the second is the measured tail
        pool.request_swap(models[0])
        pool.maybe_swap(eng)
        tput(eng)
        pooled_tok_s = tput(eng)
        outcomes: dict = {}
        for k, _ in swap_ms:
            outcomes[k] = outcomes.get(k, 0) + 1
        cold = pcts(("cold",))
        warm = pcts(("host", "spill", "resident"))
        out["pooled"] = {
            "swaps": len(swap_ms),
            "swap_outcomes": outcomes,
            "cold_swap_to_first_token_ms": cold,
            "warm_swap_to_first_token_ms": warm,
            "collapsed_decode_tok_s": round(pooled_tok_s, 1),
        }
        if warm["n"] and warm["p50_ms"]:
            out["pooled"]["warm_vs_cold_speedup"] = round(
                cold["p50_ms"] / warm["p50_ms"], 1)
    # static half: a plain engine with no pool anywhere near it — the
    # collapsed-tail baseline, plus the redeploy cost a static fleet
    # pays for ANY model change (build + warmup from scratch)
    eng_s = build(models[0])
    tput(eng_s)
    static_tok_s = tput(eng_s)
    t0 = time.perf_counter()
    build(models[1])
    static_change_s = time.perf_counter() - t0
    out["static"] = {"decode_tok_s": round(static_tok_s, 1),
                     "model_change_s": round(static_change_s, 2)}
    if "pooled" in out and static_tok_s:
        out["collapsed_tok_s_ratio"] = round(
            out["pooled"]["collapsed_decode_tok_s"] / static_tok_s, 3)
    return out


def _two_class_workload(engine, interactive, offsets, inter_params,
                        batch_jobs=(), batch_params=None):
    """Drive a two-class mix on a bare engine: batch jobs land at t=0
    (background saturation), interactive requests arrive Poisson.
    Returns per-class client-observed latency plus the overload-policy
    counters (preemptions / sheds / max brownout level)."""
    stats = engine.stats
    pre0 = stats.slo_preemptions
    shed0 = stats.requests_shed
    rids_b = set()
    for p in batch_jobs:
        rids_b.add(engine.add_request(prompt_token_ids=p,
                                      params=batch_params))
    pending = sorted(zip(offsets, interactive))
    t0 = time.perf_counter()
    t0_mono = time.monotonic()
    intended: dict = {}
    last_tok: dict = {}
    itls_i: list = []
    # client-observed inter-token gaps INCLUDING preemption stalls: a
    # preempted stream's client waits out queue + re-prefill between two
    # consecutive tokens — the convention-pure itl list excludes that
    # (RequestOutput.from_prefill doc), but for the SLO story it is
    # exactly the regression class-aware victim choice prevents
    gaps_i: list = []
    batch_tokens = 0
    rejected = 0
    brownout_max = 0
    while True:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            off, p = pending.pop(0)
            try:
                rid = engine.add_request(prompt_token_ids=p,
                                         params=inter_params)
            except (MemoryError, RuntimeError):
                rejected += 1        # backpressure 503 / brownout shed
                continue
            intended[rid] = t0_mono + off
        if not engine.has_work():
            if not pending:
                break
            time.sleep(max(0.0, pending[0][0]
                           - (time.perf_counter() - t0)))
            continue
        outs = engine.step()
        brownout_max = max(brownout_max, stats.brownout_level)
        t_emit = time.perf_counter()
        for o in outs:
            if o.request_id in rids_b:
                batch_tokens += len(o.new_token_ids)
            prev = last_tok.get(o.request_id)
            if prev is not None and o.request_id in intended:
                gaps_i.append(t_emit - prev)
            if o.from_prefill and o.num_output_tokens > 1:
                last_tok[o.request_id] = t_emit   # re-prefill: reset clock
                continue
            if prev is not None and o.request_id in intended:
                itls_i.append(t_emit - prev)
            last_tok[o.request_id] = t_emit
    wall = time.perf_counter() - t0
    reqs = getattr(engine, "requests", {})
    ttfts = sorted(
        1000.0 * (rq.first_token_time - intended[rid])
        for rid, rq in ((r, reqs.get(r)) for r in intended)
        if rq is not None and rq.first_token_time is not None)
    itls = sorted(1000.0 * x for x in itls_i)
    gaps = sorted(1000.0 * x for x in gaps_i)
    out = {
        "wall_s": round(wall, 3),
        "interactive_done": len(ttfts),
        "interactive_rejected": rejected,
        "interactive_ttft_p50_ms": round(_pct(ttfts, 0.50), 2),
        "interactive_ttft_p99_ms": round(_pct(ttfts, 0.99), 2),
        "interactive_itl_p50_ms": round(_pct(itls, 0.50), 3),
        "interactive_itl_p99_ms": round(_pct(itls, 0.99), 3),
        "interactive_gap_p99_ms": round(_pct(gaps, 0.99), 3),
        "preemptions": stats.preemptions,
        "slo_preemptions": stats.slo_preemptions - pre0,
        "requests_shed": stats.requests_shed - shed0,
        "brownout_level_max": brownout_max,
    }
    if rids_b:
        out["batch_jobs"] = len(rids_b)
        out["batch_tokens"] = batch_tokens
        out["batch_tok_s"] = round(batch_tokens / wall, 1) if wall else 0.0
    return out


def _two_class_ab(args, model, on_tpu, *, attn_impl, pipeline, vocab):
    """Two-class Poisson mix (ISSUE 8 acceptance): interactive p99 ITL
    with background batch jobs saturating leftover budget, vs an
    interactive-only baseline on an identical engine.  SLO scheduling
    on/off comes from the environment (TPUSERVE_SLO_CLASSES=0 is the
    same-commit A/B row, two-class-noslo in tools/bench_sweep.py): with
    classes ON, interactive preempts/queue-jumps batch and p99 ITL holds
    near the baseline; OFF, interactive queues FIFO behind long batch
    generations and degrades materially."""
    import numpy as np

    from tpuserve.runtime.request import SamplingParams
    from tpuserve.utils import env_flag

    if on_tpu:
        n_inter, inter_gen, n_batch, batch_gen = 64, 32, 16, 512
        prompt_len, rate, seqs = 128, args.arrival_rate, 16
    else:
        n_inter, inter_gen, n_batch, batch_gen = 24, 16, 8, 160
        prompt_len, rate, seqs = 32, max(args.arrival_rate, 12.0), 8
    rng = np.random.default_rng(17)
    inter = [rng.integers(1, vocab - 1, size=prompt_len).tolist()
             for _ in range(n_inter)]
    bjobs = [rng.integers(1, vocab - 1, size=prompt_len).tolist()
             for _ in range(n_batch)]
    offsets = np.cumsum(rng.exponential(1.0 / rate, size=n_inter)).tolist()
    inter_params = SamplingParams(max_tokens=inter_gen, temperature=0.0,
                                  ignore_eos=True, slo_class="interactive")
    batch_params = SamplingParams(max_tokens=batch_gen, temperature=0.0,
                                  ignore_eos=True, slo_class="batch")

    # batch jobs saturate every seat at t=0 AND the block pool is sized
    # under the full batch working set, so an interactive arrival needs a
    # seat or blocks someone else holds: classless FIFO makes it wait
    # out a whole batch generation (and decode-OOM evicts the MOST
    # RECENT row — usually the interactive stream itself, whose client
    # then waits out queue + re-prefill mid-stream); class-aware
    # scheduling preempts a batch row instead
    blocks_full = -(-(prompt_len + batch_gen) // args.block_size)
    num_blocks = seqs * blocks_full - max(2, seqs // 2)

    from tpuserve.utils import next_power_of_2

    def build():
        eng = _build_engine(
            model, seqs, prompt_len, batch_gen, attn_impl=attn_impl,
            pipeline=pipeline, multi_step=args.multi_step,
            quantization=args.quant, kv_quant=args.kv_quant,
            block_size=args.block_size, max_num_seqs=seqs,
            num_blocks=num_blocks)
        # arrival ladder PLUS the preemption re-prefill buckets: an
        # evicted batch row replays prompt+generated at its grown
        # length, and a cold (1, 256) prefill compile landing inside a
        # measured TTFT would masquerade as scheduling latency
        kw = _warm_plan_arrivals(eng, seqs, prompt_len)
        L = 2 * next_power_of_2(prompt_len)
        top = next_power_of_2(prompt_len + batch_gen)
        extra = []
        while L <= top:
            extra.append((1, L))
            L *= 2
        kw["prefill_buckets"] = list(kw["prefill_buckets"]) + extra
        eng.warmup(sample_modes=("greedy",), **kw)
        return eng

    eng = build()
    slo_on = eng._slo is not None
    out = {"slo_classes_enabled": slo_on,
           "env_kill_switch": not env_flag("TPUSERVE_SLO_CLASSES"),
           "interactive_n": n_inter, "interactive_gen": inter_gen,
           "batch_jobs": n_batch, "batch_gen": batch_gen,
           "prompt_len": prompt_len, "max_num_seqs": seqs,
           "arrival_rate_req_s": rate}
    # interactive-only baseline: the ITL/TTFT floor this engine gives an
    # interactive stream with nothing competing
    out["baseline"] = _two_class_workload(eng, inter, offsets, inter_params)
    # two-class mix on a FRESH engine (prefix caches / stats clean)
    out["two_class"] = _two_class_workload(build(), inter, offsets,
                                           inter_params, bjobs,
                                           batch_params)
    for key in ("interactive_itl_p99_ms", "interactive_gap_p99_ms",
                "interactive_ttft_p99_ms"):
        base = out["baseline"][key]
        out[key.replace("_ms", "_ratio")] = (
            round(out["two_class"][key] / base, 3) if base else 0.0)
    return out


def _roofline(eng0, batch, prompt_len, gen_len, steps_s, device_kind):
    """Estimated HBM traffic at the measured rate against the device's
    peak — decode is bandwidth-bound, so tok/s is only meaningful against
    the pipe (VERDICT r3 weak #4 derived this by hand).
    ``steps_s`` is the MEASURED decode-invocation rate (num_decode_steps /
    decode_s) — each invocation re-reads the weights once regardless of
    how many tokens it emits (speculative verify emits several), and its
    queries share one read of each sequence's live context (mean over the
    run ~= prompt + gen/2)."""
    from tpuserve.models.weights import param_nbytes
    from tpuserve.runtime.kv_cache import bytes_per_block
    mc = eng0.model_cfg
    cc = eng0.cache_cfg
    weight_bytes = param_nbytes(eng0.params)
    kv_per_token = bytes_per_block(mc, cc) / cc.block_size
    avg_ctx = prompt_len + gen_len / 2
    weight_gbs = weight_bytes * steps_s / 1e9
    kv_gbs = batch * avg_ctx * kv_per_token * steps_s / 1e9
    total = weight_gbs + kv_gbs
    return {"weight_gb_s": round(weight_gbs, 1),
            "kv_gb_s": round(kv_gbs, 1),
            "total_gb_s": round(total, 1),
            "hbm_fraction": round(total / _hbm_gbs(device_kind), 3)}


def _seed_spill_dir(spill_dir):
    """Phase 1 of the cold-start measurement: one throwaway replica
    serves a shared prefix, churn evicts it HBM -> host -> PVC spill,
    and the spill files stay behind — exactly what a scaled-to-zero
    pool's PVC looks like between bursts."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SchedulerConfig)
    from tpuserve.runtime.request import SamplingParams
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=24,
                          max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  min_prefill_bucket=8,
                                  min_decode_bucket=2),
        enable_prefix_caching=True, kv_tiers=True, kv_host_bytes=3000,
        kv_spill_dir=spill_dir))
    shared = list(range(2, 26))          # 6 full blocks at block_size 4
    p = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    eng.generate([shared + [30]], p)
    eng.generate([[100 + i] * 40 for i in range(3)], p)   # churn/evict
    eng._kv_tiers.flush()
    return shared, int(eng.stats.kv_spilled_blocks)


def _autoscale_ab(args):
    """--autoscale-replay: drive the SLI-driven autoscaler end to end
    on the simulated replica pool (tpuserve/autoscale/pool.py), in
    virtual time, tiny CPU model — this measures POLICY dynamics
    (scale-out timing vs the brownout ladder, per-class SLI deltas,
    cold-start behaviour), not silicon throughput.

    storm mode: the same recorded brownout storm replayed twice —
    static topology vs autoscaled — and diffed per class (the tuning
    loop: change a policy knob, rerun, diff).  cold-start mode: a pool
    scaled to ZERO with a pre-seeded KV spill dir takes a burst; the
    from-zero replica must serve its first token with a warm-prefix
    restore, and the report carries cold-pod-to-first-token."""
    from tpuserve.autoscale import (PolicyConfig, PoolReplayOptions,
                                    make_storm_workload, pool_replay)

    def sli_row(rep, cls="interactive"):
        s = rep["sli"].get(cls, {}).get("ttft", {})
        return {"ttft_p50_s": s.get("p50"), "ttft_p95_s": s.get("p95"),
                "n": s.get("n")}

    if args.autoscale_mode == "cold-start":
        import shutil
        import tempfile
        spill = tempfile.mkdtemp(prefix="tpuserve-coldstart-")
        try:
            shared, spilled = _seed_spill_dir(spill)
            from tpuserve.replay.workload import Workload, WorkloadRequest
            wl = Workload(requests=[WorkloadRequest(
                request_id=f"cold-{i}", arrival_s=0.2 * i,
                prompt_tokens=len(shared) + 1,
                prompt_token_ids=shared + [30 + i], max_tokens=4,
                slo_class="interactive", seed=i)
                for i in range(4)], seed=3)
            rep = pool_replay(
                wl,
                PoolReplayOptions(initial_replicas=0, cold_start_s=1.0,
                                  control_interval_s=0.1,
                                  kv_spill_dir=spill,
                                  kv_host_bytes=3000),
                PolicyConfig(min_replicas=0, max_replicas=1))
        finally:
            # repeated sweep rows must not accumulate spill dirs in tmp
            shutil.rmtree(spill, ignore_errors=True)
        return {
            "mode": "cold-start",
            "spilled_blocks_seeded": spilled,
            "cold_starts_s": rep["cold_starts_observed_s"],
            "warm_prefix_blocks_restored":
                rep["counters"]["kv_restored_blocks"],
            "completed": rep["counters"]["completed"],
            "decisions": len(rep["decisions"]),
            "interactive": sli_row(rep),
            "wall_s": rep["wall_s"],
        }

    # tuned so ONE 2-seat replica is ~2x oversubscribed mid-storm (the
    # static arm climbs to L3 and sheds) while three drain it
    wl = make_storm_workload(n=80, ramp_s=5.0, span_s=16.0,
                             max_tokens=16)
    opts = PoolReplayOptions(step_time_s=0.05, control_interval_s=0.25,
                             cold_start_s=1.0, initial_replicas=1,
                             max_num_seqs=2, max_waiting=12)
    policy = PolicyConfig(min_replicas=1, max_replicas=3,
                          scale_out_cooldown_s=2.0,
                          scale_in_cooldown_s=20.0, idle_in_s=10.0)
    static = pool_replay(wl, opts)
    auto = pool_replay(wl, opts, policy)
    s_p95 = (static["sli"].get("interactive", {}).get("ttft", {})
             .get("p95") or 0.0)
    a_p95 = (auto["sli"].get("interactive", {}).get("ttft", {})
             .get("p95") or 0.0)
    out_t = auto["first_scale_out_t"]
    # first degradation event of EITHER kind: ladder L3 entry or an
    # intake shed (queue-full class eviction can shed below L3)
    shed_ts = [t for t in (auto["first_l3_t"], auto["first_shed_t"])
               if t is not None]
    shed_t = min(shed_ts) if shed_ts else None
    return {
        "mode": "storm",
        "workload": {"requests": len(wl.requests),
                     "span_s": wl.duration_s()},
        "static": {"interactive": sli_row(static),
                   "shed": static["counters"]["shed"],
                   "completed": static["counters"]["completed"],
                   "wall_s": static["wall_s"]},
        "autoscaled": {"interactive": sli_row(auto),
                       "shed": auto["counters"]["shed"],
                       "completed": auto["counters"]["completed"],
                       "replicas_peak": auto["replicas_peak"],
                       "decisions": auto["decisions"],
                       "cold_starts_s": auto["cold_starts_observed_s"],
                       "wall_s": auto["wall_s"]},
        # virtual-time policy A/B: >1 = autoscaling improved the
        # interactive tail during the storm
        "interactive_ttft_p95_improvement_x":
            round(s_p95 / a_p95, 3) if a_p95 else 0.0,
        "first_scale_out_t": out_t,
        "first_l3_or_shed_t": shed_t,
        "scale_out_before_shed": (out_t is not None
                                  and (shed_t is None or out_t < shed_t)),
        "decision_digest": auto["decision_digest"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--gen-len", type=int, default=None)
    ap.add_argument("--attn", default=None,
                    choices=["auto", "pallas", "reference"])
    ap.add_argument("--no-pipeline", action="store_true")
    ap.add_argument("--multi-step", type=int, default=None, metavar="S",
                    help="fused decode window size (default: auto — 32 on "
                         "TPU, off on CPU); 1 disables")
    ap.add_argument("--no-adaptive-window", action="store_true",
                    help="disable adaptive window shrink on arrivals "
                         "(EngineConfig.adaptive_multi_step) — fixed S "
                         "windows regardless of offered load")
    ap.add_argument("--quant", default=None, choices=["int8"],
                    help="weight-only quantization variant")
    ap.add_argument("--kv-quant", default=None, choices=["int8"],
                    help="KV-cache quantization: int8 halves KV bytes per "
                         "decode step and doubles cache capacity "
                         "(per-token-per-head scales)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the headline "
                         "default).  Non-zero measures the in-window "
                         "sampler's cost at the serving shape")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling; <1 routes windows through the "
                         "full sort-based sampler (window_sample "
                         "mode='full') — measures what production "
                         "sampling configs actually cost on chip")
    ap.add_argument("--block-size", type=int, default=32,
                    help="KV cache page size in tokens.  Bigger pages mean "
                         "fewer, larger page DMAs per decode step — the "
                         "lever that tests whether the paged kernel is "
                         "DMA-latency bound (headline sits ~9x off the "
                         "byte roofline while int8 bought only +4%%)")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="speculative decoding with K draft tokens on a "
                         "repetitive-prompt workload")
    ap.add_argument("--compare-disagg", action="store_true",
                    help="also measure the disaggregated prefill/decode "
                         "engine on the same workload")
    ap.add_argument("--mixed", action="store_true",
                    help="ragged mixed prefill+decode batching "
                         "(SchedulerConfig.mixed_batching): every step "
                         "with admissible prefill work runs ONE flat-"
                         "token dispatch carrying all decode rows plus "
                         "prefill-chunk tokens — no phase split")
    ap.add_argument("--mixed-budget", type=int, default=None, metavar="N",
                    help="mixed-mode flat-token budget per step (Sarathi "
                         "chunk sizing; default: SchedulerConfig's 512 "
                         "for --mixed, 256 for the --compare-mixed A/B "
                         "engines — the p50-ITL vs admission-latency "
                         "knob)")
    ap.add_argument("--compare-mixed", action="store_true",
                    help="A/B phase-split vs mixed ragged batching under "
                         "Poisson arrivals across a prefill:decode ratio "
                         "sweep (p50/p99 client-observed ITL), plus a "
                         "pure-decode burst throughput guard; adds a "
                         "'mixed_ab' sub-object")
    def _positive(v):
        v = int(v)
        if v < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return v

    ap.add_argument("--repeat", type=_positive, default=None, metavar="N",
                    help="run the measured workload N times and report the "
                         "median (default: 3 on TPU, 1 on CPU)")
    ap.add_argument("--prefill-split", type=int, default=1, metavar="N",
                    help="admit the arrival burst in N prefill batches "
                         "instead of one (p50-TTFT vs throughput trade)")
    ap.add_argument("--arrival", default="burst",
                    choices=["burst", "poisson"],
                    help="request arrival process: 'burst' (all at once — "
                         "worst-case p50 TTFT) or 'poisson' (timed "
                         "exponential interarrivals — what a real client "
                         "mix sees)")
    ap.add_argument("--arrival-rate", type=float, default=16.0, metavar="R",
                    help="mean request arrival rate for --arrival poisson, "
                         "req/s (default 16)")
    ap.add_argument("--interleave-prefill", action="store_true",
                    help="run one decode step between prefill admission "
                         "batches (bounds running streams' ITL during "
                         "arrival bursts; trades tail-of-burst TTFT)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="recovery-overhead A/B (runtime/faults.py): after "
                         "the clean run, repeat the workload on an engine "
                         "with this chaos spec armed (e.g. "
                         "'decode_dispatch:raise:0.02'), driven through "
                         "the salvage-capable runner; reports wall-clock "
                         "overhead + salvage/poison/watchdog counters")
    ap.add_argument("--multiturn", action="store_true",
                    help="tiered-KV A/B on a shared-system-prompt Poisson "
                         "conversation mix at an HBM budget that forces "
                         "eviction: per-turn TTFT/ITL percentiles, prefix "
                         "hit rate, and demote/restore counters for the "
                         "tiered vs HBM-only engine (TPUSERVE_KV_TIERS=0 "
                         "in the env measures the legacy half only); adds "
                         "a 'multiturn' sub-object")
    ap.add_argument("--model-mix", action="store_true", dest="model_mix",
                    help="model-pool hot-swap A/B (tpuserve/modelpool): "
                         "a Poisson request stream naming 3 tiny models "
                         "on one replica — swap-to-first-token split "
                         "cold vs warm source tier, plus collapsed-mix "
                         "steady-state tok/s vs a plain pool-free engine "
                         "(TPUSERVE_MODELPOOL=0 measures the static "
                         "redeploy half only); adds a 'model_mix' "
                         "sub-object")
    ap.add_argument("--two-class", action="store_true", dest="two_class",
                    help="two-class SLO A/B (runtime/slo.py): interactive "
                         "Poisson stream alone vs mixed with background "
                         "batch jobs on an identical engine — interactive "
                         "p99 ITL held vs classless FIFO "
                         "(TPUSERVE_SLO_CLASSES=0 re-runs the same "
                         "workload with classes off); emits a 'two_class' "
                         "sub-object")
    ap.add_argument("--turns", type=int, default=4, metavar="T",
                    help="turns per conversation for --multiturn "
                         "(default 4)")
    ap.add_argument("--clients-sweep", default=None, metavar="N,N,...",
                    help="host-overhead scaling rows: re-run the workload "
                         "at each client count (e.g. 16,64,256), reporting "
                         "decode tok/s and host-ms-per-cycle per count "
                         "(schedule + block accounting + detokenize — the "
                         "phases the native/batched host path moved off "
                         "per-request Python; TPUSERVE_HOST_BATCHED=0 "
                         "measures the legacy path for the A/B)")
    ap.add_argument("--autoscale-replay", action="store_true",
                    dest="autoscale_replay",
                    help="SLI-driven autoscaler A/B on the simulated "
                         "replica pool (tpuserve/autoscale): replay a "
                         "synthetic brownout storm static vs "
                         "autoscaled in virtual time and diff the "
                         "per-class SLIs (policy dynamics, not silicon "
                         "throughput — always the tiny model)")
    ap.add_argument("--autoscale-mode", default="storm",
                    choices=["storm", "cold-start"],
                    help="storm: static-vs-autoscaled SLI diff; "
                         "cold-start: scale-from-zero with a "
                         "pre-seeded KV spill dir, measuring "
                         "cold-pod-to-first-token with a warm prefix")
    ap.add_argument("--recorder-ab", action="store_true",
                    dest="recorder_ab",
                    help="flight-recorder overhead guard (runtime/"
                         "flight.py): after the main (recorder-on, the "
                         "default) run, repeat the identical workload on "
                         "an engine built with the recorder removed "
                         "(TPUSERVE_FLIGHT=0 equivalent) and report the "
                         "tok/s delta; 'ok' asserts the always-on "
                         "recorder costs <1%%")
    ap.add_argument("--canary-ab", action="store_true", dest="canary_ab",
                    help="canary overhead guard (ISSUE 13): interleaved "
                         "soak pairs with the synthetic prober + "
                         "in-process burn-rate evaluator armed vs the "
                         "plain runner soak; contract <1%% tok/s")
    ap.add_argument("--devprof", action="store_true",
                    help="device-telemetry overhead guard (runtime/"
                         "devprof.py): interleaved soak pairs on the "
                         "SAME warm engine with the devprof layer "
                         "toggled per arm (the exact state "
                         "TPUSERVE_DEVPROF=0 serves in), reporting the "
                         "tok/s delta plus the ON arm's device/dispatch "
                         "ms-per-cycle attribution, compile count and "
                         "HBM watermark; 'ok' asserts the always-on "
                         "layer costs <1%%")
    ap.add_argument("--backtest", action="store_true",
                    help="after the run, backtest the generated "
                         "workload through the burn-rate alert engine "
                         "(tpuserve/obs/backtest.py) twice and assert "
                         "the firing sequence is deterministic")
    ap.add_argument("--emit-trace", default=None, metavar="PATH",
                    dest="emit_trace",
                    help="write the generated workload (prompt ids, "
                         "arrival offsets, sampling knobs, fault spec) as "
                         "a portable replay file (tpuserve/replay/), so "
                         "this bench row is reproducible via tools/"
                         "replay.py run — applies to the main workload "
                         "path (burst/poisson), not the specialised "
                         "--multiturn/--two-class drivers")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-model CPU smoke run (does not update baselines)")
    args = ap.parse_args(argv)
    if args.faults:
        # Validate the chaos spec BEFORE any engine work: a typo'd site
        # name must not surface as a ValueError after the clean pass has
        # already burned minutes of warmup.  Same site registry
        # (runtime/faults.SITES) that tpulint's unknown-fault-site rule
        # checks statically.
        from tpuserve.runtime.faults import FaultInjector
        try:
            FaultInjector.from_spec(args.faults, seed=0)
        except ValueError as e:
            ap.error(f"--faults: {e}")
    if args.spec and args.temperature > 0.0:
        # speculation only engages on all-greedy batches (engine gate);
        # a sampled spec run would emit a spec block with 0 acceptance
        # that LOOKS like a measured failure when speculation never ran
        ap.error("--spec requires greedy sampling (temperature 0)")

    import jax
    import numpy as np

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.smoke and dev.platform != "tpu":
        sys.exit(f"bench.py measures on a TPU; JAX found {device}. "
                 "Use --smoke for the CPU tiny path.")

    from tpuserve.utils import compile_cache
    cache_dir = compile_cache.configure()
    cache_entries_before = compile_cache.entries(cache_dir)

    from tpuserve.runtime.request import SamplingParams

    on_tpu = jax.default_backend() == "tpu"
    if args.smoke:
        model, batch, prompt_len, gen_len = "tiny-qwen3", 8, 16, 16
    else:
        model = args.model
        batch = args.batch or 64
        prompt_len = args.prompt_len or 128
        gen_len = args.gen_len or 128

    # tiny-model head dims don't meet Pallas TPU tiling minima (8, 128)
    attn_impl = args.attn or ("reference" if args.smoke else "auto")
    pipeline = False if args.no_pipeline else None
    engine = _build_engine(model, batch, prompt_len, gen_len,
                           attn_impl=attn_impl, pipeline=pipeline,
                           spec_k=args.spec, multi_step=args.multi_step,
                           quantization=args.quant,
                           prefill_split=args.prefill_split,
                           kv_quant=args.kv_quant,
                           interleave=args.interleave_prefill,
                           adaptive_window=not args.no_adaptive_window,
                           block_size=args.block_size, mixed=args.mixed,
                           mixed_budget=args.mixed_budget,
                           # the ON arm of --recorder-ab must actually
                           # carry the recorder: a TPUSERVE_FLIGHT=0
                           # shell would otherwise compare off-vs-off and
                           # publish a green guard that measured nothing
                           flight=True if args.recorder_ab else None)

    eng0 = getattr(engine, "prefill", engine)
    rng = np.random.default_rng(0)
    vocab = eng0.model_cfg.vocab_size
    if args.spec:
        # n-gram prompt lookup needs self-similar context: tile a short
        # random segment so drafts can actually hit (random tokens would
        # measure pure verify overhead, not speculation)
        seg = rng.integers(1, vocab - 1, size=16)
        prompts = [np.tile(seg, -(-prompt_len // 16))[:prompt_len].tolist()
                   for _ in range(batch)]
    else:
        prompts = [rng.integers(1, vocab - 1, size=prompt_len).tolist()
                   for _ in range(batch)]
    params = SamplingParams(max_tokens=gen_len,
                            temperature=args.temperature,
                            top_p=args.top_p, seed=0, ignore_eos=True)

    poisson = args.arrival == "poisson"
    arrival_offsets = None
    if poisson:
        # fixed seed: every repeat (and every variant comparison) sees the
        # SAME arrival sample path, so differences are engine, not luck
        inter = np.random.default_rng(7).exponential(
            1.0 / args.arrival_rate, size=batch)
        arrival_offsets = np.cumsum(inter).tolist()

    bench_trace = None
    if args.emit_trace or args.backtest:
        # every bench row can be a manufacturable replay scenario: the
        # exact generated workload (ids included — no synthesis needed)
        # saved BEFORE warmup, so even a run the driver later kills
        # leaves a usable trace (--backtest reuses it in memory)
        from tpuserve.replay.workload import Workload, WorkloadRequest
        trace = Workload(
            requests=[WorkloadRequest(
                request_id=f"bench-{i}",
                arrival_s=(arrival_offsets[i] if arrival_offsets
                           else 0.0),
                prompt_tokens=len(p), prompt_token_ids=list(p),
                max_tokens=gen_len, temperature=args.temperature,
                top_p=args.top_p, seed=0, ignore_eos=True)
                for i, p in enumerate(prompts)],
            seed=0, faults=args.faults,
            meta={"source": "bench", "model": model,
                  "arrival": args.arrival,
                  "arrival_rate": args.arrival_rate if poisson else None})
        bench_trace = trace
        if args.emit_trace:
            trace.save(args.emit_trace)
            print(f"[bench] wrote replay trace ({len(prompts)} requests) "
                  f"to {args.emit_trace}", file=sys.stderr)

    # derive from the REQUEST the run will actually send — the engine's
    # own greedy/truncation predicates — so the warmed sampler executable
    # can't drift from the dispatched one (e.g. temperature<=0 is greedy)
    warm_modes = (("greedy",) if params.greedy
                  else ("full",) if params.needs_truncation
                  else ("temperature",))
    t_warm = time.perf_counter()
    _warm(engine, batch, prompt_len, arrivals=poisson,
          modes=warm_modes)
    warmup_s = time.perf_counter() - t_warm
    # Host<->device round-trip floor: every decode window and every
    # TTFT pays at least one of these, so recording it separates engine
    # cost from transport cost in ttft_ms.
    import jax.numpy as jnp
    one = jnp.zeros((), jnp.int32) + 1   # resident device scalar
    jax.device_get(one)                  # settle any lazy init
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.device_get(one + 1)
        rtts.append(time.perf_counter() - t0)
    host_rtt_ms = 1000.0 * sorted(rtts)[len(rtts) // 2]
    # Median-of-N on TPU: a one-chip machine shares its host's cores, and
    # a single sample would publish a host hiccup as the framework's
    # throughput.  Warmup already compiled every bucket, so repeats cost
    # only the workload itself.
    n_rep = args.repeat or (3 if on_tpu else 1)
    runs = [_run_workload(engine, prompts, params,
                          arrival_offsets=arrival_offsets)
            for _ in range(n_rep)]

    def _rate(x):
        return ((x["gen_tokens"] - batch) / x["decode_s"]
                if x["decode_s"] else 0.0)

    runs_tok_s = sorted(round(_rate(x), 1) for x in runs)
    r = sorted(runs, key=_rate)[len(runs) // 2]

    stats = r["stats"]
    gen_tokens = r["gen_tokens"]
    # Each request's first token is sampled during its prefill step; only the
    # rest were produced in decode-timed steps.  The engine runs on a single
    # chip (no mesh), so the per-chip divisor is 1.
    decode_tokens = gen_tokens - batch
    decode_tok_s = decode_tokens / r["decode_s"] if r["decode_s"] else 0.0
    # TTFT of the SELECTED median run only — aggregating over all repeats
    # would let a hiccup in a rejected run leak into the headline
    # p50 (the BASELINE target is p50, not mean)
    ttfts = r["ttfts_ms"]
    ttft_ms = sum(ttfts) / len(ttfts) if ttfts else 0.0
    ttft_p50 = ttfts[len(ttfts) // 2] if ttfts else 0.0
    ttft_p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))] if ttfts else 0.0

    out = {
        "metric": "decode_throughput",
        "value": round(decode_tok_s, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(decode_tok_s / TARGET_TOK_S_PER_CHIP, 3),
        "model": eng0.model_cfg.name,
        "backend": jax.default_backend(),
        "device": device,
        "attn_impl": eng0.attn_impl,
        "multi_step": eng0._multi_step,
        "quantization": eng0.config.quantization,
        "kv_quant": args.kv_quant,
        "block_size": args.block_size,
        "temperature": args.temperature,
        "top_p": args.top_p,
        "batch": batch,
        "prompt_len": prompt_len,
        "gen_len": gen_len,
        "ttft_ms": round(ttft_ms, 1),
        "ttft_p50_ms": round(ttft_p50, 1),
        "ttft_p99_ms": round(ttft_p99, 1),
        "e2e_tok_s": round(gen_tokens / r["total_s"], 1),
        "prefill_s": round(r["prefill_s"], 3),
        "decode_s": round(r["decode_s"], 3),
        # Startup-cost story (BASELINE TTFT budget): warmup wall-clock and
        # whether the persistent XLA cache was warm when compiles started.
        "warmup_s": round(warmup_s, 1),
        "host_rtt_ms": round(host_rtt_ms, 2),
        "runs_tok_s": runs_tok_s,
        "compile_cache": "warm" if cache_entries_before else "cold",
        "scheduler": "mixed" if args.mixed else "phase_split",
        "commit": _git_commit(),
    }
    if on_tpu:
        # a roofline share is a device metric: a CPU (--smoke) row has none
        out["roofline"] = _roofline(
            eng0, batch, prompt_len, gen_len,
            r["num_decode_steps"] / r["decode_s"] if r["decode_s"] else 0.0,
            dev.device_kind)
    if poisson:
        out["arrival"] = {"process": "poisson",
                          "rate_req_s": args.arrival_rate}
    if r.get("latency_windows"):
        # adaptive window sizing engaged: how many dispatches shrank
        out["latency_windows"] = r["latency_windows"]
    if args.spec:
        # per-run deltas (the selected median run), NOT cumulative stats —
        # with --repeat the counters span every run
        proposed = r["spec_proposed"]
        out["spec"] = {
            "k": args.spec,
            "spec_steps": r["spec_steps"],
            "decode_steps": r["num_decode_steps"],
            "acceptance": round(r["spec_accepted"] / proposed, 3)
                          if proposed else 0.0,
            "tokens_per_step": round(
                decode_tokens / r["num_decode_steps"], 2)
                          if r["num_decode_steps"] else 0.0,
        }
    if args.clients_sweep:
        out["host_overhead"] = _host_overhead_sweep(
            args, model, prompt_len, gen_len, attn_impl=attn_impl,
            pipeline=pipeline, warm_modes=warm_modes)
    if args.multiturn:
        out["multiturn"] = _multiturn_ab(
            args, model, on_tpu, attn_impl=attn_impl,
            pipeline=pipeline, vocab=vocab)
    if args.model_mix:
        out["model_mix"] = _model_mix_ab(
            args, on_tpu, attn_impl=attn_impl, pipeline=pipeline)
    if args.two_class:
        out["two_class"] = _two_class_ab(
            args, model, on_tpu, attn_impl=attn_impl,
            pipeline=pipeline, vocab=vocab)
    if args.autoscale_replay:
        out["autoscale"] = _autoscale_ab(args)
    if args.compare_mixed:
        out["mixed_ab"] = _compare_mixed(
            args, model, batch, prompt_len, gen_len, on_tpu,
            attn_impl=attn_impl, pipeline=pipeline, vocab=vocab,
            warm_modes=warm_modes)
    if args.compare_disagg:
        d_engine = _build_engine(model, batch, prompt_len, gen_len,
                                 attn_impl=attn_impl, pipeline=pipeline,
                                 disagg=True, multi_step=args.multi_step,
                                 quantization=args.quant,
                                 prefill_split=args.prefill_split,
                                 kv_quant=args.kv_quant,
                                 block_size=args.block_size)
        # same arrival process as the main run, or vs_colocated would
        # compare a poisson workload against a burst workload
        _warm(d_engine, batch, prompt_len, arrivals=poisson,
              modes=warm_modes)
        dr = _run_workload(d_engine, prompts, params,
                           arrival_offsets=arrival_offsets)
        d_decode = dr["gen_tokens"] - batch
        d_tok_s = d_decode / dr["decode_s"] if dr["decode_s"] else 0.0
        out["disagg"] = {
            "decode_tok_s": round(d_tok_s, 1),
            "e2e_tok_s": round(dr["gen_tokens"] / dr["total_s"], 1),
            "kv_transfers": d_engine.stats.kv_transfers,
            "kv_mb_transferred": round(
                d_engine.stats.kv_bytes_transferred / 1e6, 1),
            "transfer_s": round(d_engine.stats.transfer_time_s, 3),
            "vs_colocated": round(d_tok_s / decode_tok_s, 3)
                            if decode_tok_s else 0.0,
        }

    if args.recorder_ab:
        # Flight-recorder overhead guard: the recorder-ON engine is the
        # main (already-warm) engine — the recorder is always-on by
        # default — and the OFF twin is built identically with the
        # recorder removed.  INTERLEAVED pairs (on, off, on, off, ...)
        # with medians per arm, the same drift-cancelling methodology as
        # the host-overhead A/B: a sequential on-block/off-block ordering
        # measured an 11% phantom delta from machine drift on CPU.  The
        # guard contract is <1% tok/s.
        off_engine = _build_engine(
            model, batch, prompt_len, gen_len, attn_impl=attn_impl,
            pipeline=pipeline, spec_k=args.spec,
            multi_step=args.multi_step, quantization=args.quant,
            prefill_split=args.prefill_split, kv_quant=args.kv_quant,
            interleave=args.interleave_prefill,
            block_size=args.block_size, mixed=args.mixed,
            mixed_budget=args.mixed_budget,
            adaptive_window=not args.no_adaptive_window,
            flight=False)
        _warm(off_engine, batch, prompt_len, arrivals=poisson,
              modes=warm_modes)
        pairs = max(n_rep, 3)
        on_runs, off_runs = [], []
        eng_main = getattr(engine, "prefill", engine)
        engine_flight_on = getattr(eng_main, "flight", None) is not None \
            and eng_main.flight.enabled
        assert engine_flight_on, \
            "--recorder-ab ON arm has no recorder (flight=True forced " \
            "at build — a facade must forward EngineConfig.flight)"
        # the recorder flips the process-global hostprof profiler
        # always-on; a true TPUSERVE_FLIGHT=0 process never pays it,
        # so the OFF arm must run with it disabled or the guard
        # undercounts the recorder's real cost
        from tpuserve.runtime.hostprof import PROF
        for _ in range(pairs):
            PROF.enabled = True
            on_runs.append(_run_workload(
                engine, prompts, params,
                arrival_offsets=arrival_offsets))
            PROF.enabled = False
            off_runs.append(_run_workload(
                off_engine, prompts, params,
                arrival_offsets=arrival_offsets))
        # restore the ON-arm state (the main engine's recorder is
        # forced on under --recorder-ab, so this is always True here)
        PROF.enabled = engine_flight_on
        on_tok_s = _rate(sorted(on_runs, key=_rate)[len(on_runs) // 2])
        off_tok_s = _rate(sorted(off_runs, key=_rate)[len(off_runs) // 2])
        overhead = (1.0 - on_tok_s / off_tok_s) if off_tok_s else 0.0
        out["recorder_ab"] = {
            "pairs": pairs,
            "on_tok_s": round(on_tok_s, 1),
            "off_tok_s": round(off_tok_s, 1),
            "on_runs_tok_s": sorted(round(_rate(x), 1) for x in on_runs),
            "off_runs_tok_s": sorted(round(_rate(x), 1)
                                     for x in off_runs),
            # negative = recorder-on measured FASTER (noise floor)
            "overhead_frac": round(overhead, 4),
            "ok": overhead < 0.01,
        }
        if overhead >= 0.01:
            import sys as _sys
            print(f"recorder-ab GUARD FAILED: always-on flight recorder "
                  f"costs {overhead:.1%} tok/s (budget <1%)",
                  file=_sys.stderr, flush=True)

    if args.canary_ab:
        # Canary overhead guard (ISSUE 13 acceptance): interleaved pairs
        # on the SAME warm engine — ON arm = soak with the synthetic
        # prober injecting tagged canaries + the burn-rate evaluator
        # armed, OFF arm = the plain runner soak.  Same drift-cancelling
        # methodology as --recorder-ab; contract <1% tok/s.
        pairs = max(n_rep, 3)
        gen_total = params.max_tokens * len(prompts)
        on_walls, off_walls, canaries = [], [], 0
        for _ in range(pairs):
            wall_on, _f, served = _canary_runner_workload(
                engine, prompts, params)
            on_walls.append(wall_on)
            canaries += served
            off_walls.append(_runner_workload(engine, prompts,
                                              params)[0])
        on_med = sorted(on_walls)[len(on_walls) // 2]
        off_med = sorted(off_walls)[len(off_walls) // 2]
        on_tok_s = gen_total / on_med if on_med else 0.0
        off_tok_s = gen_total / off_med if off_med else 0.0
        overhead = (1.0 - on_tok_s / off_tok_s) if off_tok_s else 0.0
        out["canary_ab"] = {
            "pairs": pairs,
            "on_tok_s": round(on_tok_s, 1),
            "off_tok_s": round(off_tok_s, 1),
            "canaries_served": canaries,
            # negative = prober-on measured FASTER (noise floor)
            "overhead_frac": round(overhead, 4),
            "ok": overhead < 0.01,
        }
        if overhead >= 0.01:
            import sys as _sys
            print(f"canary-ab GUARD FAILED: prober+evaluator cost "
                  f"{overhead:.1%} tok/s (budget <1%)",
                  file=_sys.stderr, flush=True)

    if args.devprof:
        # Device-telemetry overhead guard: interleaved pairs on the
        # SAME warm engine — the devprof layer is toggled per arm into
        # the exact state TPUSERVE_DEVPROF=0 serves in (dp.enabled
        # False AND the flight handle None, so note_step never reads a
        # step delta).  Same drift-cancelling methodology as
        # --recorder-ab; contract <1% tok/s.  The ON arm's attribution
        # breakdown rides along so the sweep captures device vs host
        # ms-per-cycle and the HBM watermark with every guard row.
        inners = [e for e in (getattr(engine, "prefill", None),
                              getattr(engine, "decode", None))
                  if e is not None] or [engine]
        dps = [e.devprof for e in inners]
        assert all(dp.enabled for dp in dps), \
            "--devprof ON arm has devprof disabled " \
            "(TPUSERVE_DEVPROF=0 in the bench environment?)"

        def _set_devprof(enabled):
            for e in inners:
                e.devprof.enabled = enabled
                e.flight.devprof = e.devprof if enabled else None

        pairs = max(n_rep, 3)
        on_runs, off_runs = [], []
        for _ in range(pairs):
            _set_devprof(True)
            on_runs.append(_run_workload(
                engine, prompts, params,
                arrival_offsets=arrival_offsets))
            _set_devprof(False)
            off_runs.append(_run_workload(
                engine, prompts, params,
                arrival_offsets=arrival_offsets))
        _set_devprof(True)
        on_tok_s = _rate(sorted(on_runs, key=_rate)[len(on_runs) // 2])
        off_tok_s = _rate(sorted(off_runs, key=_rate)[len(off_runs) // 2])
        overhead = (1.0 - on_tok_s / off_tok_s) if off_tok_s else 0.0
        rep = dps[0].report()
        out["devprof"] = {
            "pairs": pairs,
            "on_tok_s": round(on_tok_s, 1),
            "off_tok_s": round(off_tok_s, 1),
            # negative = devprof-on measured FASTER (noise floor)
            "overhead_frac": round(overhead, 4),
            "ok": overhead < 0.01,
            "device_ms_per_cycle": rep["device_ms_per_cycle"],
            "dispatch_ms_per_cycle": rep["dispatch_ms_per_cycle"],
            "compiles": rep["ladder"]["compiles"],
            "compile_ms": rep["ladder"]["compile_ms"],
            "retained_executables": rep["ladder"]["retained"],
            "hbm": rep["hbm"],
        }
        if overhead >= 0.01:
            import sys as _sys
            print(f"devprof GUARD FAILED: device-telemetry layer costs "
                  f"{overhead:.1%} tok/s (budget <1%)",
                  file=_sys.stderr, flush=True)

    if args.backtest and bench_trace is not None:
        # Alert-backtest smoke (ISSUE 13): run the burn-rate engine over
        # this row's own workload twice; the firing sequence must be
        # byte-identical (the tier-1 determinism pin, exercised from the
        # bench so the sweep covers it on every capture).
        from tpuserve.obs import backtest
        from tpuserve.obs.burnrate import BurnWindow
        from tpuserve.replay.harness import ReplayOptions
        windows = (BurnWindow("fast", 60.0, 10.0, 14.4, 5.0),
                   BurnWindow("slow", 300.0, 60.0, 6.0, 30.0))
        runs = [backtest(bench_trace, windows=windows,
                         replay_opts=ReplayOptions(
                             include_token_streams=False),
                         min_events=5) for _ in range(2)]
        deterministic = (runs[0]["firing_digest"]
                         == runs[1]["firing_digest"])
        out["backtest"] = {
            "transitions": len(runs[0]["transitions"]),
            "alerts_fired": runs[0]["alerts_fired"],
            "firing_digest": runs[0]["firing_digest"][:16],
            "deterministic": deterministic,
        }
        if not deterministic:
            import sys as _sys
            print("backtest GUARD FAILED: alert firing sequence not "
                  "deterministic across identical replays",
                  file=_sys.stderr, flush=True)

    if args.faults:
        # Recovery-overhead A/B (crash-only engine): same workload, same
        # config, behind AsyncEngineRunner with and without the chaos spec
        # armed.  The clean pass reuses the already-warm main engine so
        # the ratio isolates salvage/replay cost, not compile noise.
        clean_s, clean_failed = _runner_workload(engine, prompts,
                                                 params)
        f_engine = _build_engine(
            model, batch, prompt_len, gen_len, attn_impl=attn_impl,
            pipeline=pipeline, spec_k=args.spec,
            multi_step=args.multi_step,
            quantization=args.quant, prefill_split=args.prefill_split,
            kv_quant=args.kv_quant,
            interleave=args.interleave_prefill,
            block_size=args.block_size,
            mixed=args.mixed, mixed_budget=args.mixed_budget,
            adaptive_window=not args.no_adaptive_window,
            faults=args.faults)
        _warm(f_engine, batch, prompt_len, modes=warm_modes)
        faulted_s, failed = _runner_workload(f_engine, prompts, params)
        fstats = f_engine.stats
        out["faults"] = {
            "spec": args.faults,
            "clean_s": round(clean_s, 3),
            "faulted_s": round(faulted_s, 3),
            "recovery_overhead_x": round(faulted_s / clean_s, 3)
                                   if clean_s else 0.0,
            "requests_failed": failed,
            "requests_failed_clean": clean_failed,
            "salvaged": fstats.requests_salvaged,
            "poisoned": fstats.requests_poisoned,
            "watchdog_trips": fstats.watchdog_trips,
            "engine_restarts": fstats.engine_restarts,
        }

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
