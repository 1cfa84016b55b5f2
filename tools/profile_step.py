#!/usr/bin/env python
"""Attribute decode-window time: measured wall vs XLA's own byte/flop cost.

VERDICT r4 next #3: the headline 4,809 tok/s moves ~11% of v5e's HBM pipe
and int8 weights bought only +4% — the weight-bandwidth model is wrong, so
*measure* where a window's time goes instead of inferring it.  Three
independent measurements per configuration:

  1. window wall time — median engine.step() over a steady decode batch
     (the serving number's denominator);
  2. XLA cost analysis of the decode_multi executable at the live shapes
     (AOT lower/compile — a cache hit after warmup): bytes accessed and
     flops per window, the compiler's own traffic model;
  3. device microbenches at the same shapes: a weight-stream pass (reads
     every param byte once) and the host round-trip floor.

Derived: achieved GB/s vs the compiler's byte count and, on a TPU whose
HBM peak bench.py records, the roofline-implied window time and the
residual (compute or host/dispatch overhead).
Prints ONE JSON line (metric: step_attribution); optionally wraps the
timed windows in jax.profiler.trace for a raw artifact.

Usage: python tools/profile_step.py [--model qwen3-0.6b] [--batch 64]
         [--prompt-len 128] [--quant int8] [--kv-quant int8]
         [--multi-step 32] [--trace-dir DIR] [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import _hbm_gbs  # noqa: E402  (the one table of HBM peaks)


def decode_window_cost(eng, B: int, S: int) -> dict:
    """XLA cost analysis for one decode window at the engine's LIVE
    shapes.  The AOT lower().compile() path hits the executable cache
    when warmup already compiled this (B, S) bucket, so this costs
    milliseconds, not a recompile."""
    import jax.numpy as jnp

    from tpuserve.models import transformer
    mb = eng.cache_cfg.max_blocks_per_seq
    lowered = transformer.decode_multi.lower(
        eng.params, eng.model_cfg,
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, mb), jnp.int32), jnp.ones((B,), jnp.int32),
        jnp.zeros((B,), bool), jnp.zeros((B, 2), jnp.uint32),
        jnp.zeros((B,), jnp.float32), eng.kv_cache, None,
        steps=S, mode="greedy", attn_impl=eng.attn_impl,
        mesh=eng._attn_mesh, out_mesh=eng.mesh)
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):          # some backends wrap it
        cost = cost[0] if cost else {}
    out = {}
    for key in ("bytes accessed", "flops"):
        v = cost.get(key) if isinstance(cost, dict) else None
        if isinstance(v, (int, float)):
            out[key.replace(" ", "_")] = float(v)
    return out


def weight_stream_time(eng, repeats: int = 5) -> float:
    """Median seconds for one pass that READS every parameter byte (sum
    of every leaf) — the floor a weight-bound decode step cannot beat."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def touch(params):
        return sum(jnp.sum(x.astype(jnp.float32))
                   for x in jax.tree_util.tree_leaves(params))

    jax.device_get(touch(eng.params))            # compile + settle
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.device_get(touch(eng.params))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def host_rtt(repeats: int = 5) -> float:
    import jax
    import jax.numpy as jnp
    one = jnp.zeros((), jnp.int32) + 1
    jax.device_get(one)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.device_get(one + 1)
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def host_soak(args):
    """Host hot-path soak (``--streams N --json``): run N concurrent
    streams through one engine with the host phase profiler armed
    (tpuserve/runtime/hostprof.py) and report ms-per-cycle per phase —
    schedule / block-accounting / dispatch / detokenize / flush.  The
    per-phase numbers are machine-readable and diffable across commits;
    ``TPUSERVE_HOST_BATCHED=0`` (plus ``TPUSERVE_BLOCK_MANAGER=python``)
    measures the pre-batching host path for the host-overhead A/B."""
    import jax
    import numpy as np

    from bench import _build_engine, _warm
    from tpuserve.runtime.hostprof import PROF
    from tpuserve.runtime.request import SamplingParams

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        model, plen = args.model, args.prompt_len or 128
        attn, gen = "auto", args.gen_len or 128
    else:
        # CPU soak shapes: tiny model, short prompts — the DEVICE work is
        # irrelevant here, the host bookkeeping per cycle is the measurand
        model, plen = "tiny-qwen3", args.prompt_len or 16
        attn, gen = "reference", args.gen_len or 48
    streams = args.streams
    # fused windows on by default even on CPU (the host win is per-window
    # batching; S=1 would measure the single-step path instead)
    ms = args.multi_step if args.multi_step is not None else (None if on_tpu
                                                              else 8)
    eng = _build_engine(model, streams, plen, gen, attn_impl=attn,
                        multi_step=ms, quantization=args.quant,
                        kv_quant=args.kv_quant)
    _warm(eng, streams, plen)
    rng = np.random.default_rng(0)
    vocab = eng.model_cfg.vocab_size
    params = SamplingParams(max_tokens=gen, temperature=0.0,
                            ignore_eos=True)
    prompts = [rng.integers(1, vocab - 1, size=plen).tolist()
               for _ in range(streams)]
    PROF.reset()
    PROF.enabled = True
    t0 = time.perf_counter()
    try:
        for p in prompts:
            eng.add_request(prompt_token_ids=p, params=params)
        while eng.has_work():
            eng.step()
    finally:
        PROF.enabled = False
    wall = time.perf_counter() - t0
    rep = PROF.report()
    out = {
        "metric": "host_phase_breakdown",
        "backend": jax.default_backend(),
        "model": eng.model_cfg.name,
        "streams": streams,
        "prompt_len": plen,
        "gen_len": gen,
        "multi_step": eng._multi_step,
        "block_manager": type(eng.block_manager).__name__,
        "host_batched": eng._host_batched,
        "wall_s": round(wall, 3),
        "gen_tok_s": round(streams * gen / wall, 1),
        **rep,
    }
    print(json.dumps(out))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--gen-len", type=int, default=None)
    ap.add_argument("--quant", default=None, choices=["int8"])
    ap.add_argument("--kv-quant", default=None, choices=["int8"])
    ap.add_argument("--multi-step", type=int, default=None)
    ap.add_argument("--windows", type=int, default=12,
                    help="timed decode windows (median reported)")
    ap.add_argument("--streams", type=int, default=None, metavar="N",
                    help="host hot-path soak: run N concurrent streams "
                         "with the host phase profiler armed and report "
                         "per-phase host ms/cycle (use with --json)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable per-phase host-time breakdown "
                         "(one JSON line; implied output format of "
                         "--streams)")
    ap.add_argument("--trace-dir", default=None,
                    help="also capture a jax.profiler trace of the timed "
                         "windows into this directory")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-model CPU shapes (harness tests)")
    args = ap.parse_args(argv)

    from tpuserve.utils import compile_cache
    compile_cache.configure()
    if args.streams:
        return host_soak(args)

    import jax
    import numpy as np

    from bench import _build_engine, _warm
    from tpuserve.runtime.request import SamplingParams

    on_tpu = jax.default_backend() == "tpu"
    if args.smoke or not on_tpu:
        model, batch, plen = "tiny-qwen3", 8, 16
        attn = "reference"
    else:
        model, batch, plen = args.model, args.batch or 64, args.prompt_len or 128
        attn = "auto"
    # Cache (and max_model_len) must cover every timed window at the
    # largest window size this config can resolve — a sequence hitting
    # max_model_len mid-profile turns the tail windows into degenerate
    # drain steps and poisons the median (round-5 review).
    s_max = args.multi_step or 64
    budget = (args.windows + 4) * s_max
    eng = _build_engine(model, batch, plen, budget, attn_impl=attn,
                        multi_step=args.multi_step, quantization=args.quant,
                        kv_quant=args.kv_quant)
    gen = budget + s_max                         # never finish mid-profile
    _warm(eng, batch, plen)
    S = eng._multi_step
    rng = np.random.default_rng(0)
    vocab = eng.model_cfg.vocab_size
    params = SamplingParams(max_tokens=gen, temperature=0.0, ignore_eos=True)
    for _ in range(batch):
        eng.add_request(prompt_token_ids=rng.integers(
            1, vocab - 1, size=plen).tolist(), params=params)
    while any(not r.output_token_ids for r in eng.requests.values()):
        eng.step()                               # drain prefill
    eng.step()                                   # settle into steady decode

    def timed_windows():
        walls = []
        for _ in range(args.windows):
            t0 = time.perf_counter()
            eng.step()
            walls.append(time.perf_counter() - t0)
        return walls

    from tpuserve.runtime.hostprof import PROF
    if args.json:
        # per-phase host breakdown alongside the attribution numbers
        PROF.reset()
        PROF.enabled = True
    if args.trace_dir:
        with jax.profiler.trace(args.trace_dir):
            walls = timed_windows()
    else:
        walls = timed_windows()
    PROF.enabled = False
    for r in list(eng.requests):
        eng.abort_request(r)

    wall = sorted(walls)[len(walls) // 2]
    B = eng.scheduler.decode_bucket(batch)
    cost = decode_window_cost(eng, B, S)
    wst = weight_stream_time(eng)
    rtt = host_rtt()

    from tpuserve.models.weights import param_nbytes
    weight_bytes = param_nbytes(eng.params)
    out = {
        "metric": "step_attribution",
        "backend": jax.default_backend(),
        "model": eng.model_cfg.name,
        "batch": batch, "bucket": B, "steps_per_window": S,
        "attn_impl": eng.attn_impl,
        "quantization": args.quant, "kv_quant": args.kv_quant,
        # real sequences emit batch*S tokens per window; the padded bucket
        # rows (B - batch) burn compute but produce nothing countable
        "window_wall_ms": round(1000 * wall, 2),
        "per_token_us": round(1e6 * wall / (batch * S), 2),
        "tok_s_implied": round(batch * S / wall, 1),
        "windows_ms": [round(1000 * w, 2) for w in sorted(walls)],
        "weight_bytes": weight_bytes,
        "weight_stream_ms": round(1000 * wst, 2),
        "weight_stream_gb_s": round(weight_bytes / wst / 1e9, 1),
        "host_rtt_ms": round(1000 * rtt, 2),
    }
    if cost.get("bytes_accessed"):
        gbs = cost["bytes_accessed"] / wall / 1e9
        out["xla_bytes_accessed_per_window"] = cost["bytes_accessed"]
        out["achieved_gb_s_vs_xla_bytes"] = round(gbs, 1)
    if cost.get("bytes_accessed") and on_tpu:
        # a roofline share is a device metric: off the TPU it is left out
        peak = _hbm_gbs(jax.devices()[0].device_kind)
        out["hbm_fraction"] = round(gbs / peak, 3)
        # what the window SHOULD cost if it were purely HBM-bound at the
        # compiler's byte count — the residual is compute or host/dispatch
        roofline_ms = 1000 * cost["bytes_accessed"] / (peak * 1e9)
        out["roofline_window_ms"] = round(roofline_ms, 2)
        out["residual_ms"] = round(1000 * wall - roofline_ms, 2)
    if cost.get("flops"):
        out["xla_flops_per_window"] = cost["flops"]
        out["achieved_tflops"] = round(cost["flops"] / wall / 1e12, 2)
    if args.json:
        out["host_phases"] = PROF.report()
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
