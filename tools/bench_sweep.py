#!/usr/bin/env python
"""Run the bench across its variants and record each result.

Each variant is one `python bench.py ...` subprocess (fresh backend, the
shared persistent XLA compile cache of tpuserve/utils/compile_cache.py, so
repeat sweeps skip the multi-minute model compiles).  This parent never
imports JAX: a chip belongs to one process at a time, and it is the child
that needs it — so the children run strictly one after another.  Variants
run smallest compile first, and every completed variant is appended to
bench_results.md and bench_sweep.jsonl immediately, so an interrupted sweep
keeps what it finished.

Usage: python tools/bench_sweep.py [--quick] [--only NAME[,..]]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import signal
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS: list[tuple[str, list[str], dict[str, str]]] = [
    # (name, bench.py args, extra env) — ordered smallest-compile-first
    ("base", [], {}),                              # TPU defaults: S=32, pallas, piped
    ("multistep1", ["--multi-step", "1"], {}),
    ("multistep8", ["--multi-step", "8"], {}),
    ("multistep16", ["--multi-step", "16"], {}),
    ("multistep32", ["--multi-step", "32"], {}),
    ("no-pipeline", ["--no-pipeline", "--multi-step", "1"], {}),
    ("attn-reference", ["--attn", "reference"], {}),
    # Paged-decode kernel knobs (pallas_paged_attention.py): sequences per
    # grid program (cross-sequence DMA pipeline depth) and pages per group.
    ("pallas-spp1", ["--attn", "pallas", "--multi-step", "1"],
     {"TPUSERVE_SEQS_PER_PROGRAM": "1"}),
    ("pallas-spp4", ["--attn", "pallas", "--multi-step", "1"],
     {"TPUSERVE_SEQS_PER_PROGRAM": "4"}),
    ("pallas-spp16", ["--attn", "pallas", "--multi-step", "1"],
     {"TPUSERVE_SEQS_PER_PROGRAM": "16"}),
    ("pallas-ppg4", ["--attn", "pallas", "--multi-step", "1"],
     {"TPUSERVE_PAGES_PER_GROUP": "4"}),
    ("pallas-ppg32", ["--attn", "pallas", "--multi-step", "1"],
     {"TPUSERVE_PAGES_PER_GROUP": "32"}),
    # flash prefill block split (prefill bounds TTFT)
    ("flash-q64", [], {"TPUSERVE_FLASH_BLK_Q": "64"}),
    ("flash-k256", [], {"TPUSERVE_FLASH_BLK_K": "256"}),
    ("multistep64", ["--multi-step", "64"], {}),
    # Host-overhead scaling (ROADMAP open item 3): decode tok/s + pure-host
    # ms/cycle (schedule + block accounting + detokenize) at growing
    # concurrent-stream counts; the legacy row re-measures with the
    # batched host path and the native block manager disabled (the
    # host-overhead A/B; not measured on the current code).
    ("host-overhead", ["--clients-sweep", "16,64,256"], {}),
    ("host-overhead-legacy", ["--clients-sweep", "16,64,256"],
     {"TPUSERVE_HOST_BATCHED": "0", "TPUSERVE_BLOCK_MANAGER": "python"}),
    # Tiered KV cache (ISSUE 7): multi-turn shared-prefix Poisson mix at
    # an HBM budget forcing eviction — per-turn TTFT, prefix hit rate,
    # demote/restore counters, tiered vs HBM-only in one row.  The
    # legacy row re-runs with the kill switch so the HBM-only number is
    # measured under the exact pre-tiering code path.
    ("kv-tiers", ["--multiturn"], {}),
    ("kv-tiers-legacy", ["--multiturn"], {"TPUSERVE_KV_TIERS": "0"}),
    # Overload robustness (ISSUE 8): two-class Poisson mix — interactive
    # p99 ITL with batch jobs saturating leftover budget vs an
    # interactive-only baseline; the noslo row re-runs the SAME workload
    # under the kill switch so the classless-FIFO degradation is
    # measured on the same commit.
    ("two-class", ["--two-class"], {}),
    ("two-class-noslo", ["--two-class"], {"TPUSERVE_SLO_CLASSES": "0"}),
    # Flight recorder (ISSUE 9): the always-on overhead guard on silicon
    # — recorder-on vs TPUSERVE_FLIGHT=0 on the same workload; the
    # acceptance contract is <1% tok/s (not measured on the chip).
    ("recorder-ab", ["--recorder-ab"], {}),
    # Trace replay (ISSUE 11): a Poisson bench row that also exports its
    # workload as a replay file — the sweep's rows become reproducible
    # scenarios (tools/replay.py run bench_replay_trace.json), and the
    # export path itself is exercised on silicon.
    ("replay-smoke", ["--arrival", "poisson", "--arrival-rate", "16",
                      "--emit-trace", "bench_replay_trace.json"], {}),
    # SLI-driven autoscaler (ISSUE 12): the brownout-storm policy A/B
    # (static vs autoscaled simulated pool, virtual time — measures
    # scale-out-before-shed timing and the per-class SLI delta) and the
    # scale-from-zero cold start with a warm-prefix KV spill restore.
    ("autoscale-storm", ["--autoscale-replay"], {}),
    ("cold-start", ["--autoscale-replay",
                    "--autoscale-mode", "cold-start"], {}),
    # Fleet SLO engine (ISSUE 13): canary prober + in-process burn-rate
    # evaluator overhead guard (<1% tok/s, interleaved pairs) and the
    # alert-backtest determinism smoke over the row's own workload.
    ("canary-smoke", ["--canary-ab"], {}),
    ("backtest-smoke", ["--arrival", "poisson", "--arrival-rate", "16",
                        "--backtest"], {}),
    # Device telemetry (ISSUE 16): the always-on devprof overhead guard
    # on silicon (<1% tok/s, interleaved same-engine toggle) — the row
    # also records the first REAL device/dispatch ms-per-cycle split,
    # compile walls per ladder bucket, and the HBM watermark; the
    # legacy row pins the removed-layer baseline under
    # TPUSERVE_DEVPROF=0 on the same commit.
    ("devprof", ["--devprof"], {}),
    ("devprof-legacy", [], {"TPUSERVE_DEVPROF": "0"}),
    # Model pool (ISSUE 17): hot-swap a 3-model catalog through one
    # replica under a Poisson model mix — p95 cold- vs warm-swap-to-
    # first-token and the collapsed-mix tok/s parity guard; the static
    # row re-runs under the kill switch so the one-model baseline and
    # the redeploy cost are measured on the same commit.
    ("model-mix", ["--model-mix"], {}),
    ("model-mix-static", ["--model-mix"], {"TPUSERVE_MODELPOOL": "0"}),
    ("int8", ["--quant", "int8"], {}),
    ("int8-multistep16", ["--quant", "int8", "--multi-step", "16"], {}),
    ("int8-multistep32", ["--quant", "int8", "--multi-step", "32"], {}),
    # p50-TTFT lever: admit the 64-request burst in 2/4 prefill batches
    ("prefill-split2", ["--prefill-split", "2"], {}),
    ("prefill-split4", ["--prefill-split", "4"], {}),
    # Realistic-arrival TTFT rows (VERDICT r3 weak #2: every recorded TTFT
    # was the worst-case simultaneous 64-burst).  single-request = an
    # unloaded engine's floor; poisson = clients arriving into a busy
    # engine at a sustainable offered load.
    ("single-request", ["--batch", "1", "--repeat", "5"], {}),
    ("poisson16", ["--arrival", "poisson", "--arrival-rate", "16"], {}),
    ("poisson32", ["--arrival", "poisson", "--arrival-rate", "32"], {}),
    # adaptive window sizing (EngineConfig.adaptive_multi_step, default
    # on): arrivals into a busy engine shrink fused windows to
    # min_multi_step.  The r4 rows named plain poisson16/poisson32 were
    # captured pre-feature (commit <= cef5452) = the fixed-window
    # baseline; these re-measure the same workloads with the feature.
    ("poisson16-adaptive", ["--arrival", "poisson", "--arrival-rate", "16"],
     {}),
    ("poisson32-adaptive", ["--arrival", "poisson", "--arrival-rate", "32"],
     {}),
    ("poisson16-fixed", ["--arrival", "poisson", "--arrival-rate", "16",
                         "--no-adaptive-window"], {}),
    ("poisson16-interleave", ["--arrival", "poisson", "--arrival-rate", "16",
                              "--interleave-prefill"], {}),
    # HBM-roofline headroom probe (VERDICT r3 weak #4: 4,210 tok/s moves
    # ~80 GB/s of an 819 GB/s pipe — int8 halves weight bytes and bigger
    # batches amortize them; these rows answer how much of the 2x+ is real)
    ("batch128", ["--batch", "128"], {}),
    ("int8-batch128", ["--quant", "int8", "--batch", "128"], {}),
    ("int8-batch256", ["--quant", "int8", "--batch", "256"], {}),
    # Page-size lever: fewer, larger page DMAs per decode step.  The
    # headline sits ~9x off the byte roofline while int8 bought only +4%
    # — if the paged kernel is DMA-LATENCY bound (64 seqs x ~5 pages x
    # 28 layers of small transfers), bigger pages should move the number
    # where byte-halving didn't.
    ("block64", ["--block-size", "64"], {}),
    ("block128", ["--block-size", "128"], {}),
    ("int8-block64", ["--quant", "int8", "--block-size", "64"], {}),
    # int8 KV cache: halves the OTHER half of decode's HBM traffic (KV
    # reads rival weight reads at the headline shape, by byte count);
    # with int8 weights too, decode moves ~1/2 the bytes
    ("kv-int8", ["--kv-quant", "int8"], {}),
    ("int8-kv-int8", ["--quant", "int8", "--kv-quant", "int8"], {}),
    ("int8-kv-int8-batch256", ["--quant", "int8", "--kv-quant", "int8",
                               "--batch", "256"], {}),
    # In-window sampler cost at the serving shape: "temperature" adds
    # per-row Gumbel argmax; "full" adds the 151k-vocab sort every scan
    # iteration (top-p is most clients' default — if the sort costs real
    # throughput on chip, serving guidance must say so)
    ("sampled-temp", ["--temperature", "0.8"], {}),
    ("sampled-top-p", ["--temperature", "0.8", "--top-p", "0.95"], {}),
    ("spec4", ["--spec", "4"], {}),
    ("disagg", ["--compare-disagg"], {}),
    # Ragged mixed prefill+decode batching (scheduler mixed mode, the
    # Pallas ragged kernel on chip): the headline-shape main line under
    # mixed scheduling, the sustained-admission Poisson row, and the
    # phase-split-vs-mixed A/B (p99 ITL ratio sweep + pure-decode guard)
    ("mixed", ["--mixed"], {}),
    ("mixed-poisson16", ["--mixed", "--arrival", "poisson",
                         "--arrival-rate", "16"], {}),
    ("compare-mixed", ["--compare-mixed"], {}),
    # Long-context path: prompts routed through chunked prefill (the
    # Pallas windowed kernel) — the framework's long-context story on
    # silicon, not just in interpret-mode tests
    ("long-prompt", ["--prompt-len", "4096", "--gen-len", "64",
                     "--batch", "4"], {}),
    # Context-length sweep at fixed batch/gen: decode time vs context
    # separates KV-read cost (scales with ctx) from fixed per-step cost —
    # the slope is the paged kernel's EFFECTIVE HBM bandwidth against the
    # 819 GB/s roofline (r4: headline sits at ~0.2 of HBM; where is the
    # rest going?)
    ("ctx512", ["--prompt-len", "512"], {}),
    ("ctx1024", ["--prompt-len", "1024"], {}),
    ("int8-ctx1024", ["--prompt-len", "1024", "--quant", "int8",
                      "--kv-quant", "int8"], {}),
    # Alternate served families (the reference's other models,
    # kubernetes-single-node.yaml:15 / templates/*.yaml) — random-init
    # weights (air-gapped build host), so throughput is real but text is
    # not; smaller batch for the 3.8B phi to fit v5e HBM alongside KV.
    ("phi3-mini", ["--model", "phi3-mini", "--batch", "32"], {}),
    ("opt-1.3b", ["--model", "opt-1.3b"], {}),
    # Flagship-scale single chip: 8B int8 weights (~8 GB) + bf16 KV fit
    # v5e's 16 GB HBM; random-init (air-gapped), throughput is real
    ("llama3-8b-int8", ["--model", "llama3-8b", "--quant", "int8",
                        "--batch", "16", "--gen-len", "64"], {}),
    # Sliding-window family at long context: with W=4096 and an 8k
    # prompt, windowed decode DMAs roughly HALF the KV pages per step —
    # the page-skip path measured on silicon
    ("mistral7b-int8-sw8k", ["--model", "mistral-7b", "--quant", "int8",
                             "--kv-quant", "int8", "--batch", "4",
                             "--prompt-len", "8192", "--gen-len", "64"], {}),
    # Gemma2 traits on silicon (softcaps in all kernels, sandwich norms,
    # alternating windows, 256k-vocab unembed/sampling)
    ("gemma2-2b-int8", ["--model", "gemma2-2b", "--quant", "int8",
                        "--batch", "16", "--gen-len", "64"], {}),
]

QUICK = ["base", "multistep1", "int8", "kv-int8", "poisson16", "disagg"]


def run_variant(name: str, args: list[str], timeout: int,
                env: dict[str, str] | None = None,
                bench_path: str | None = None) -> dict | None:
    """Run one bench variant to its end (or ``timeout``) and return its
    last result line, or None when it printed none."""
    cmd = [sys.executable, bench_path or os.path.join(ROOT, "bench.py")] + args
    print(f"=== {name}: {' '.join(cmd)}", flush=True)
    # Own session, so a timeout kills the whole process GROUP: nothing a
    # variant started may outlive it holding the chip.
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, cwd=ROOT,
                            env=env, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.communicate()
        print(f"--- {name}: TIMEOUT after {timeout}s", flush=True)
        return None
    result = None
    for l in out.splitlines():
        l = l.strip()
        if l.startswith("{") and '"metric"' in l:
            try:
                row = json.loads(l)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict):
                result = row
    if result is None:
        print(f"--- {name}: no JSON (rc={proc.returncode})\n"
              f"{err[-2000:]}", flush=True)
        return None
    if proc.returncode != 0:
        # measured but died in teardown: keep the number, but never
        # indistinguishable from a healthy run
        result["rc"] = proc.returncode
    result["variant"] = name
    return result


def format_row(r: dict) -> str:
    notes = []
    if r.get("rc"):
        notes.append(f"rc={r['rc']} (died post-measurement)")
    if "spec" in r:
        notes.append(f"accept={r['spec']['acceptance']}, "
                     f"tok/step={r['spec']['tokens_per_step']}")
    if "disagg" in r:
        notes.append(f"disagg={r['disagg']['decode_tok_s']} "
                     f"({r['disagg']['vs_colocated']}x)")
    if "mixed_ab" in r:
        ab = r["mixed_ab"]
        improv = max((row.get("p99_itl_improvement", 0)
                      for row in ab.get("rows", [])), default=0)
        notes.append(f"p99-ITL up to {improv}x better mixed; "
                     f"pure-decode {ab['pure_decode']['ratio']}x")
    return (f"| {r['variant']} | {r['backend']} | {r['value']} | "
            f"{r['vs_baseline']} | {r['ttft_ms']} | {r['attn_impl']} "
            f"| {r.get('multi_step')} | {r.get('quantization') or '-'}"
            f" | {'; '.join(notes) or '-'} |\n")


_HEADER_WRITTEN = False


def append_markdown(r: dict, path: str | None = None) -> None:
    """Append ONE result row immediately — a crash or Ctrl-C mid-sweep must
    not lose the variants that already completed."""
    global _HEADER_WRITTEN
    path = path or os.path.join(ROOT, "bench_results.md")
    new_file = not os.path.exists(path)
    with open(path, "a") as f:
        if new_file:
            f.write("# Sweep results\n\n"
                    "Decode throughput per chip on the headline workload "
                    "(Qwen3-0.6B, batch 64, 128 in / 128 out) across engine "
                    "variants, on the device named in each row.  Target: "
                    "2,000 tok/s/chip (BASELINE.md); the reference "
                    "publishes no numbers (SURVEY.md §6).\n")
        if not _HEADER_WRITTEN:
            stamp = datetime.datetime.now().strftime("%Y-%m-%d %H:%M")
            f.write(f"\n## Sweep @ {stamp}\n\n")
            f.write("| variant | backend | tok/s/chip | vs target | TTFT ms "
                    "| attn | S | quant | notes |\n"
                    "|---|---|---|---|---|---|---|---|---|\n")
            _HEADER_WRITTEN = True
        f.write(format_row(r))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="four-variant sweep only")
    ap.add_argument("--only", default=None,
                    help="comma-separated variant names")
    ap.add_argument("--timeout", type=int, default=5400,
                    help="per-variant timeout, cold compiles included")
    args = ap.parse_args()
    known = [n for n, _, _ in VARIANTS]
    if args.only:
        names = [n.strip() for n in args.only.split(",")]
        unknown = sorted(set(names) - set(known))
        if unknown:
            ap.error(f"unknown variants {unknown}; known: {known}")
    else:
        names = QUICK if args.quick else known
    count = 0
    log = open(os.path.join(ROOT, "bench_sweep.jsonl"), "a")
    for name, vargs, venv in VARIANTS:
        if name not in names:
            continue
        env = dict(os.environ, **venv) if venv else None
        r = run_variant(name, vargs, args.timeout, env=env)
        if r is not None:
            r["ts"] = datetime.datetime.now().isoformat(timespec="seconds")
            print(json.dumps(r), flush=True)
            log.write(json.dumps(r) + "\n")
            log.flush()
            append_markdown(r)       # per-variant: partial sweeps survive
            count += 1
    print(f"appended {count} results to bench_results.md" if count
          else "no results", flush=True)


if __name__ == "__main__":
    main()
