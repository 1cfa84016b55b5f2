"""One run of one cell: the process that owns the chips.

Set-up (everything before the window opens, reported as ``setup_s``):
compile cache, the real server built from argv by the program's own
``build_server``, weights on the device, warm-up of the cell's own shapes,
three correctness probes scored by the plain reference the configuration
names, the load generator's child started, its pre-roll.  Then the window.
Nothing in here names a configuration, a traffic mix, a cell, a per-layer
metric or a reference.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from . import host_spans, plan, stats, trace_reduce
from . import traffic as traffic_mod
from .meter import CompileMeter
from .shapes import warm_shapes

# Chosen-token logprob: served path (bf16 weights, activations and cache,
# f32 softmax) against the float32 reference on the same weights; and how
# far behind the reference's best token a served token may be (a greedy
# stream may part from the reference only at a near-tie).  chip_smoke.py
# measured 0.031 / 0.016 between two bf16 attention implementations; a
# wrong kernel or a skipped layer is off by order 1, int8 weights by more
# than this.
LOGPROB_ATOL = 0.1
TIE_ATOL = 0.1
PROBE_PROMPTS = (64, 96, 128)
PROBE_TOKENS = 16
POLL_S = 0.5
NO_SPAN = "no_span"          # idle time under no span of the engine loop


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Refused(SystemExit):
    """The run cannot be a measurement: non-zero exit, no result line."""

    def __init__(self, why: str):
        say(f"REFUSED: {why}")
        super().__init__(1)


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def device_info(peaks_path: str, chips: int) -> dict:
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    say(f"device {info}; jax {jax.__version__}")
    if info["platform"] != "tpu":
        raise Refused("JAX found no TPU; the benchmark measures on the chip "
                      "only")
    if info["count"] != chips:
        raise Refused(f"the cell wants {chips} chip(s), the machine has "
                      f"{info['count']}")
    peaks = plan.read_json(peaks_path)
    if info["kind"] not in peaks["devices"]:
        raise Refused(f"device kind {info['kind']!r} is not in {peaks_path}")
    return info


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

def register_configuration(cell) -> str:
    """Register the cell's architecture under the configuration's own
    name and return that name: the registered model with the keys listed
    under ``reduced`` replaced, and nothing else.  Refused, before any
    server is built, where the file misdescribes what runs or its
    reference does not describe the family."""
    from tpuserve.models.config import (get_model_config,
                                        register_model_config)
    base = get_model_config(cell.config["model"])
    name = "bench/" + cell.config_name
    model_cfg = dataclasses.replace(
        base, name=name, **plan.architecture_overrides(cell.config))
    wrong = plan.architecture_mismatches(cell.config, model_cfg,
                                         cell.reference)
    if wrong:
        raise Refused(f"configuration {cell.config_name} does not describe "
                      f"what runs: {wrong}")
    try:
        cell.reference.check_family(model_cfg)
    except ValueError as e:
        raise Refused(f"configuration {cell.config_name}: its reference "
                      f"does not describe what runs: {e}") from e
    register_model_config(model_cfg)
    return name


def build(cell, meter: CompileMeter, seconds: float):
    """The real server, warm for this cell's traffic.  Returns
    ``(server, url, model name)``."""
    # a window of --seconds holds more step records than the recorder's
    # default ring
    os.environ.setdefault("TPUSERVE_FLIGHT_STEPS", "262144")
    from tpuserve.server.openai_api import build_server
    name = register_configuration(cell)
    argv = ["--model", name, *cell.config["server_args"],
            "--no-warmup", "--host", "127.0.0.1", "--port", "0"]
    t0 = time.monotonic()
    server, _ = build_server(argv)
    engine = server.engine
    port = server.start(warmup=False)
    say(f"server built in {time.monotonic() - t0:.1f}s: attn_impl="
        f"{engine.attn_impl} multi_step={engine._multi_step} block_manager="
        f"{type(engine.block_manager).__name__} kv_blocks="
        f"{engine.cache_cfg.num_blocks} x {engine.cache_cfg.block_size}")
    want = cell.config.get("expect", {})
    got = {"attn_impl": engine.attn_impl,
           "block_manager": type(engine.block_manager).__name__}
    for key, value in want.items():
        if got.get(key) != value:
            server.shutdown()
            raise Refused(f"the engine runs {key}={got.get(key)!r}, the "
                          f"configuration expects {value!r}")
    pool = traffic_mod.pool_size(cell.traffic, cell.params.get("rate", 0.0),
                                 seconds)
    shapes = warm_shapes(engine.scheduler,
                         traffic_mod.bounds(cell.traffic, pool))
    t0, c0 = time.monotonic(), meter.snapshot()
    engine.warmup(sample_modes=("greedy",), **shapes)
    c1 = meter.snapshot()
    say(f"warm-up {time.monotonic() - t0:.1f}s: "
        f"{len(shapes['prefill_buckets'])} prefill, "
        f"{len(shapes['chunk_buckets'])} chunk, "
        f"{len(shapes['decode_buckets'])} decode buckets; compile requests "
        f"{c1['requests'] - c0['requests']} (persistent cache: "
        f"{c1['hits'] - c0['hits']} warm, {c1['misses'] - c0['misses']} "
        f"cold) in {c1['seconds'] - c0['seconds']:.1f}s")
    warm_demotion_ladder(engine)
    warm_chained_decode(engine, shapes["decode_buckets"])
    return server, f"http://127.0.0.1:{port}", name


def warm_chained_decode(engine, decode_buckets: list) -> None:
    """Pipelined decode chains one dispatch's tokens into the next on the
    device: ``toks[:, -1]`` of a window, then ``_select_tokens`` from the
    previous batch's bucket into the new one's.  ``Engine.warmup`` warms
    that for equal buckets only; arrivals and departures move a batch
    from any bucket to any other, so warm every pair, or those small
    programs compile inside the window."""
    import jax
    import jax.numpy as jnp

    from tpuserve.runtime import engine as engine_mod
    if not engine._pipeline_decode:
        return
    t0 = time.monotonic()
    windows = {engine._multi_step, engine._min_multi_step}
    tails = []
    for prev in decode_buckets:
        for steps in sorted(windows):
            tails.append(jnp.zeros((prev, steps), jnp.int32)[:, -1])
        for new in decode_buckets:
            tails.append(engine_mod._select_tokens(
                jnp.zeros((prev,), jnp.int32), jnp.zeros((new,), jnp.int32),
                jnp.zeros((new,), jnp.int32), jnp.zeros((new,), bool)))
    jax.block_until_ready(tails)
    say(f"chained-decode programs for {len(decode_buckets)}^2 bucket pairs "
        f"warmed in {time.monotonic() - t0:.1f}s")


def kv_block_bytes(engine) -> int:
    """Bytes of one block of the paged cache, over all its leaves."""
    import jax
    return sum(leaf.nbytes for leaf in jax.tree.leaves(
        engine.kv_cache)) // engine.cache_cfg.num_blocks


def warm_demotion_ladder(engine) -> None:
    """The tiered KV cache copies every evicted prefix block to the host
    through a gather whose block axis is padded to a power of two;
    ``Engine.warmup`` warms 1..16.  One prefill batch can evict as many
    blocks as its token budget holds, so warm the rest of that ladder too
    (where the gather's output fits the memory that is free)."""
    import jax
    if getattr(engine, "_kv_tiers", None) is None:
        return
    from tpuserve.runtime.kv_cache import gather_block_pages
    block = engine.cache_cfg.block_size
    top = engine.scheduler.cfg.max_prefill_tokens // block
    block_bytes = kv_block_bytes(engine)
    stats = jax.local_devices()[0].memory_stats()
    # a backend without memory statistics (the CPU rehearsal) has no limit
    free = (stats["bytes_limit"] - stats["bytes_in_use"]) if stats \
        else float("inf")
    top = min(top, engine.cache_cfg.num_blocks)
    t0, sizes, n = time.monotonic(), [], 32
    while n <= top and n * block_bytes < 0.8 * free:
        gather_block_pages(engine.kv_cache, [0] * n)
        sizes.append(n)
        n *= 2
    say(f"demotion gather ladder {sizes} warmed in "
        f"{time.monotonic() - t0:.1f}s ({block_bytes} B a block, "
        f"{free} B free)")


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def http_json(url: str, payload: dict, timeout: float = 600.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise RuntimeError(f"{url}: HTTP {e.code}: "
                           f"{e.read()[:300].decode('replace')}") from e


def scrape(url: str) -> dict:
    """``{sample name: sum over its label sets}`` of a /metrics page."""
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        text = r.read().decode()
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def probe(url: str, model: str, engine, seed: int, reference) -> dict:
    """Seeded prompts through the served path, scored by the plain
    reference the configuration names.  Which row of log-probabilities
    belongs to which served token is the reference's to say (it is handed
    each choice's whole ``logprobs`` object, unread here beyond its tokens
    and their logprobs); the prompts, the request, the tolerances and the
    comparison are this function's, so no reference can loosen them."""
    import numpy as np

    vocab = engine.model_cfg.vocab_size
    served = []
    for i, n in enumerate(PROBE_PROMPTS):
        ids = traffic_mod.prompt_ids(seed, "probe", i, n, vocab)
        body = http_json(url + "/v1/completions", {
            "model": model, "prompt": ids, "max_tokens": PROBE_TOKENS,
            "temperature": 0, "ignore_eos": True, "logprobs": 5})
        lp = body["choices"][0]["logprobs"]
        toks = [int(t) for t in lp["tokens"]]
        if len(toks) != PROBE_TOKENS:
            return {"ok": False, "why": f"probe {i} returned {len(toks)} "
                                        f"tokens, wanted {PROBE_TOKENS}"}
        served.append((ids, toks, lp))
    ref = np.asarray(reference.score_probes(
        engine.params, engine.model_cfg, served), np.float32)
    if ref.shape != (len(served) * PROBE_TOKENS, vocab):
        return {"ok": False, "why": f"the reference scored {ref.shape}, "
                f"wanted {(len(served) * PROBE_TOKENS, vocab)}"}
    worst_lp = worst_tie = 0.0
    parted = 0
    r = 0
    for _, toks, lps in served:
        for tok, lp in zip(toks, lps["token_logprobs"]):
            worst_lp = max(worst_lp, abs(float(ref[r, tok]) - float(lp)))
            tie = float(ref[r].max() - ref[r, tok])
            worst_tie = max(worst_tie, tie)
            parted += tie > 0
            r += 1
    ok = worst_lp <= LOGPROB_ATOL and worst_tie <= TIE_ATOL
    return {"ok": bool(ok), "positions": r, "logprob_diff_max": worst_lp,
            "tie_gap_max": worst_tie, "not_reference_argmax": int(parted),
            "why": "" if ok else
            f"served path and float32 reference disagree: logprob diff "
            f"{worst_lp:.4f} (atol {LOGPROB_ATOL}), tie gap {worst_tie:.4f} "
            f"(atol {TIE_ATOL})"}


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

class Poller(threading.Thread):
    """Gauges of /metrics, sampled through the window of a traced run."""

    def __init__(self, url: str, t_from: float, t_to: float):
        super().__init__(daemon=True, name="bench-poll")
        self.url, self.t_from, self.t_to = url, t_from, t_to
        self.samples: list = []
        self._stopped = threading.Event()

    def stop(self) -> None:
        self._stopped.set()
        self.join(timeout=5)

    def run(self) -> None:
        self._stopped.wait(max(0.0, self.t_from - time.monotonic()))
        while time.monotonic() < self.t_to and not self._stopped.is_set():
            try:
                page = scrape(self.url)
            except OSError:
                page = None
            if page is not None:
                self.samples.append({"t": time.monotonic(), **{
                    k: page[k] for k in page if k.startswith("vllm_num_")
                    or k.startswith("vllm_kv_")}})
            self._stopped.wait(POLL_S)


def sleep_until(t: float) -> None:
    time.sleep(max(0.0, t - time.monotonic()))


def loadgen_argv(cell, url: str, model: str, vocab: int, seed: int,
                 seconds: float, out: str) -> list:
    here = os.path.dirname(os.path.abspath(__file__))
    port = url.rsplit(":", 1)[1]
    return [sys.executable, os.path.join(here, "loadgen.py"),
            "--port", port, "--mix", cell.traffic_path, "--model", model,
            "--vocab", str(vocab), "--seed", str(seed),
            "--seconds", str(seconds),
            "--preroll", str(cell.traffic["preroll_s"]),
            "--rate", str(cell.params.get("rate", 0.0)),
            "--clients", str(cell.params.get("clients", 0)),
            "--ramp", str(cell.params.get("ramp_s", 0.0)),
            "--out", out]


def run_window(cell, server, url: str, model: str, seed: int, seconds: float,
               trace: bool, out_dir: str, meter: CompileMeter,
               trace_seconds: float = 2.0) -> dict:
    """Start the child, wait through pre-roll and window, collect."""
    import jax
    engine = server.engine
    os.makedirs(out_dir, exist_ok=True)
    records_path = os.path.join(out_dir, "records.json")
    trace_dir = os.path.join(out_dir, "trace")
    for path in (records_path, trace_dir):
        if os.path.isdir(path):
            shutil.rmtree(path)
        elif os.path.exists(path):
            os.remove(path)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(
        loadgen_argv(cell, url, model, engine.model_cfg.vocab_size, seed,
                     seconds, records_path),
        stdout=subprocess.PIPE, env=env)
    try:
        times = json.loads(child.stdout.readline())
        t_window, t_end = times["t_window"], times["t_end"]
        poller = None
        if trace:
            poller = Poller(url, t_window, t_end)
            poller.start()
        sleep_until(t_window)
        c0 = meter.snapshot()
        meter.watch_names(True)
        ladder0 = set(engine.devprof.ladder)
        page0 = scrape(url) if trace else {}
        span = None
        if trace:
            sleep_until(t_end - min(trace_seconds, seconds / 2))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            ta = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        sleep_until(t_end)
        c1 = meter.snapshot()
        meter.watch_names(False)
        new_execs = sorted(map(str, set(engine.devprof.ladder) - ladder0)) \
            + list(meter.names)
        if trace:
            span = (ta, time.monotonic())
            jax.profiler.stop_trace()     # seconds of work: after the window
        page1 = scrape(url) if trace else {}
        drain = 15.0 if cell.traffic["loop"] == "closed" else 60.0
        child.wait(timeout=drain + 30.0)
    finally:
        meter.watch_names(False)
        if child.poll() is None:
            child.kill()
        child.wait()
        child.stdout.close()
    if poller is not None:
        poller.stop()
    if child.returncode != 0:
        raise Refused(f"the load generator exited {child.returncode}")
    data = plan.read_json(records_path)
    steps = [s for s in engine.flight.steps_snapshot(limit=1 << 30)
             if t_window <= s["t"] < t_end]
    return {"t_window": t_window, "t_end": t_end, "seconds": seconds,
            "records": data["records"], "steps": steps,
            "compiles_in_window": c1["requests"] - c0["requests"],
            "new_executables": new_execs,
            "metrics_start": page0, "metrics_end": page1,
            "polls": poller.samples if poller else [],
            "trace_dir": trace_dir if trace else None, "trace_span": span,
            "chips": cell.chips, "multi_step": engine._multi_step,
            "config": cell.config,
            "cell": {"name": cell.name, "chips": cell.chips,
                     "params": cell.params},
            "kv_bytes_per_token":
                kv_block_bytes(engine) / engine.cache_cfg.block_size}


def moved_counters(start: dict, end: dict) -> dict:
    """``end - start`` of two /metrics pages for the counters that moved:
    ``*_total``, and a histogram's ``_sum`` and ``_count``.  A gauge's
    difference between two instants says nothing and is left out."""
    return {k: v - start.get(k, 0.0) for k, v in end.items()
            if k.endswith(("_total", "_sum", "_count"))
            and v != start.get(k, 0.0)}


def idle_gaps(run: dict) -> list:
    """The ten largest ``[name, seconds]`` of the busiest chip's idle time:
    by the engine loop's innermost span where the trace has such spans, by
    the programs around each gap only where it has none."""
    spans = host_spans.analyse(run)
    if not spans:
        return run["trace"]["gaps"][:10]
    return [[name or NO_SPAN, ns * 1e-9]
            for name, ns in spans["by_span"].items()][:10]


def find_xplane(trace_dir: str):
    for root, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    return None


def measure(cell, seed: int, seconds: float, trace: bool, t_proc0: float,
            out_dir: str, peaks_path: str, keep_trace: str = "",
            trace_seconds: float = 2.0) -> dict:
    """The whole run.  Returns the object of the last line, and under
    ``compared`` the sentence of each number compared beside its limit,
    which the command prints as the last line of standard error."""
    device = device_info(peaks_path, cell.chips)
    from tpuserve.utils import compile_cache
    cache_dir = compile_cache.configure()
    # every run is a new process: keep the quick compiles too (JAX's default
    # leaves out what compiles in under a second, and a warm-up makes
    # dozens of those)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(f"compile cache at {cache_dir}: {compile_cache.entries(cache_dir)} "
        "entries before")
    meter = CompileMeter()
    server, url, model = build(cell, meter, seconds)
    try:
        t0 = time.monotonic()
        verdict = probe(url, model, server.engine, seed, cell.reference)
        say(f"probes {time.monotonic() - t0:.1f}s: {verdict}")
        run = run_window(cell, server, url, model, seed, seconds, trace,
                         out_dir, meter, trace_seconds)
    finally:
        server.shutdown()
    c = meter.snapshot()
    say(f"compile requests in the whole run {c['requests']} (persistent "
        f"cache: {c['hits']} warm, {c['misses']} cold); entries after "
        f"{compile_cache.entries(cache_dir)}")
    summary = stats.summarize(run["records"], cell.traffic["loop"],
                              run["t_window"], run["t_end"])
    setup_s = run["t_window"] - t_proc0
    late = summary["loadgen_late_ms"]
    say(f"window {seconds}s: attempted {summary['attempted']} failed "
        f"{summary['failed']} cut {summary['cut']} tokens_in_window "
        f"{summary['tokens_in_window']} errors {summary['errors']}")
    say("loadgen_late_p95_ms "
        + (f"{stats.percentile(late, 95):.3f}" if late else "n/a")
        + " max_gap_p95_ms "
        + (f"{stats.percentile(summary['max_gap_ms'], 95):.3f}"
           if summary["max_gap_ms"] else "n/a")
        + f" steps_in_window {len(run['steps'])} kv_bytes_per_token "
        f"{run['kv_bytes_per_token']}")
    correct = verdict["ok"]
    if run["compiles_in_window"]:
        correct = False
        say(f"NOT A MEASUREMENT: {run['compiles_in_window']} compile "
            f"request(s) inside the window; executables first dispatched "
            f"there: {run['new_executables']}")
    if summary["attempted"] == 0:
        correct = False
        say("NOT A MEASUREMENT: no request belongs to the window")
    device["memory_peak_bytes"] = memory_peak_bytes()
    result = {"correct": bool(correct), "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": {}, "device": device}
    compared = (
        f"correct {bool(correct)}: logprob_diff_max "
        f"{verdict.get('logprob_diff_max')} (limit {LOGPROB_ATOL}); "
        f"tie_gap_max {verdict.get('tie_gap_max')} (limit {TIE_ATOL}); "
        f"positions {verdict.get('positions')} (of "
        f"{len(PROBE_PROMPTS) * PROBE_TOKENS}); compiles_in_window "
        f"{run['compiles_in_window']} (limit 0); attempted "
        f"{summary['attempted']} (over 0)")
    say(compared)
    result["compared"] = compared
    units = cell.units
    if not trace:
        for name in cell.end_to_end:
            value = setup_s if name == "setup_s" \
                else stats.end_to_end(name, summary)
            result["metrics"][name] = {"value": value, "unit": units[name]}
        return result
    reduced = None
    xplane = find_xplane(run["trace_dir"])
    if xplane:
        t0 = time.monotonic()
        reduced = trace_reduce.reduce(xplane)
        say(f"trace {os.path.getsize(xplane)} bytes reduced in "
            f"{time.monotonic() - t0:.1f}s")
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, keep_trace)
    if reduced is None or reduced["busy_s"] <= 0:
        raise Refused("the traced run saw no operation on the device")
    say("/metrics counters over the window (end - start, those that moved): "
        + json.dumps(moved_counters(run["metrics_start"],
                                    run["metrics_end"]), sort_keys=True))
    run["trace"] = reduced
    run["summary"] = summary
    run["peaks"] = plan.read_json(peaks_path)["devices"][device["kind"]]
    readers = plan.discover_layer_metrics(plan.BENCH_ROOT)
    for name in cell.per_layer:
        value = readers[name].compute(run)
        if value is not None:
            result["metrics"][name] = {"value": float(value),
                                       "unit": units[name]}
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    result["breakdown"] = {"device_ops": reduced["ops"][:10],
                           "idle_gaps": idle_gaps(run)}
    return result

