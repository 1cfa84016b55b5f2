#!/usr/bin/env python
"""Find the knee of an open-loop cell, once, when the cell is defined.

    python benchmark/sweep.py --config <configuration> --traffic <mix> \
        --rates 4,6,8,12,16,24,32 --seconds 20 --seed 1

One process and one set-up: the server is built and warmed as a run of the
cell would, then the ladder of rates is offered one after the other through
the same child load generator, each step with its own pre-roll and drain.
The knee is the highest rate with no failed request and no growing backlog
(requests waiting unscheduled at the end of the step no more than at its
middle).  A cell's fixed rate is 0.8 of it, rounded down; it goes into the
cell file by hand, with this table into PERF.md.  Not part of a run of the
benchmark: the driver never calls this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmark.harness import plan, session, stats
    from benchmark.harness.meter import CompileMeter
    root = plan.BENCH_ROOT
    traffic_path = os.path.join(root, "traffic", args.traffic + ".json")
    config = plan.read_json(os.path.join(root, "configs",
                                         args.config + ".json"))
    mix = plan.read_json(traffic_path)
    device = session.device_info(os.path.join(root, "peaks.json"),
                                 config["chips"])
    from tpuserve.utils import compile_cache
    compile_cache.configure()
    meter = CompileMeter()
    out_dir = os.path.join(REPO_ROOT, "benchmark_out", "sweep")

    def cell_at(rate: float):
        return plan.Cell(name="sweep", chips=config["chips"],
                         config_name=args.config, config=config,
                         traffic_name=args.traffic, traffic=mix,
                         traffic_path=traffic_path, params={"rate": rate},
                         end_to_end=(), per_layer=())

    rates = [float(r) for r in args.rates.split(",")]

    def pct(values, q):
        return stats.percentile(values, q) if values else None

    # warm for the highest rate: its pool reaches furthest into the tails
    server, url, model = session.build(cell_at(max(rates)), meter,
                                       args.seconds)
    table = []
    try:
        for rate in rates:
            poller = session.Poller(url, 0.0, float("inf"))
            poller.start()
            # a seed of its own for each step: a repeated prompt would be
            # served from the prefix cache and its lower tiers
            run = session.run_window(cell_at(rate), server, url, model,
                                     args.seed + len(table), args.seconds,
                                     False, out_dir, meter)
            poller.stop()
            s = stats.summarize(run["records"], "open", run["t_window"],
                                run["t_end"])
            mid = (run["t_window"] + run["t_end"]) / 2
            quarter = args.seconds / 4

            def waiting(t_lo, t_hi):
                xs = [p.get("vllm_num_requests_waiting", 0.0)
                      for p in poller.samples if t_lo <= p["t"] < t_hi]
                return sum(xs) / len(xs) if xs else 0.0
            row = {
                "rate": rate, "attempted": s["attempted"],
                "failed": s["failed"],
                "ttft_p50_ms": pct(s["ttft_ms"], 50),
                "ttft_p95_ms": pct(s["ttft_ms"], 95),
                "tpot_p95_ms": pct(s["tpot_ms"], 95),
                "out_tok_s": s["tokens_in_window"] / s["seconds"],
                "waiting_mid": waiting(mid - quarter / 2, mid + quarter / 2),
                "waiting_end": waiting(run["t_end"] - quarter, run["t_end"]),
                "late_p95_ms": pct(s["loadgen_late_ms"], 95),
                "compiles": run["compiles_in_window"]}
            row["sustained"] = (row["failed"] == 0 and row["waiting_end"]
                                <= max(row["waiting_mid"], 1.0))
            table.append(row)
            session.say("sweep " + json.dumps(row))
    finally:
        server.shutdown()
    knee = max((r["rate"] for r in table if r["sustained"]), default=None)
    print(json.dumps({"device": device, "knee": knee, "table": table}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
