#!/usr/bin/env python
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the cell's chips: it builds the real server, warms
the cell's own shapes, checks the served path against the plain reference,
and measures a window of traffic sent by a child process that never imports
JAX.  ``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` is
a run of its own that also takes a profiler trace of the window's last
seconds and prints the per-layer metrics.  The last line of stdout is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``); everything else is on earlier
lines, and the last line of stderr holds each number compared for
``correct`` beside its limit.  Off the TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result line.

Which configuration, traffic mix, cell, per-layer metric and reference
exist is data: ``BENCHMARK.json`` and the files under ``benchmark/`` (see
``harness/plan.py``).  This file names none of them.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="", metavar="DIR",
                    help="also copy the traced run's .xplane.pb here")
    ap.add_argument("--trace-seconds", type=float, default=2.0,
                    help="how much of the window's end a traced run traces")
    args = ap.parse_args(argv)

    from benchmark.harness import plan, session
    cell = plan.load_cell(args.workload, plan.load_benchmark())
    out_dir = os.path.join(REPO_ROOT, "benchmark_out", cell.name)
    result = session.measure(
        cell, args.seed, args.seconds, bool(args.trace), T_PROCESS_START,
        out_dir, os.path.join(plan.BENCH_ROOT, "peaks.json"),
        keep_trace=args.keep_trace, trace_seconds=args.trace_seconds)
    print("[bench] " + result.pop("compared"), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
