"""What the host was doing while the device idled.

The program's engine loop opens its spans (``tpuserve/runtime/hostprof.py``
holds the table) as ``jax.profiler.TraceAnnotation``s, so a traced run's
``.xplane.pb`` carries them on a line of the ``/host:CPU`` plane, on the
clock of the device planes.  This module opens the trace once per run,
finds that line (the one that holds ``engine.step`` events: thread names
are not reliable), takes the busiest chip's idle intervals
(``trace_reduce.union_and_gaps`` on its ``XLA Ops``) and gives every idle
nanosecond to the INNERMOST span open on the loop thread at that instant.

    classes     idle nanoseconds by class of span: ``kv_demote``,
                ``host_loop``, ``sync``, ``unattributed`` (the four
                ``idle.*`` metrics are their shares of ``idle_ns``)
    by_span     the same by span name ("" where no span was open)
    longest     the ten longest gaps: [start, length, {span: ns}]
    decode_attn_ns, decode_ctx_tokens
                self time of the paged decode kernel per chip, and the
                context tokens attended by the decode windows whose
                ``engine.step`` span (joined to the step records by its
                ``seq`` argument) lies in the trace

``analyse(run)`` returns that dict, or None where the run has no trace or
the trace has no such spans (a program from before they existed); it is
computed once and kept on ``run``.  A traced run prints the partition on
earlier lines of stdout and leaves the joined step records beside the
trace (``host_spans.steps.json``), which is what a fixture is cut from.
"""

from __future__ import annotations

import bisect
import json
import os

from . import trace_reduce as tr

ROOT = "engine.step"
DECODE_KERNEL = "_paged_decode_attention"
CLASSES = ("kv_demote", "host_loop", "sync", "unattributed")
_KV = {"kv.demote", "kv.restore", "sync.demote"}
_HOST = {"slo.admission", "schedule", "block", "dispatch", "sample",
         "detokenize", "step.close"}
_KEY = "_host_spans"


def span_class(name: str):
    """The class of a span name; None for an event that is not one of the
    engine loop's spans (the tracer's own events share the line)."""
    if name in _KV:
        return "kv_demote"
    if name in _HOST or name.startswith(("runner.", "dispatch.")):
        return "host_loop"
    if name.startswith("sync."):
        return "sync"
    if name == ROOT:
        return "unattributed"
    return None


def innermost(spans: list) -> list:
    """``(start, end, name)`` spans that nest by time on one thread, cut
    into pieces that do not overlap, each under the innermost span open
    there; in order of start.  A child is held inside its parent."""
    out, stack, cursor = [], [], 0       # stack of (end, name)

    def advance(t):
        nonlocal cursor
        if stack and t > cursor:
            out.append((cursor, t, stack[-1][1]))
        cursor = max(cursor, t)

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            advance(stack[-1][0])
            stack.pop()
        advance(s)
        if stack:
            e = min(e, stack[-1][0])
        if e > s:
            stack.append((e, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return out


def split_idle(gaps: list, pieces: list) -> list:
    """For each ``(start, length)`` gap, ``{span name: nanoseconds}`` by
    the piece that covers each instant ("" where none does)."""
    starts = [p[0] for p in pieces]
    out = []
    for g0, length in gaps:
        g1, shares, covered = g0 + length, {}, 0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(pieces) and pieces[i][0] < g1:
            s, e, name = pieces[i]
            n = min(e, g1) - max(s, g0)
            if n > 0:
                shares[name] = shares.get(name, 0) + n
                covered += n
            i += 1
        if length > covered:
            shares[""] = length - covered
        out.append(shares)
    return out


def partition(gaps: list, spans: list) -> dict:
    """Idle nanoseconds by span and by class; the classes add up to the
    idle time exactly."""
    per_gap = split_idle(gaps, innermost(spans))
    by_span: dict = {}
    for shares in per_gap:
        for name, n in shares.items():
            by_span[name] = by_span.get(name, 0) + n
    classes = dict.fromkeys(CLASSES, 0)
    for name, n in by_span.items():
        classes[span_class(name) or "unattributed"] += n
    longest = sorted(zip(gaps, per_gap), key=lambda x: -x[0][1])[:10]
    return {"idle_ns": sum(n for _, n in gaps), "classes": classes,
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
            "longest": [[g[0], g[1], shares] for g, shares in longest]}


def loop_spans(data) -> list:
    """``(start, end, name, seq or None)`` of the engine loop's spans: the
    host line with the most ``engine.step`` events, the tracer's own
    events dropped."""
    best, most = [], 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            n = sum(1 for e in events if e.name == ROOT)
            if n > most:
                best, most = events, n
    out = []
    for e in best:
        if span_class(e.name) is None:
            continue
        seq = dict(e.stats).get("seq") if e.name == ROOT else None
        out.append((int(e.start_ns), int(e.start_ns + e.duration_ns),
                    e.name, None if seq is None else int(seq)))
    return out


def busiest_chip(data) -> tuple:
    """``(gaps, decode kernel self nanoseconds per chip)`` from the device
    planes: the idle intervals of the chip with the most busy time."""
    gaps, most, kernel, chips = [], -1, 0, 0
    for plane in data.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
               for line in plane.lines if line.name == tr.OPS_LINE
               for e in line.events]
        if not ops:
            continue
        chips += 1
        covered, found = tr.union_and_gaps([(s, e) for s, e, _ in ops])
        if covered > most:
            most, gaps = covered, found
        kernel += sum(d for name, d in tr.self_times(ops)
                      if tr.op_kind(name) == DECODE_KERNEL)
    return gaps, kernel / max(chips, 1)


def attended(step: dict) -> float:
    """Context tokens a decode dispatch attends: a window of S steps over
    n rows reads its first step's ``ctx_tokens`` S times, and one more
    token a row each step."""
    n = step["rows"]
    if not n or step["kind"] not in ("window", "decode"):
        return 0.0
    s = step["actual_tokens"] / n
    return s * step["ctx_tokens"] + n * s * (s - 1) / 2


def analyse_trace(path: str, steps: list):
    data = tr.load(path)
    spans = loop_spans(data)
    if not spans:
        return None
    gaps, kernel_ns = busiest_chip(data)
    out = partition(gaps, [s[:3] for s in spans])
    by_seq = {s["seq"]: s for s in steps if "seq" in s}
    joined = [by_seq[q] for _, _, _, q in spans if q in by_seq]
    out["spans"] = len(spans)
    out["steps_joined"] = joined
    out["decode_attn_ns"] = kernel_ns
    out["decode_ctx_tokens"] = sum(attended(s) for s in joined
                                   if "ctx_tokens" in s)
    return out


def say(result: dict) -> None:
    idle = max(result["idle_ns"], 1)
    print("[bench] idle time of the busiest chip by the engine loop's "
          f"innermost span ({result['spans']} spans, "
          f"{len(result['steps_joined'])} steps joined by seq, idle "
          f"{result['idle_ns'] / 1e9:.4f}s):", flush=True)
    for name, n in result["by_span"].items():
        print(f"[bench]   {name or '(no span)':<24} {n / 1e9:.6f}s "
              f"{100 * n / idle:5.1f}%", flush=True)
    for start, length, shares in result["longest"]:
        top = ", ".join(f"{k or '(no span)'} {v / 1e6:.2f}ms"
                        for k, v in sorted(shares.items(),
                                           key=lambda kv: -kv[1])[:4])
        print(f"[bench]   gap {length / 1e6:8.2f}ms at {start}: {top}",
              flush=True)


def analyse(run: dict):
    """The analysis of a traced run (see the module docstring), or None."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    if not run.get("trace") or not run.get("trace_dir"):
        return None
    from .session import find_xplane
    path = find_xplane(run["trace_dir"])
    if not path:
        return None
    result = analyse_trace(path, run["steps"])
    if result is None:
        return None
    say(result)
    with open(os.path.join(os.path.dirname(run["trace_dir"]),
                           "host_spans.steps.json"), "w") as f:
        json.dump(result["steps_joined"], f)
    run[_KEY] = result
    return result


def idle_share(run: dict, cls: str):
    """One ``idle.*`` metric: the class's share of the idle time, in %."""
    result = analyse(run)
    if not result or result["idle_ns"] <= 0:
        return None
    return 100.0 * result["classes"][cls] / result["idle_ns"]
