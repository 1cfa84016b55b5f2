"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX.

Reads the file with ``jax.profiler.ProfileData``.  A TPU trace has one
plane per chip (``/device:TPU:<n>``); on it the line ``XLA Ops`` carries
one event per executed HLO operation (nested where an operation contains
others, as a ``while`` does its body) and the line ``XLA Modules`` one
event per executed program.  Everything is reduced by the names the trace
printed: the program gives its kernels and steps no stable names yet.

    busy_s      union of the ``XLA Ops`` intervals, averaged over the chips
    window_s    the traced span: first start to last end over the chips
    ops         self time by operation name (an operation's time less its
                children's), summed over chips, most expensive first
    modules     count and seconds by program name, averaged over the chips
    gaps        idle seconds on the busiest chip by what ran before and
                after each gap ("jit_a -> jit_b": the device had finished
                program a and waited for the host to launch b), largest
                first; ``longest_gap_s`` is the single longest
    classes     self seconds by class of operation: ``collective`` (an
                all-reduce and its kin), ``kernel`` (a custom call, which
                is how a Pallas kernel appears), ``other``

``reduce(path)`` returns that as a dict; where the trace has no device
plane it returns None, and the caller then has nothing to report.
"""

from __future__ import annotations

import gzip
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# An operation is printed as its HLO instruction, "%name.12 = shape
# opcode(operands), attributes": the name before " = " says what it is (a
# Pallas kernel carries its function's name there), the rest which class.
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
KERNEL = re.compile(r"tpu_custom_call")
# "fusion.123" and "fusion.7" are one kind of operation; a program is
# printed as "jit_name(fingerprint)"
_OP_SUFFIX = re.compile(r"[.\d]+$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def load(path: str):
    import jax
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(path)


def op_kind(name: str) -> str:
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _OP_SUFFIX.sub("", head) or head


def op_class(name: str) -> str:
    if KERNEL.search(name):
        return "kernel"
    if COLLECTIVE.search(name) or COLLECTIVE.search(op_kind(name) + "("):
        return "collective"
    return "other"


def union_and_gaps(intervals: list) -> tuple:
    """(covered length, gaps as (start, length)) of (start, end) pairs."""
    covered, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            covered += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        covered += cur_e - cur_s
    return covered, gaps


def self_times(events: list) -> list:
    """(name, self duration) of (start, end, name) events that nest: a
    parent's self time is its own less its direct children's."""
    out, stack = [], []          # stack of [end, name, own, children]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][0]:
            end, n, own, kids = stack.pop()
            out.append((n, max(0, own - kids)))
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0])
    while stack:
        end, n, own, kids = stack.pop()
        out.append((n, max(0, own - kids)))
    return out


def name_gaps(gaps: list, modules: list) -> list:
    """Idle time by the programs on either side of each gap, largest sum
    first: [("jit_a -> jit_b", total length), ...]."""
    import bisect
    mods = sorted(modules)
    starts = [m[0] for m in mods]
    total = {}
    for start, length in gaps:
        i = bisect.bisect_right(starts, start) - 1
        j = bisect.bisect_left(starts, start + length)
        before = _MODULE_SUFFIX.sub("", mods[i][2]) if i >= 0 else "start"
        after = _MODULE_SUFFIX.sub("", mods[j][2]) if j < len(mods) \
            else "end"
        if i >= 0 and mods[i][1] > start + length:
            before = after = _MODULE_SUFFIX.sub("", mods[i][2])  # inside it
        key = f"{before} -> {after}"
        total[key] = total.get(key, 0) + length
    return sorted(total.items(), key=lambda kv: -kv[1])


def reduce(path: str, top: int = 10) -> dict | None:
    data = load(path)
    chips = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                        e.name) for e in line.events]
            elif line.name == MODULES_LINE:
                modules = [(int(e.start_ns),
                            int(e.start_ns + e.duration_ns), e.name)
                           for e in line.events]
        if ops:
            chips.append((plane.name, ops, modules))
    if not chips:
        return None
    n = len(chips)
    t0 = min(s for _, ops, _ in chips for s, _, _ in ops)
    t1 = max(e for _, ops, _ in chips for _, e, _ in ops)
    busy, op_s, class_s, module_s, module_n = [], {}, {}, {}, {}
    busiest_gaps, busiest_modules, busiest = [], [], -1
    for _, ops, modules in chips:
        covered, gaps = union_and_gaps([(s, e) for s, e, _ in ops])
        busy.append(covered)
        if covered > busiest:
            busiest, busiest_gaps, busiest_modules = covered, gaps, modules
        for name, dur in self_times(ops):
            kind = op_kind(name)
            op_s[kind] = op_s.get(kind, 0) + dur
            cls = op_class(name)
            class_s[cls] = class_s.get(cls, 0) + dur
        for s, e, name in modules:
            key = _MODULE_SUFFIX.sub("", name)
            module_s[key] = module_s.get(key, 0) + (e - s)
            module_n[key] = module_n.get(key, 0) + 1
    ns = 1e-9
    return {
        "chips": n, "t0_ns": t0, "t1_ns": t1,
        "window_s": (t1 - t0) * ns,
        "busy_s": sum(busy) / n * ns,
        "ops": [[k, v / n * ns] for k, v in
                sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
        "classes": {k: v / n * ns for k, v in class_s.items()},
        "modules": {k: {"count": module_n[k] / n, "seconds": v / n * ns}
                    for k, v in module_s.items()},
        "gaps": [[k, v * ns] for k, v in name_gaps(
            busiest_gaps, busiest_modules)[:top]],
        "longest_gap_s": max((d for _, d in busiest_gaps), default=0) * ns,
    }
