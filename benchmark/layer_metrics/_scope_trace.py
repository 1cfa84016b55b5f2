"""Device time by model part, from the trace alone (the leading underscore
keeps ``plan.discover_layer_metrics`` from taking this for a metric).

The program opens a ``jax.named_scope`` on every phase and part of every
trunk (``tpuserve/ops/scopes.py`` holds the table).  JAX writes the scope
path into each HLO instruction's ``op_name``, and the profiler carries it
into the trace as the stat ``tf_op`` of the operation's EVENT METADATA
("jit(decode_multi)/decode/while/body/closed_call/mlp/dot_general:"), not
of the event: ``jax.profiler.ProfileData`` shows an event's own stats only
(found on the chip, PERF.md §3), so this file reads the ``.xplane.pb``'s
wire format itself (``read_ops``; field numbers of tsl's ``xplane.proto``.
No compiled schema of it comes with JAX; the copy inside TensorFlow takes
11 s to import and is a package the benchmark does not otherwise need).

Per chip, every ``XLA Ops`` event's SELF time (``trace_reduce.self_times``)
is filed under ``(phase, part)``: the first path component that names a
phase and the last that names a part (parts nest: ``mlp/moe.route``);
``(phase, "")`` where only the phase is known, ``("", "")`` where nothing
is.  What the COMPILER made carries no ``op_name`` at all (the wait for a
prefetched slice of a weight matrix, ``slice-done``; a layout ``copy``; a
``copy-done``): such an event is filed under the next event of its program
that names a PART, which is its consumer (the compiler schedules the wait
right before the use; held on the compiled text,
tests/test_chip_compile.py).  A bare ``while`` or phase names no consumer
and is passed over; and an event that carries a loop's own ``op_name``
(".../while") without being that loop is the compiler's too: inside a
pipelined loop its prefetches are named after the loop
(``compiler_made``).  The inherited seconds are also kept by kind under
``inherited``.  Busy time is the union of the events, as ``trace_reduce``
takes it; the scopes' seconds add up to it.

**The count comes from the same events as the time:** a fused decode step
runs the paged decode kernel (``_paged_decode_attention``) once a layer, so
the steps in the span are its calls under ``decode/`` over the layers that
run.  ``measure(run)`` prints it beside what the step records give (the
count ``step.decode_device_ms`` divides by, and the ``seq`` join's), with
the same reckoning for the other two kernels a reader divides by rows.

``measure(run)`` returns ``{"busy_s", "scopes": {(phase, part): seconds},
"decode_steps", "inherited", "unscoped", "calls"}`` (per chip), or None
where the run has no trace or the trace names no scope (a program from
before they existed): the seven readers then report nothing.
"""

import gzip

from benchmark.harness import trace_reduce as tr

PHASES = ("decode", "prefill", "chunk", "verify", "draft", "score")
PARTS = ("embed", "attn.qkv", "attn.kv_write", "attn.kernel", "attn.out",
         "mlp", "moe.route", "moe.gather", "moe.experts", "moe.combine",
         "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out", "head", "sample",
         "carry")
PREFILL_PHASES = ("prefill", "chunk")
DECODE_KERNEL = "_paged_decode_attention"
#: the kernels whose readers divide by a count of the ``seq`` join
COUNTED = (DECODE_KERNEL, "_ssm_state_update", "_moe_grouped_matmul")
_KEY = "_scope_trace"


# ---- the xplane's wire format ---------------------------------------------

def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif wire == 1:
            v = buf[i:i + 8]
            i += 8
        elif wire == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, v


def _stat(buf, names):
    """``(name, value)`` of an XStat that holds a string, a reference to
    one (``ref_value`` names another stat's metadata, whose name is the
    string) or a whole number; the two stats read here are strings."""
    f = dict(_fields(buf))
    if 5 in f:
        v = bytes(f[5]).decode("utf8", "replace")
    elif 7 in f:
        v = names.get(f[7], "")
    else:
        v = f.get(3, f.get(4))
    return names.get(f.get(1)), v


def read_ops(path: str) -> list:
    """Per device plane, the ``XLA Ops`` events as ``(start_ns, end_ns,
    name, op_name, program)``: ``op_name`` the metadata's ``tf_op`` without
    its trailing ``:type`` ("" where the compiler made the operation),
    ``program`` its ``program_id``."""
    with (gzip.open(path, "rb") if path.endswith(".gz")
          else open(path, "rb")) as f:
        raw = memoryview(f.read())
    chips = []
    for field, plane in _fields(raw):
        if field != 1:
            continue
        name, lines, metas, names = "", [], {}, {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 3:
                lines.append(v)
            elif f == 4:
                kv = dict(_fields(v))
                metas[kv[1]] = kv[2]
            elif f == 5:
                kv = dict(_fields(v))
                names[kv[1]] = bytes(dict(_fields(kv[2])).get(2, b"")) \
                    .decode()
        if not tr.DEVICE_PLANE.match(name):
            continue
        for line in lines:
            lname, t0, events = "", 0, []
            for f, v in _fields(line):
                if f == 2:
                    lname = bytes(v).decode()
                elif f == 3:
                    t0 = v
                elif f == 4:
                    events.append(v)
            if lname != tr.OPS_LINE:
                continue
            known, ops = {}, []
            for v in events:
                ev = {k: x for k, x in _fields(v) if k != 4}
                mid = ev[1]
                if mid not in known:
                    ename, stats = "", {}
                    for k, x in _fields(metas[mid]):
                        if k == 2:
                            ename = bytes(x).decode("utf8", "replace")
                        elif k == 5:
                            key, val = _stat(x, names)
                            stats[key] = val
                    known[mid] = (ename,
                                  str(stats.get("tf_op") or "")
                                  .rsplit(":", 1)[0],
                                  str(stats.get("program_id", "")))
                # ProfileData's clock: the line's stamp plus the offset
                start = t0 + ev.get(2, 0) / 1000.0
                ops.append((int(start), int(start + ev.get(3, 0) / 1000.0),
                            *known[mid]))
            if ops:
                chips.append(ops)
    return chips


# ---- from events to scopes ------------------------------------------------

def scope_of(op_name: str) -> tuple:
    """``(phase, part)`` of an ``op_name`` path; "" for what it lacks."""
    comps = op_name.split("/")
    return (next((c for c in comps if c in PHASES), ""),
            next((c for c in reversed(comps) if c in PARTS), ""))


def compiler_made(name: str, op_name: str) -> bool:
    """No ``op_name`` at all, or a loop's own (".../while") on an event
    that is not that loop: what the compiler prefetches inside a pipelined
    ``while`` carries the loop's name (Falcon-H1's ``slice-done``s)."""
    return not op_name or (op_name.endswith("/while")
                           and tr.op_kind(name) != "while")


def file_chip(ops: list) -> dict:
    """One chip's events -> ``{"busy_ns", "scopes": {(phase, part): ns},
    "inherited": {kind: ns}, "unscoped": {kind: ns}, "calls": {(kernel,
    phase): n}}``."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    # a compiler-made event takes the scope of the next event of its
    # program that names a PART (a bare ``while`` or phase names no
    # consumer)
    scopes, nxt = [None] * len(ops), {}
    made = [compiler_made(name, op_name) for _, _, name, op_name, _ in ops]
    for i in range(len(ops) - 1, -1, -1):
        _, _, _, op_name, program = ops[i]
        if made[i]:
            scopes[i] = nxt.get(program)
        else:
            scopes[i] = scope_of(op_name)
            if scopes[i][1]:
                nxt[program] = scopes[i]
    busy, _ = tr.union_and_gaps([(s, e) for s, e, *_ in ops])
    out = {"busy_ns": busy, "scopes": {}, "inherited": {}, "unscoped": {},
           "calls": {}}
    for i, self_ns in tr.self_times([(s, e, i) for i, (s, e, *_)
                                     in enumerate(ops)]):
        scope = scopes[i] or ("", "")
        out["scopes"][scope] = out["scopes"].get(scope, 0) + self_ns
        kind = tr.op_kind(ops[i][2])
        if made[i] and scopes[i]:
            out["inherited"][kind] = out["inherited"].get(kind, 0) + self_ns
        if not scope[1]:
            out["unscoped"][kind] = out["unscoped"].get(kind, 0) + self_ns
        if kind in COUNTED:
            key = (kind, scope[0])
            out["calls"][key] = out["calls"].get(key, 0) + 1
    return out


def reduce(path: str, layers: int):
    """The trace at ``path`` by scope, per chip (seconds and calls averaged
    over the chips); None where it has no device plane or names no scope."""
    chips = [file_chip(ops) for ops in read_ops(path)]
    if not chips or not any(any(scope) for c in chips for scope in c["scopes"]):
        return None
    n = len(chips)

    def mean(key):
        total = {}
        for c in chips:
            for k, v in c[key].items():
                total[k] = total.get(k, 0) + v / n
        return total

    calls = mean("calls")
    return {"busy_s": sum(c["busy_ns"] for c in chips) / n * 1e-9,
            "scopes": {k: v * 1e-9 for k, v in mean("scopes").items()},
            "inherited": {k: v * 1e-9 for k, v in mean("inherited").items()},
            "unscoped": {k: v * 1e-9 for k, v in mean("unscoped").items()},
            "calls": calls,
            "decode_steps": calls.get((DECODE_KERNEL, "decode"), 0) / layers}


def seconds(m: dict, phases=None, parts=None) -> float:
    """Seconds of ``m["scopes"]`` under these phases and parts (None: any)."""
    return sum(s for (phase, part), s in m["scopes"].items()
               if (phases is None or phase in phases)
               and (parts is None or part in parts))


def per_decode_step_ms(run, parts) -> float | None:
    """Milliseconds under ``decode/`` in ``parts`` per fused decode step."""
    m = measure(run)
    if m is None or m["decode_steps"] <= 0:
        return None
    return seconds(m, ("decode",), parts) * 1e3 / m["decode_steps"]


def share_of_busy(run, phases=None, parts=None) -> float | None:
    """Per cent of busy time under these phases and parts; None where the
    trace has no such time at all."""
    m = measure(run)
    if m is None or m["busy_s"] <= 0:
        return None
    s = seconds(m, phases, parts)
    return 100.0 * s / m["busy_s"] if s > 0 else None


def fused_steps(steps: list) -> float:
    """Fused decode steps of step records: a window of S steps over n rows
    holds ``actual_tokens`` = n S."""
    return sum(s["actual_tokens"] / s["rows"] for s in steps
               if s.get("kind") in ("window", "decode") and s.get("rows"))


def say(run, m: dict, layers: int) -> None:
    """The earlier lines of a traced run: the partition, and the trace's
    own counts beside the step records'."""
    busy = m["busy_s"]
    print(f"[bench] device time by scope (self seconds of {busy:.4f} busy; "
          f"sum {sum(m['scopes'].values()):.4f}):")
    for (phase, part), s in sorted(m["scopes"].items(), key=lambda kv: -kv[1]):
        print(f"[bench]   {phase or '-':8s} {part or '-':14s} {s:.6f}s "
              f"{100 * s / busy:5.1f}%")
    for what, kinds in (
            ("filed under the next part of its program (the compiler's own "
             "operations, no op_name)", m["inherited"]),
            ("under no part", m["unscoped"])):
        print(f"[bench] of it {what}: " + ", ".join(
            f"{k} {v:.4f}s" for k, v in
            sorted(kinds.items(), key=lambda kv: -kv[1])[:8]))
    span = run.get("trace_span")
    by_time = fused_steps([s for s in run.get("steps", ())
                           if span and span[0] <= s["t"] < span[1]])
    joined = None
    if "steps" in run:
        from benchmark.harness import host_spans
        joined = (host_spans.analyse(run) or {}).get("steps_joined")
    print(f"[bench] fused decode steps in the traced span: "
          f"{m['decode_steps']:.2f} by the trace (calls of {DECODE_KERNEL} "
          f"under decode/ over {layers} layers), {by_time:.2f} by the step "
          "records' stamps (what step.decode_device_ms divides by), "
          + (f"{fused_steps(joined):.2f} by the seq join "
             f"({len(joined)} records)" if joined is not None
             else "no seq join"))
    for kernel in COUNTED[1:]:
        n = {p: c for (k, p), c in m["calls"].items() if k == kernel}
        if n:
            per = layers * (3 if kernel == "_moe_grouped_matmul" else 1)
            print(f"[bench] calls of {kernel} by phase: "
                  + ", ".join(f"{p or '-'} {c:.0f} (= {c / per:.2f} "
                              "dispatch-steps)" for p, c in sorted(n.items())))


def measure(run):
    """See the module docstring; computed once and kept on ``run``."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    if not run.get("trace_dir"):
        return None
    from benchmark.harness.session import find_xplane
    path = find_xplane(run["trace_dir"])
    if not path:
        return None
    layers = run["config"]["num_hidden_layers"]
    m = reduce(path, layers)
    if m is not None:
        say(run, m, layers)
    run[_KEY] = m
    return m
