"""Cut a recorded ``.xplane.pb`` down to a fixture that keeps the engine
loop's spans beside the device's lines.

    python benchmark/fixtures/crop_spans.py <in.xplane.pb> <steps.json> <out.xplane.pb.gz> <from_ms> <ms>

``crop_trace.py`` drops the host planes; this keeps, for the ``<ms>``
milliseconds that start ``<from_ms>`` after the first device operation
(``auto``: where the first whole ``engine.step`` span of a decode window
starts):
the device planes' ``XLA Ops`` and ``XLA Modules`` events that start there,
and the one host line that holds the ``engine.step`` spans, cut to the
engine loop's own spans (``harness/host_spans.py`` knows which), clipped to
the window, with ``engine.step``'s ``seq``.  ``<steps.json>`` is the
``host_spans.steps.json`` the traced run left beside its trace.  Writes the
cropped trace (gzip) and ``<name>.expected.json``: what ``trace_reduce.reduce``
read from it (as ``crop_trace.py`` records) and, under ``host_spans``, the step
records the kept spans join and what the five readers read from the crop.
"""

import gzip
import json
import os
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)

from benchmark.fixtures.crop_trace import quote  # noqa: E402

READERS = ("idle.kv_demote_share", "idle.host_loop_share", "idle.sync_share",
           "idle.unattributed_share", "kernel.decode_attn_ns_per_ctx_tok")


def plane_text(name: str, t0: int, lines: dict) -> str:
    """One plane in text form; ``lines`` is ``{line name: [(start, end,
    event name, seq or None)]}``, times in nanoseconds."""
    names: dict = {}
    body = []
    for lid, (lname, events) in enumerate(sorted(lines.items()), 1):
        body.append(f"  lines {{ id: {lid} name: {quote(lname)} "
                    f"timestamp_ns: {t0}")
        for s, e, ename, seq in events:
            mid = names.setdefault(ename, len(names) + 1)
            stat = "" if seq is None else \
                f" stats {{ metadata_id: 1 int64_value: {seq} }}"
            body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                        f"{(s - t0) * 1000} duration_ps: {(e - s) * 1000}"
                        f"{stat} }}")
        body.append("  }")
    meta = [f"  event_metadata {{ key: {mid} value {{ id: {mid} name: "
            f"{quote(n)} }} }}" for n, mid in names.items()]
    meta.append('  stat_metadata { key: 1 value { id: 1 name: "seq" } }')
    return "planes {\n  name: " + quote(name) + "\n" \
        + "\n".join(body + meta) + "\n}"


def main(src: str, steps_path: str, dst: str, from_ms: str,
         ms: float) -> None:
    import jax

    from benchmark.harness import host_spans as hs
    from benchmark.harness import plan
    from benchmark.harness import trace_reduce as tr
    data = jax.profiler.ProfileData.from_file(src)
    first = min(int(e.start_ns) for plane in data.planes
                if tr.DEVICE_PLANE.match(plane.name)
                for line in plane.lines if line.name == tr.OPS_LINE
                for e in line.events)
    with open(steps_path) as f:
        steps = {s["seq"]: s for s in json.load(f)}
    if from_ms == "auto":
        t0 = min(s for s, _, name, seq in hs.loop_spans(data)
                 if s >= first and seq in steps
                 and steps[seq]["kind"] == "window")
    else:
        t0 = first + int(float(from_ms) * 1e6)
    t1 = t0 + int(ms * 1e6)
    out = []
    for plane in data.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: [(int(e.start_ns),
                            int(e.start_ns + e.duration_ns), e.name, None)
                           for e in ln.events if t0 <= e.start_ns < t1]
                 for ln in plane.lines
                 if ln.name in (tr.OPS_LINE, tr.MODULES_LINE)}
        out.append(plane_text(plane.name, t0, lines))
    spans = [(max(s, t0), min(e, t1), name, seq)
             for s, e, name, seq in hs.loop_spans(data) if s < t1 and e > t0]
    out.append(plane_text("/host:CPU", t0, {"engine-loop": spans}))
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        "\n".join(out))
    kept = {seq for _, _, _, seq in spans}
    steps = [s for seq, s in steps.items() if seq in kept]
    readers = plan.discover_layer_metrics(plan.BENCH_ROOT)
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = os.path.join(tmp, "trace")
        os.mkdir(trace_dir)
        with open(os.path.join(trace_dir, "crop.xplane.pb"), "wb") as f:
            f.write(raw)
        run = {"trace": {"busy_s": 1.0}, "trace_dir": trace_dir,
               "steps": steps}
        metrics = {name: readers[name].compute(run) for name in READERS}
        result = hs.analyse(run)
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(raw)
    expected = dst.replace(".xplane.pb.gz", ".expected.json")
    with open(expected, "w") as f:
        # what trace_reduce reads, as crop_trace.py records it, and beside
        # it what host_spans and the five readers read
        json.dump({**tr.reduce(dst), "host_spans": {
            "from": os.path.basename(src), "from_ms": (t0 - first) / 1e6,
            "ms": ms, "metrics": metrics, "idle_ns": result["idle_ns"],
            "by_span": result["by_span"], "steps": steps}}, f, indent=1)
    print(os.path.getsize(dst), "bytes;", len(spans), "spans;", metrics)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4],
         float(sys.argv[5]))
