"""Cut a recorded ``.xplane.pb`` down to a fixture that KEEPS what names the
device's time: each operation's ``tf_op`` (the HLO ``op_name``, with the
program's named scopes in it) and ``program_id``.

    python benchmark/fixtures/crop_scopes.py <in.xplane.pb[.gz]> <out.xplane.pb.gz> <from_ms> <ms> <layers>

``crop_trace.py`` and ``crop_spans.py`` drop every stat; the scopes are a
stat of the operations' event METADATA (``layer_metrics/_scope_trace.py``
says how that was found).  Keeps the device planes' ``XLA Ops`` and ``XLA
Modules`` events that start in the ``<ms>`` milliseconds beginning
``<from_ms>`` after the first operation (``auto``: 2 ms before the first
prefill program that starts after a decode window did, so that both
phases are in it), with their names, starts and durations and those two
stats.  ``<layers>`` is the configuration's depth, which the count of
fused decode steps divides by.  Writes the cropped trace (gzip) and
``<name>.expected.json``: what ``trace_reduce.reduce`` read from it (as
``crop_trace.py`` records) and, under ``scopes``, what ``_scope_trace``
and the seven readers read.
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

READERS = ("trunk.unscoped_device_share", "step.prefill_device_share",
           "trunk.decode_proj_ms", "trunk.decode_head_ms",
           "trunk.decode_glue_ms", "moe.around_gmm_device_share",
           "ssm.prefill_scan_device_share")


def plane_text(name: str, t0: int, lines: dict) -> str:
    """One plane in text form; ``lines`` is ``{line name: [(start, end,
    event name, op_name, program)]}``, times in nanoseconds."""
    metas: dict = {}
    body = []
    for lid, (lname, events) in enumerate(sorted(lines.items()), 1):
        body.append(f"  lines {{ id: {lid} name: {quote(lname)} "
                    f"timestamp_ns: {t0}")
        for s, e, *what in events:
            mid = metas.setdefault(tuple(what), len(metas) + 1)
            body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                        f"{(s - t0) * 1000} duration_ps: {(e - s) * 1000} }}")
        body.append("  }")
    meta = []
    for (ename, op_name, program), mid in metas.items():
        stats = ""
        if op_name:
            stats += f" stats {{ metadata_id: 1 str_value: {quote(op_name + ':')} }}"
        if program:
            stats += f" stats {{ metadata_id: 2 str_value: {quote(program)} }}"
        meta.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                    f"name: {quote(ename)}{stats} }} }}")
    meta.append('  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }')
    meta.append('  stat_metadata { key: 2 value { id: 2 name: "program_id" } }')
    return "planes {\n  name: " + quote(name) + "\n" \
        + "\n".join(body + meta) + "\n}"


def main(src: str, dst: str, from_ms: str, ms: float, layers: int) -> None:
    import jax

    from benchmark.harness import plan
    from benchmark.harness import trace_reduce as tr
    from benchmark.layer_metrics import _scope_trace as st
    data = tr.load(src)
    chips = st.read_ops(src)
    out = []
    for plane, ops in zip([p for p in data.planes
                           if tr.DEVICE_PLANE.match(p.name)], chips):
        modules = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name,
                    "", "") for line in plane.lines
                   if line.name == tr.MODULES_LINE for e in line.events]
        first = min(s for s, *_ in ops)
        if from_ms == "auto":
            window = min(s for s, _, n, *_ in modules if "decode_multi" in n)
            t0 = min(s for s, _, n, *_ in modules
                     if s > window and ("forward_ragged" in n
                                        or "prefill" in n)) - 2_000_000
        else:
            t0 = first + int(float(from_ms) * 1e6)
        t1 = t0 + int(ms * 1e6)
        out.append(plane_text(plane.name, t0, {
            tr.OPS_LINE: [o for o in ops if t0 <= o[0] < t1],
            tr.MODULES_LINE: [m for m in modules if t0 <= m[0] < t1]}))
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        "\n".join(out))
    readers = plan.discover_layer_metrics(plan.BENCH_ROOT)
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = os.path.join(tmp, "trace")
        os.mkdir(trace_dir)
        with open(os.path.join(trace_dir, "crop.xplane.pb"), "wb") as f:
            f.write(raw)
        run = {"trace_dir": trace_dir,
               "config": {"num_hidden_layers": layers}}
        metrics = {name: readers[name].compute(run) for name in READERS}
        m = st.measure(run)
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(raw)
    expected = dst.replace(".xplane.pb.gz", ".expected.json")
    with open(expected, "w") as f:
        json.dump({**tr.reduce(dst), "scopes": {
            "from": os.path.basename(src), "from_ms": (t0 - first) / 1e6,
            "ms": ms, "layers": layers, "busy_s": m["busy_s"],
            "decode_steps": m["decode_steps"],
            "seconds": {f"{phase}/{part}": s for (phase, part), s
                        in sorted(m["scopes"].items())},
            "inherited": m["inherited"],
            "calls": {f"{kernel}@{phase}": n for (kernel, phase), n
                      in sorted(m["calls"].items())},
            "metrics": metrics}}, f, indent=1)
    print(os.path.getsize(dst), "bytes;", metrics)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3], float(sys.argv[4]),
         int(sys.argv[5]))
