"""Cut a recorded ``.xplane.pb`` down to a fixture small enough to commit.

    python benchmark/fixtures/crop_trace.py <in.xplane.pb> <out.xplane.pb.gz> <milliseconds>

Keeps the device planes' ``XLA Ops`` and ``XLA Modules`` lines, only the
events that start in the first ``<milliseconds>`` after the first
operation, with their recorded names, starts and durations; drops stats,
host planes and the other lines.  Writes the cropped trace (gzip) and,
beside it, ``<name>.expected.json``: what ``trace_reduce.reduce`` read
from it when it was recorded.
"""

import gzip
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)


def quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n") + '"'


def main(src: str, dst: str, ms: float) -> None:
    import jax

    from benchmark.harness import trace_reduce as tr
    data = jax.profiler.ProfileData.from_file(src)
    out = []
    for plane in data.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines
                 if ln.name in (tr.OPS_LINE, tr.MODULES_LINE)}
        t0 = min(e.start_ns for e in lines[tr.OPS_LINE])
        t1 = t0 + ms * 1e6
        names: dict = {}
        body = []
        for lid, (lname, events) in enumerate(sorted(lines.items()), 1):
            body.append(f"  lines {{ id: {lid} name: {quote(lname)} "
                        f"timestamp_ns: {int(t0)}")
            for e in events:
                if not t0 <= e.start_ns < t1:
                    continue
                mid = names.setdefault(e.name, len(names) + 1)
                body.append(
                    f"    events {{ metadata_id: {mid} offset_ps: "
                    f"{int(round((e.start_ns - t0) * 1000))} duration_ps: "
                    f"{int(round(e.duration_ns * 1000))} }}")
            body.append("  }")
        meta = [f"  event_metadata {{ key: {mid} value {{ id: {mid} name: "
                f"{quote(name)} }} }}" for name, mid in names.items()]
        out.append("planes {\n  name: " + quote(plane.name) + "\n"
                   + "\n".join(body + meta) + "\n}")
    raw = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        "\n".join(out))
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(raw)
    reduced = tr.reduce(dst)
    expected = dst.replace(".xplane.pb.gz", ".expected.json")
    with open(expected, "w") as f:
        json.dump(reduced, f, indent=1)
    print(os.path.getsize(dst), "bytes;", {k: reduced[k] for k in
                                           ("chips", "busy_s", "window_s")})


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], float(sys.argv[3]))
