"""Device milliseconds per fused decode step: the device time of the
decode-window program in the traced span (``XLA Modules`` events whose
name holds ``decode_multi``) over the decode steps the engine's step
records count in the same span (a window of S steps counts S)."""

LAYER = "model trunk"
UNIT = "ms"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    trace, span = run.get("trace"), run.get("trace_span")
    if not trace or not span:
        return None
    seconds = sum(m["seconds"] for name, m in trace["modules"].items()
                  if "decode_multi" in name)
    steps = sum(s["actual_tokens"] / s["rows"] for s in run["steps"]
                if s["kind"] == "window" and s["rows"]
                and span[0] <= s["t"] < span[1])
    if seconds <= 0 or steps <= 0:
        return None
    return seconds * 1e3 / steps
