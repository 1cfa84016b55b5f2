"""Mean wall milliseconds of a prefill or prefill-chunk engine step (step
records' ``ms``): host clock around a step that ends in a sync."""

LAYER = "engine step"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def compute(run):
    ms = [s["ms"] for s in run["steps"]
          if s["kind"] in ("prefill", "prefill_chunk")]
    if not ms:
        return None
    return sum(ms) / len(ms)
