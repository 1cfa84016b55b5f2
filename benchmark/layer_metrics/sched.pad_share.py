"""Share of dispatched token slots that were padding: 1 - actual / padded
over every step record of the window (power-of-two batch and length
buckets, and fused windows that run past a request's last token)."""

LAYER = "scheduler"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "program_span"


def compute(run):
    padded = sum(s["padded_tokens"] for s in run["steps"])
    if padded <= 0:
        return None
    actual = sum(s["actual_tokens"] for s in run["steps"])
    return 100.0 * (1.0 - actual / padded)
