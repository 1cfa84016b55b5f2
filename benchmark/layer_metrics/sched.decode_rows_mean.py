"""Mean number of sequences in a decode dispatch (step records of kind
``window`` and ``decode``): how full the decode batch ran."""

LAYER = "scheduler"
UNIT = "rows"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "program_span"


def compute(run):
    rows = [s["rows"] for s in run["steps"] if s["kind"] in ("window", "decode")]
    if not rows:
        return None
    return sum(rows) / len(rows)
