"""Host milliseconds per decode cycle: the hostprof phases that are pure
host work (schedule, block accounting, dispatch, detokenize; not ``flush``,
which is the wait for the device), summed per step record of kind
``window`` or ``decode``, mean over the window."""

LAYER = "engine step"
UNIT = "ms"
BETTER = "lower"
MOVES = "tpot_p95_ms"
SOURCE = "program_span"

HOST_PHASES = ("schedule", "block", "dispatch", "detokenize")


def compute(run):
    per_cycle = [sum((s.get("phase_ms") or {}).get(p, 0.0)
                     for p in HOST_PHASES)
                 for s in run["steps"] if s["kind"] in ("window", "decode")]
    if not per_cycle:
        return None
    return sum(per_cycle) / len(per_cycle)
