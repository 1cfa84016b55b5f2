"""Share of device busy time spent in collectives (all-reduce and its
kin) under tensor parallelism; nothing to read on one chip."""

LAYER = "tp collectives"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    trace = run.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    coll = trace["classes"].get("collective")
    if not coll:
        return None
    return 100.0 * coll / trace["busy_s"]
