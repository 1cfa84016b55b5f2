"""Share of the window's wall time the engine loop spent blocked on the
device: devprof's ``sync`` milliseconds (host seconds inside
``device_get``; the step records' ``dev.device_ms``) over the window.  A
HOST measure: low means the host sets the pace, not that the device is
idle."""

LAYER = "engine step"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "program_span"


def compute(run):
    if not run["steps"]:
        return None
    blocked = sum((s.get("dev") or {}).get("device_ms", 0.0)
                  for s in run["steps"])
    return 100.0 * blocked / (run["seconds"] * 1e3)
