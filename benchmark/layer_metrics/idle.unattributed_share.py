"""Share of the device's idle time in the traced span under no leaf span:
``engine.step``'s own time, or between spans.  The tracing's own health;
with the other three ``idle.*`` shares it sums to 100
(``benchmark/harness/host_spans.py``)."""

from benchmark.harness import host_spans

LAYER = "engine step"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return host_spans.idle_share(run, "unattributed")
