"""Share of the device's idle time in the traced span during which the host
was itself waiting for the device: the innermost open span was a
``sync.*`` other than ``sync.demote``, so what is left is the result's
transfer and the host's wake-up (``benchmark/harness/host_spans.py``)."""

from benchmark.harness import host_spans

LAYER = "engine step"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return host_spans.idle_share(run, "sync")
