"""Share of the device's idle time in the traced span during which the KV
tier was at work on the engine loop: the innermost open span was
``kv.demote`` (with its blocking ``sync.demote`` copy of evicted pages to
the host) or ``kv.restore`` (``benchmark/harness/host_spans.py``)."""

from benchmark.harness import host_spans

LAYER = "block manager"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return host_spans.idle_share(run, "kv_demote")
