"""Share of the device's idle time in the traced span during which the host
was at work while the chip waited: the innermost open span was one of
``runner.*``, ``slo.admission``, ``schedule``, ``block``, ``dispatch*``,
``sample``, ``detokenize``, ``step.close``
(``benchmark/harness/host_spans.py``)."""

from benchmark.harness import host_spans

LAYER = "engine step"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return host_spans.idle_share(run, "host_loop")
