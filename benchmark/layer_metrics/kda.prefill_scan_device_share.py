"""Share of device busy time a Kimi-delta model's PREFILL spends in its
chunked scan and its convolution: self time under ``prefill/`` and
``chunk/`` in ``ssm.scan`` (the pairwise decays a sub-block of 16 rows at a
time, the triangular solve and products a chunk, the ``lax.scan`` that
carries the state, the state's write-back to the pool) and ``ssm.conv``
(the short causal convolution, its activation, the decay gate's
activation, the heads' normalisation, the memory's gather and shift), over
the union of all device operations in the traced span (per chip;
``_scope_trace``).  ``lin.prefill_scan_device_share``'s call in another
cell; a span with no prefill in it reads 0, not nothing, for its reason.
None where the run has no trace or the model no Kimi-delta layer."""

from benchmark.layer_metrics import _kda_trace, _scope_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("ssm.scan", "ssm.conv")


def compute(run):
    return _kda_trace.share_of_busy(run, _scope_trace.PREFILL_PHASES, PARTS)
