"""Share of device busy time a state-space model's PREFILL spends in its
chunked scan and its convolution: self time under ``prefill/`` and
``chunk/`` in ``ssm.scan`` (the ``lax.scan`` of ``jax.numpy`` products over
chunks, and the state's write-back) and ``ssm.conv`` (the short causal
convolution, its activation, the memory's gather and shift), over the union
of all device operations in the traced span (per chip; ``_scope_trace``).
The decode-time state update is a Pallas kernel with readers of its own
(``ssm.device_share``)."""

from benchmark.layer_metrics import _scope_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("ssm.scan", "ssm.conv")


def compute(run):
    return _scope_trace.share_of_busy(run, _scope_trace.PREFILL_PHASES, PARTS)
