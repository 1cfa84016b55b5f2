"""Share of device busy time a linear-attention model's PREFILL spends in
its chunked scan and its convolution: self time under ``prefill/`` and
``chunk/`` in ``ssm.scan`` (the WY form's triangular solve and products a
chunk, the ``lax.scan`` that carries the state, the state's write-back to
the pool) and ``ssm.conv`` (the short causal convolution, its activation,
the heads' normalisation, the memory's gather and shift), over the union
of all device operations in the traced span (per chip; ``_scope_trace``).
The linear layers open the scopes of their ROLE in a recurrent mixer, so
this is ``ssm.prefill_scan_device_share``'s one call in another cell; the
decode-time state update is a Pallas kernel with readers of its own
(``lin.state_update_*``).

**A span with no prefill in it reads 0, not nothing.**  This traffic
prefills 1.5-2 times a second between fused decode windows, so a short
capture can hold none (the driver's first check of PR 43: 0.58 s, two
decode windows); ``share_of_busy`` gives None there, which leaves the
metric out of the result's line, and the line is then refused.  Where the
configuration has linear layers and the trace has busy time the share is
a reading, and zero is one.  None where the run has no trace or the model
no linear layer."""

from benchmark.layer_metrics import _lin_trace, _scope_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"
PARTS = ("ssm.scan", "ssm.conv")


def compute(run):
    m = _scope_trace.measure(run)
    if m is None or m["busy_s"] <= 0 \
            or not _lin_trace.linear_layers(run.get("config") or {}):
        return None
    return 100.0 * _scope_trace.seconds(
        m, _scope_trace.PREFILL_PHASES, PARTS) / m["busy_s"]
