"""Share of device busy time a latent-attention model's PREFILL spends
attending: self time under ``prefill/`` and ``chunk/`` in ``attn.kernel``
(the ragged kernel's latent entry: the absorbed form over the latent pages
the dispatch just wrote, 3.4 times the naive form's operations), over the
union of all device operations in the traced span (per chip;
``_scope_trace``).  Swings with ``step.prefill_device_share``.  0.0 where
the span holds no prefill."""

from benchmark.layer_metrics import _mla_trace, _scope_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return _mla_trace.share_of_busy(run, _scope_trace.PREFILL_PHASES,
                                    ("attn.kernel",))
