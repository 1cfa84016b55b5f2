"""Share of device busy time a decode step's latent PROJECTIONS take: self
time under ``decode/`` in ``attn.qkv`` (the query's two steps through its
latent, the token's compressed K/V and its rope key, ``W_uk`` folded into
the query) and ``attn.out`` (``W_uv`` out of the latent, the output
projection, the sandwich's norm), over the union of all device operations
in the traced span (per chip; ``_scope_trace``).  At 128 rows these read
their weights once a step (393 MB a layer at the published sizes) whatever
the context, where the kernel's time follows the context.  0.0 where the
span holds no decode step."""

from benchmark.layer_metrics import _mla_trace

LAYER = "model trunk"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    return _mla_trace.share_of_busy(run, ("decode",),
                                    ("attn.qkv", "attn.out"))
