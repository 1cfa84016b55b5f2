"""The latent decode attention's share of its roofline: the least time the
chip could take for the token-layers its calls attended in the traced span
(``roofline.share``: the larger of operations over 197e12 and bytes over
819e9, never clipped) over the self time under ``decode/attn.kernel``.
The work is the architecture's, counted in ``_mla_trace.latent_work`` from
the configuration's keys: 2 x heads x ((kv_lora_rank + qk_rope_head_dim) +
kv_lora_rank) operations and 2 x (kv_lora_rank + qk_rope_head_dim) bytes a
cached token a layer (278,528 and 1,152), whatever implements it and
however the page is stored, so lanes a layout pads and a second read of the
values are lost share, never work.  0.0 where the span holds no decode
call."""

from benchmark.harness import roofline
from benchmark.layer_metrics import _mla_trace

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def compute(run):
    d = _mla_trace.decode_attention(run)
    if d is None or not run.get("peaks"):
        return None
    if d["ns"] <= 0 or d["token_layers"] <= 0:
        return 0.0
    flops, nbytes = _mla_trace.latent_work(run["config"])
    part = roofline.share(d["ns"] * 1e-9, flops * d["token_layers"],
                          nbytes * d["token_layers"], run["peaks"])
    return None if part is None else 100.0 * part
