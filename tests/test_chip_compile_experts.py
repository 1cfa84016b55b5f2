"""The expert layer compiles for the chip: the grouped product at the tiles
each regime picks, the whole sparse layer at every rung of a packed
prefill, and the layer told it holds a share of its experts (see
``tests/test_chip_compile.py`` and ``tests/chip_v5e.py``)."""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_v5e import (k_exaone_share, ling_share, shapes_on)
from chip_v5e import (  # noqa: F401  (fixtures, found by name)
    _no_persistent_cache, one_chip, topo)

# (rows, contraction, output columns) of the expert layer's grouped
# products at Mellum2-12B-A2.5B's widths (64 experts of width 896 on a
# hidden size of 2,304, 8 a token): the smallest and the largest decode
# bucket, and packed prefills of 512 and 8,192 tokens (max_prefill_tokens)
MOE_EXPERTS = 64
MOE_SHAPES = {
    "decode-8.up": (8 * 8, 2304, 896),
    "decode-64.up": (64 * 8, 2304, 896),
    "decode-64.down": (64 * 8, 896, 2304),
    "prefill-512.up": (512 * 8, 2304, 896),
    "prefill-8192.up": (8192 * 8, 2304, 896),
    "prefill-8192.down": (8192 * 8, 896, 2304),
}


@pytest.mark.parametrize("shape", sorted(MOE_SHAPES))
def test_the_grouped_product_compiles_for_v5e(shape, one_chip):
    """``_moe_grouped_matmul`` at the tiles ``tiling`` picks for each
    regime: compiled (the blocks fit the VMEM limit the kernel asks for),
    and named as the benchmark's ``moe.*`` readers match it."""
    from benchmark.layer_metrics import _moe_trace
    from tpuserve.ops.pallas_moe_gmm import KERNEL_NAME, grouped_matmul

    S, _ = shapes_on(one_chip)
    assert KERNEL_NAME == _moe_trace.KERNEL == "_moe_grouped_matmul"
    m, k, n = MOE_SHAPES[shape]
    text = jax.jit(lambda lhs, rhs, sizes: grouped_matmul(
        lhs, rhs, sizes, interpret=False)).lower(
            S((m, k), jnp.bfloat16), S((MOE_EXPERTS, k, n), jnp.bfloat16),
            S((MOE_EXPERTS,), jnp.int32)).compile().as_text()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", text)


# EVERY flat-token rung a packed prefill of the benchmark's cells can take
# (scheduler.packed_prefill_bucket: 13 rungs to max_prefill_tokens; all 13
# and the chunk program were also compiled as whole 12-layer trunks by
# hand, PRs 35 and 42) and the largest decode bucket
MOE_TOKENS = [128, 256, 512, 768, 1024, 1280, 1536, 1792, 2048, 3072, 4096,
              6144, 8192, 64]


@pytest.mark.parametrize("tokens", MOE_TOKENS)
def test_the_expert_layer_compiles_for_v5e_at_every_rung(tokens, one_chip,
                                                         monkeypatch):
    """The whole sparse expert layer (router, sort, the rows' gather, three
    grouped products, the add-back) at Mellum2-12B-A2.5B's widths.  What
    this guards: the TPU compiler refuses the PLAIN row gather of 1,536
    tokens into 12,288 rows for the grouped product (scoped VMEM, by
    0.4 MB; found on the chip, PR 35) and no other rung; what
    ``_gather_rows`` chooses compiles at all of them: the rows go into
    expert order plain from 16,384 rows (then no ``(rows, 18, 128)``
    array and none of its relayout copies is left in the program) and as
    ``(tiles, 128)`` slices under that, and come back plain wherever a
    prefill permutes them (PR 42)."""
    import dataclasses
    from tpuserve.models import transformer
    from tpuserve.models.config import get_model_config
    from tpuserve.ops.pallas_moe_gmm import grouped_matmul

    S, _ = shapes_on(one_chip)
    # the kernel's wrapper asks jax.default_backend(), which is the CPU
    # here: steer it to the compiled kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        get_model_config("JetBrains/Mellum2-12B-A2.5B-Instruct"),
        num_layers=1)
    H, I, E = cfg.hidden_size, cfg.expert_intermediate_size, cfg.num_experts
    bf16 = jnp.bfloat16
    p = {"router": {"kernel": S((H, E), bf16)},
         "experts": {"gate_proj": {"kernel": S((E, H, I), bf16)},
                     "up_proj": {"kernel": S((E, H, I), bf16)},
                     "down_proj": {"kernel": S((E, I, H), bf16)}}}
    compiled = jax.jit(lambda x, p: transformer._moe_mlp(x, p, cfg)).lower(
        S((tokens, H), bf16), p).compile()
    text = compiled.as_text()
    assert text.count("_moe_grouped_matmul") >= 3
    # the experts' kernels go to the custom calls as they are: no copy
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 5 * tokens * 8 * H * 2 + (64 << 20)
    rows = tokens * cfg.num_experts_per_tok
    into, back = transformer.moe_plain_moves(cfg, tokens)
    assert (into, back) == (rows >= 16384, rows > 1024)
    sliced = len(re.findall(rf"= bf16\[{rows},18,128\][^\n]* fusion\(", text))
    assert sliced == (not into) + (not back), (sliced, into, back)
    if tokens == 1536:
        k = cfg.num_experts_per_tok
        with pytest.raises(Exception, match="vmem"):
            jax.jit(lambda x, order, w, sizes:
                    grouped_matmul(x[order // k], w, sizes, interpret=False)
                    ).lower(S((tokens, H), bf16), S((tokens * k,), jnp.int32),
                            S((E, H, I), bf16), S((E,), jnp.int32)).compile()


def held_layer_shapes(S, cfg, shared: bool) -> dict:
    """One expert layer's parameters under ``cfg``'s share, as shapes: the
    router over every expert and its selection bias, the held experts'
    stacked kernels, the shared expert where the model has one."""
    H, I, E = cfg.hidden_size, cfg.expert_intermediate_size, cfg.num_experts
    held, bf16 = cfg.moe_experts_held, jnp.bfloat16
    p = {"router": {"kernel": S((H, E), bf16)},
         "router_bias": {"bias": S((E,), jnp.float32)},
         "experts": {"gate_proj": {"kernel": S((held, H, I), bf16)},
                     "up_proj": {"kernel": S((held, H, I), bf16)},
                     "down_proj": {"kernel": S((held, I, H), bf16)}}}
    if shared:
        p["shared"] = {"gate_proj": {"kernel": S((H, I), bf16)},
                       "up_proj": {"kernel": S((H, I), bf16)},
                       "down_proj": {"kernel": S((I, H), bf16)}}
    return p


# K-EXAONE-236B-A23B's share of the benchmark's cell: 16 of 128 experts of
# width 2,048 on a hidden size of 6,144, 8 a token; the flat-token rungs
# of its packed prefills (64-row ragged blocks, so multiples of 128 up to
# 1,024, of 512 up to 2,048, of 1,024 above) and the largest decode bucket
HELD_TOKENS = [64, 128, 512, 1024, 1536, 2048, 3072, 4096, 8192]


@pytest.mark.parametrize("tokens", HELD_TOKENS)
def test_the_expert_layer_under_a_share_compiles_for_v5e_at_every_rung(
        tokens, one_chip, monkeypatch):
    """The whole expert layer told it holds 16 of 128 experts (router over
    all 128, the sort of the picks, the loop over pieces with the rows'
    gather, three grouped products and the add to the tokens, the shared
    expert) at K-EXAONE's widths.  What this guards: the layer's transient
    memory follows what lands here.  A buffer of ``T k`` rows of 6,144
    bf16 values is 805 MB at the top rung, and a layer that moved every
    pick would hold three of them (the gathered rows, the products' output,
    that output back in token order) and the activations between; the
    whole layer here stays about ONE such buffer at every rung (820 MB at
    the top: a piece's rows, their output and its weighted float32 copy,
    and the float32 sum over the tokens)."""
    from tpuserve.models import transformer

    S, _ = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = k_exaone_share(num_layers=2)
    H = cfg.hidden_size
    compiled = jax.jit(lambda x, p: transformer._moe_mlp(x, p, cfg)).lower(
        S((tokens, H), jnp.bfloat16),
        held_layer_shapes(S, cfg, shared=True)).compile()
    text = compiled.as_text()
    assert text.count("_moe_grouped_matmul") >= 3
    assert " while(" in text            # the pieces: a trip count from data
    every_pick = tokens * cfg.num_experts_per_tok * H * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.25 * every_pick + (16 << 20), (temp, every_pick)


@pytest.mark.parametrize("tokens", [128, 1024])
def test_the_group_limited_layer_sorts_twice_on_v5e(tokens, one_chip,
                                                    monkeypatch):
    """Ling-3.0-flash-VL's expert layer under its share (a 512-wide router
    in 8 groups of which 4 survive, routing group 0 held) at a decode
    step's rows and a packed prefill's: the chip's compiler is handed TWO
    sorts, the picks' ``top_k`` over ``(T, 512)`` and the held picks'
    ``argsort``, as a layer with no groups is; the groups' top-2 and the
    surviving groups are maxima and comparisons (they were two sorts more
    and a scatter, 113 us of a 158 us route at 128 rows: PERF.md §6, PR
    56)."""
    from tpuserve.models import transformer

    S, _ = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ling_share(num_layers=2)
    assert (cfg.num_experts, cfg.moe_n_group, cfg.moe_topk_group) == (
        512, 8, 4)
    text = jax.jit(lambda x, p: transformer._moe_mlp(x, p, cfg)).lower(
        S((tokens, cfg.hidden_size), jnp.bfloat16),
        held_layer_shapes(S, cfg, shared=False)).compile().as_text()
    # (a packed prefill's add-back sorts its scatter's indices besides,
    # under ``moe.combine``: not the route's)
    sorts = re.findall(r"^\s*%\S+ = (\S+) \S+ sort\([^\n]*op_name=\"[^\"]*"
                       r"moe\.route", text, re.M)
    assert len(sorts) == 2, sorts
    assert any(f"f32[{tokens},512]" in out for out in sorts), sorts
    assert any(f"s32[{tokens * cfg.num_experts_per_tok}]" in out
               for out in sorts), sorts
