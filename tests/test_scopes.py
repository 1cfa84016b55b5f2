"""Every trunk names its device time (``tpuserve/ops/scopes.py``): the
compiled programs of one tiny model of each family carry every scope of
the table that applies to the family, each under its phase, and a scope
changes no program's name.  (What the chip's compiler makes of them is
``tests/test_chip_compile.py``; what the benchmark reads,
``tests/benchmark/test_benchmark_scope_trace.py``.)"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from tpuserve.models import transformer
from tpuserve.models.config import get_model_config
from tpuserve.models.weights import init_params
from tpuserve.ops import scopes
from tpuserve.runtime.kv_cache import (CacheConfig, create_kv_cache,
                                       create_ssm_state)

COMMON = {scopes.EMBED, scopes.ATTN_QKV, scopes.ATTN_KV_WRITE,
          scopes.ATTN_KERNEL, scopes.ATTN_OUT, scopes.MLP, scopes.HEAD}
FAMILY = {
    "tiny-qwen3": COMMON,
    "tiny-falcon-h1": COMMON | {scopes.SSM_IN_PROJ, scopes.SSM_CONV,
                                scopes.SSM_SCAN, scopes.SSM_OUT},
    "tiny-mellum2": COMMON | {scopes.MOE_ROUTE, scopes.MOE_GATHER,
                              scopes.MOE_EXPERTS, scopes.MOE_COMBINE},
    # linear-attention layers IN PLACE OF attention in six layers of eight:
    # the recurrent mixer's four parts by role, and attention's in the rest
    "tiny-olmo-hybrid": COMMON | {scopes.SSM_IN_PROJ, scopes.SSM_CONV,
                                  scopes.SSM_SCAN, scopes.SSM_OUT},
    # a share of the experts (8 of 32) beside a shared expert
    "tiny-k-exaone+share": COMMON | {scopes.MOE_ROUTE, scopes.MOE_GATHER,
                                     scopes.MOE_EXPERTS, scopes.MOE_COMBINE,
                                     scopes.MOE_SHARED},
    # latent attention: its projections, the absorption and the latent's
    # write each under one of attention's five parts, nothing new
    "tiny-pangu+share": COMMON | {scopes.MOE_ROUTE, scopes.MOE_GATHER,
                                  scopes.MOE_EXPERTS, scopes.MOE_COMBINE,
                                  scopes.MOE_SHARED},
    # Kimi-delta layers beside latent attention behind grouped experts:
    # the recurrent mixer's parts with the decay gate's inside two of
    # them, attention's with the head gate's inside its output's
    "tiny-ling-hybrid+share": COMMON | {
        scopes.SSM_IN_PROJ, scopes.SSM_CONV, scopes.SSM_SCAN, scopes.SSM_OUT,
        scopes.SSM_GATE, scopes.ATTN_GATE, scopes.MOE_ROUTE,
        scopes.MOE_GATHER, scopes.MOE_EXPERTS, scopes.MOE_COMBINE,
        scopes.MOE_SHARED},
}
ALL_PARTS = scopes.PARTS + scopes.LATER_PARTS


def family_config(model: str):
    """The registered model; ``+share``: told it holds a quarter of its
    experts."""
    name, _, share = model.partition("+")
    cfg = get_model_config(name)
    return dataclasses.replace(
        cfg, moe_experts_held=cfg.num_experts // 4) if share else cfg


# program -> (its phase, the parts only it has)
PROGRAMS = {
    "decode_multi": (scopes.DECODE, {scopes.SAMPLE, scopes.CARRY}),
    "forward_ragged": (scopes.PREFILL, set()),
    "prefill_chunk": (scopes.CHUNK, set()),
}


def scope_of(op_name: str) -> tuple:
    """``(phase, part)`` of an ``op_name``: the first component that is a
    phase, the last that is a part ("" for what it lacks)."""
    comps = op_name.split("/")
    return (next((c for c in comps if c in scopes.PHASES), ""),
            next((c for c in reversed(comps) if c in ALL_PARTS), ""))


def trunk_programs(cfg, S=jax.ShapeDtypeStruct, place=lambda tree: tree, *,
                   rows=4, steps=4, tokens=64, blk=8, prompts=4, chunk=16,
                   block_size=4, num_blocks=16, max_blocks=8,
                   attn_impl="reference", decode_rows=False) -> dict:
    """``{program: (jitted trunk, args, keyword args)}`` of shapes alone:
    a fused decode window over ``rows`` rows, a packed prefill of
    ``tokens`` flat tokens (``decode_rows``: a mixed step, whose first rows
    are ``prompts`` sequences' decode rows), one chunk of one prompt.  ``S``
    makes a shape, ``place`` puts a tree of shapes where the caller
    compiles for."""
    i32 = jnp.int32
    params = place(jax.eval_shape(lambda: init_params(cfg, 0)))
    cache_cfg = CacheConfig(block_size=block_size, num_blocks=num_blocks,
                            max_blocks_per_seq=max_blocks,
                            dtype="bfloat16" if cfg.dtype == "bfloat16"
                            else cfg.dtype)
    kv = place(jax.eval_shape(lambda: create_kv_cache(cfg, cache_cfg)))

    def tail(n):        # the seat pool and the seats, where there is one
        if not cfg.has_state:
            return ()
        pool = place(jax.eval_shape(lambda: create_ssm_state(cfg, rows + 1)))
        return (None, pool, S((n,), i32))

    vec, seqs = S((rows,), i32), S((prompts,), i32)
    return {
        "decode_multi": (transformer.decode_multi, (
            params, cfg, vec, vec, S((rows, max_blocks), i32), vec,
            S((rows,), jnp.bool_), S((rows, 2), jnp.uint32),
            S((rows,), jnp.float32), kv, *tail(rows)),
            dict(steps=steps, mode="greedy", attn_impl=attn_impl)),
        "forward_ragged": (transformer.forward_ragged, (
            params, cfg, S((tokens,), i32), S((tokens,), i32),
            S((tokens,), i32), S((tokens,), i32),
            S((prompts, max_blocks), i32), seqs, seqs, seqs, S((2,), i32),
            S((tokens // blk,), i32), seqs, kv, *tail(prompts)),
            dict(ragged_blk=blk, attn_impl=attn_impl,
                 decode_rows=decode_rows)),
        "prefill_chunk": (transformer.prefill_chunk, (
            params, cfg, S((1, chunk), i32), S((1,), i32), S((1,), i32),
            S((1, chunk), i32), S((1, max_blocks), i32), kv, *tail(1)),
            dict(attn_impl=attn_impl)),
    }


@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("model", sorted(FAMILY))
def test_a_trunk_carries_every_scope_of_its_family(model, program):
    fn, args, kwargs = trunk_programs(family_config(model))[program]
    lowered = fn.lower(*args, **kwargs)
    # the scope is inside the jitted function: the program is named as ever
    assert re.search(rf"module @jit_{program}\b", lowered.as_text())
    found = {scope_of(name) for name in re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text())}
    phase, own = PROGRAMS[program]
    want = FAMILY[model] | own
    assert {part for ph, part in found if ph == phase} >= want, \
        sorted(want - {part for ph, part in found if ph == phase})
    # and under no other phase: one trunk, one phase
    assert {ph for ph, _ in found} <= {phase, ""}, found
    # a part the family lacks is not invented
    absent = set(ALL_PARTS) - want
    assert not {part for _, part in found} & absent


@pytest.mark.parametrize("model", ["tiny-falcon-h1", "tiny-olmo-hybrid"])
def test_a_decode_step_files_each_memory_kernel_under_its_part(model):
    """Under ``attn_impl="pallas"`` a decode step moves a recurrent
    layer's two memories by a kernel each, and a traced run files them by
    the scope their caller opens: the convolution's
    (``_conv_tail_step``) under ``decode/ssm.conv``, the state's under
    ``decode/ssm.scan`` -- whatever lies between in the path (the layer's
    and the kernel's own ``jit(...)``)."""
    fn, args, kwargs = trunk_programs(
        family_config(model), attn_impl="pallas")["decode_multi"]
    names = re.findall(r'op_name="([^"]*)"',
                       fn.lower(*args, **kwargs).compile().as_text())
    for kernel, part in ((r"jit\(_conv_tail_step\)", scopes.SSM_CONV),
                         (r"jit\(_(gdn|ssm)_state_update\)",
                          scopes.SSM_SCAN)):
        under = [n for n in names if re.search(kernel, n)]
        assert under, kernel
        assert {scope_of(n) for n in under} == {(scopes.DECODE, part)}


def test_the_table_is_one_place():
    """Every name once, phases and parts apart, and no scope is opened by a
    literal string anywhere in the program."""
    import os
    import subprocess
    assert len(set(scopes.PHASES + ALL_PARTS)) \
        == len(scopes.PHASES) + len(ALL_PARTS)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        ["grep", "-rnE", r"named_scope\([\"']", os.path.join(root, "tpuserve"),
         "--include=*.py"], capture_output=True, text=True).stdout
    assert out == "", out


# --------------------------------------------------------------------------
# the accepted families' trunks are the programs they were
# --------------------------------------------------------------------------

# sha256 (first 16 hex digits) of each trunk's lowered text, operation
# names included and source lines left out (as the compile cache keys a
# program: tpuserve/utils/compile_cache.py) for the tiny model of each
# accepted configuration's family.  A layer's kind is a static branch of
# the layer bodies, so a model without linear layers must lower to the text
# it had: a scope renamed, an operation moved or added in a shared helper
# shows here before it costs the accepted cells a cold compile (or their
# speed) on the chip.  A PR that MEANS to change a trunk replaces the pins
# it changes: first taken from the commit before this model (efe1553), all
# replaced by PR 44, which put every trunk's per-layer body under its own
# ``jax.jit`` (one private function a kind of layer in each module) and
# rounds each half of a rotated vector where it is made (ops/rope.py).
LOWERED = {
    "tiny-qwen3": {
        ("pallas", "decode_multi"): "816a20b23bf8ff09",
        ("pallas", "forward_ragged"): "4f7735134122b74d",
        ("pallas", "prefill_chunk"): "0fe020fc804219d1",
        ("reference", "decode_multi"): "9a87c60d8a2dbf74",
        ("reference", "forward_ragged"): "4514f95a67f44bbb",
        ("reference", "prefill_chunk"): "c5e20e1507e0601d",
    },
    "tiny-mistral": {
        ("pallas", "decode_multi"): "da80b79a288c3ec2",
        ("pallas", "forward_ragged"): "e793e870bfd7edca",
        ("pallas", "prefill_chunk"): "070480809ba7171d",
        ("reference", "decode_multi"): "cb49c1759a45a69d",
        ("reference", "forward_ragged"): "ea7b0f5c2ce6e69b",
        ("reference", "prefill_chunk"): "9c27a385d9768d22",
    },
    # (PR 46: the convolution's memory as whole lane tiles, stepped in
    # place by its own kernel: every trunk of this family means to change)
    "tiny-falcon-h1": {
        ("pallas", "decode_multi"): "b7e68e5c59b9cb6e",
        ("pallas", "forward_ragged"): "734831dffba86e08",
        ("pallas", "prefill_chunk"): "3617877d2c533f38",
        ("reference", "decode_multi"): "21d3d3d5e21c4bca",
        ("reference", "forward_ragged"): "ecdf47368374148d",
        ("reference", "prefill_chunk"): "c787ab1df7673b9a",
    },
    "tiny-mellum2": {
        ("pallas", "decode_multi"): "885b81fb59a5e229",
        ("pallas", "forward_ragged"): "840cda028cc82f7d",
        ("pallas", "prefill_chunk"): "0441359e89149443",
        ("reference", "decode_multi"): "3bf7981e3b6915ec",
        ("reference", "forward_ragged"): "e4df6a5e4658e49b",
        ("reference", "prefill_chunk"): "ebf7b47fe0a4f1a0",
    },
    "tiny-k-exaone+share": {
        ("pallas", "decode_multi"): "858b4baaa33a367e",
        ("pallas", "forward_ragged"): "c922e55edf29688d",
        ("pallas", "prefill_chunk"): "6711998584e1329f",
        ("reference", "decode_multi"): "2bd76c32b7874656",
        ("reference", "forward_ragged"): "925f32cdc424f297",
        ("reference", "prefill_chunk"): "1e18c77450bd2dec",
    },
    # (PR 55, taken on its parent 616a3fb and equal on its own tree: the
    # two families whose helpers it edits -- the linear mixer's form is a
    # static branch of ``_lin_*``, the head gate and the q/k norm static
    # branches of ``_attn_residual`` / ``_mla_*``, the state update's
    # kernel body shared with the channel gate's)
    "tiny-olmo-hybrid": {
        ("pallas", "decode_multi"): "21c1bc55af5d3515",
        ("pallas", "forward_ragged"): "ca80fcf8115f464e",
        ("pallas", "prefill_chunk"): "e094e1974fbac77c",
        ("reference", "decode_multi"): "8d0b7cfa4c610cc5",
        ("reference", "forward_ragged"): "3ab884e285ac9286",
        ("reference", "prefill_chunk"): "4cfc807606095127",
    },
    "tiny-pangu+share": {
        ("pallas", "decode_multi"): "845a597f3e2bcb97",
        ("pallas", "forward_ragged"): "f05000f6cac1e496",
        ("pallas", "prefill_chunk"): "77ecfc967b9fede3",
        ("reference", "decode_multi"): "e97be8696c285456",
        ("reference", "forward_ragged"): "a288146f6d270750",
        ("reference", "prefill_chunk"): "5a9fec26742cf7a4",
    },
}


@pytest.mark.parametrize("model", sorted(LOWERED))
def test_the_accepted_trunks_lower_to_the_text_they_had(model):
    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    # a trunk this module lowered before holds its locations as they were
    # made: the text's hash depends on the limit set above
    jax.clear_caches()
    try:
        got = {}
        for attn_impl in ("reference", "pallas"):
            for program, (fn, args, kwargs) in trunk_programs(
                    family_config(model), attn_impl=attn_impl).items():
                text = fn.lower(*args, **kwargs).as_text(debug_info=True)
                got[attn_impl, program] = hashlib.sha256(
                    text.encode()).hexdigest()[:16]
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    assert got == LOWERED[model]
