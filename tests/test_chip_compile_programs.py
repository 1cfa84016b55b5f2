"""Whole programs as the chip's compiler leaves them: every device
operation of a decode window names its model part, and a layer under its
own ``jax.jit`` is inlined (see ``tests/test_chip_compile.py`` and
``tests/chip_v5e.py``)."""

import re

import jax
import pytest

from chip_v5e import (MAX_NUM_SEQS, MAX_PAGES, NUM_BLOCKS, PAGE, PREFILL_SEQS,
                      k_exaone_share, olmo_hybrid, shapes_on)
from chip_v5e import (  # noqa: F401  (fixtures, found by name)
    _no_persistent_cache, one_chip, topo)

# ---- every device operation of a decode window names its model part ------

# operations that do work on the chip (a bitcast, a tuple, a parameter do
# none), with the asynchronous halves the compiler splits a copy or a
# slice into
DEVICE_WORK = {"fusion", "convolution", "custom-call", "copy", "copy-start",
               "copy-done", "slice", "slice-start", "slice-done",
               "dynamic-slice", "dynamic-update-slice", "sort", "gather",
               "scatter"}
# the ONLY operations that carry an op_name and no part of the table: what
# lax.scan itself emits around the window's body (its stacked outputs'
# buffers and the write of a step's row into them), and one index clamp
# of the expert layer's row gather that XLA names outside every path
NO_PART = {
    "jit(decode_multi)/decode/broadcast_in_dim",
    "jit(decode_multi)/decode/while/body/broadcast_in_dim",
    "jit(decode_multi)/decode/while/body/dynamic_update_slice",
    "gather",
}


def _scheduled(text):
    """The compiled module's instructions that run as operations of their
    own (those of fused computations and reducers left out), by
    computation, in schedule order: ``{computation: [(name, opcode,
    op_name, operand names)]}``."""
    comps, cur, name = {}, None, None
    for line in text.split("\n"):
        if cur is None:
            m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
            if m and not line.startswith(" "):
                name, cur = m.group(1), []
        elif line.startswith("}"):
            comps[name], cur = cur, None
        else:
            cur.append(line)
    inner = set()
    for lines in comps.values():
        for line in lines:
            inner.update(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line))
    inner -= {c for lines in comps.values() for line in lines
              for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", line)}
    out = {}
    for comp, lines in comps.items():
        if comp in inner:
            continue
        rows = []
        for line in lines:
            m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\((.*)$",
                         line)
            if not m:
                continue
            op_name = re.search(r'op_name="([^"]*)"', line)
            rows.append((m.group(1), m.group(2),
                         op_name.group(1) if op_name else "",
                         set(re.findall(r"%([\w.\-]+)",
                                        m.group(3).split("metadata=")[0]))))
        out[comp] = rows
    return out


def _two_layers(model: str):
    """Two layers of a family at its published widths."""
    import dataclasses

    from tpuserve.models.config import get_model_config
    if "+share" in model:
        # a dense layer, then an expert layer told its share (the loop
        # over pieces is a computation of its own inside the window's)
        return k_exaone_share(num_layers=2)
    if "+last2" in model:
        # a linear-attention layer, then a full one (the last two of a
        # period)
        return olmo_hybrid(num_layers=2, linear_layers=(True, False))
    return dataclasses.replace(get_model_config(model), num_layers=2)


# what the chip's compiler makes ITSELF inside a called function (a
# trunk's layer body under its own jax.jit, models/transformer.py) before
# it inlines the call, it names after the CALL: a phase and no part, where
# in a flat module it carries no name at all.  The benchmark's reader
# files such an operation under no part (trunk.unscoped_device_share), so
# they are counted here and held to the few there are: index arithmetic of
# an expert layer's row moves, (64, 1) int32 a piece
LAYER_CALL = re.compile(r"/jit\(_(prefill|chunk|decode|ragged|nocache)"
                        r"_layer\)$")


@pytest.mark.parametrize("model,kernels,by_call", [
    ("Qwen/Qwen3-0.6B", {"_paged_decode_attention": "attn.kernel"}, 0),
    ("JetBrains/Mellum2-12B-A2.5B-Instruct",
     {"_paged_decode_attention": "attn.kernel",
      "_moe_grouped_matmul": "moe.experts"}, 10),
    ("LGAI-EXAONE/K-EXAONE-236B-A23B+share",
     {"_paged_decode_attention": "attn.kernel",
      "_moe_grouped_matmul": "moe.experts"}, 0),
    ("allenai/Olmo-Hybrid-7B+last2",
     {"_paged_decode_attention": "attn.kernel",
      "_gdn_state_update": "ssm.scan", "_conv_tail_step": "ssm.conv"}, 0),
])
def test_every_operation_of_a_decode_window_names_its_part(
        model, kernels, by_call, one_chip, monkeypatch):
    """``decode_multi`` at published widths, two layers, 64 rows, compiled
    for the chip: whatever carries an ``op_name`` carries a part of the
    scope table (``tpuserve/ops/scopes.py``), the exceptions listed above
    by name, so that an unscoped operation cannot come back unseen.  What
    the compiler makes itself carries no ``op_name`` at all; the benchmark
    files it under the next operation of its program that names a PART
    (``benchmark/layer_metrics/_scope_trace.py``), and here that rule is
    held to the compiled text: such an operation is followed by one, and
    for the wait on a prefetched weight slice (``slice-done``, the one
    that costs time) the next operation with a part IS its consumer.

    Every layer's operations come through a ``jax.jit`` of their own
    (``jit(_decode_layer)`` in their paths): phase and part are found as
    before, the K/V row scatter included, which the compiler names without
    the call's prefix; ``by_call`` operations are named after the call
    alone (``LAYER_CALL``)."""
    from test_scopes import scope_of, trunk_programs
    from tpuserve.ops import scopes

    S, place = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, kwargs = trunk_programs(
        _two_layers(model), S, place, rows=MAX_NUM_SEQS, steps=8,
        block_size=PAGE, num_blocks=NUM_BLOCKS, max_blocks=MAX_PAGES,
        attn_impl="pallas")["decode_multi"]
    comps = _scheduled(fn.lower(*args, **kwargs).compile().as_text())
    seen, unscoped, called, waits, ahead = set(), [], [], 0, 0
    for comp, rows in comps.items():
        scoped = [scope_of(op_name)[1] and scope_of(op_name)
                  for _, _, op_name, _ in rows]
        users = {}
        for i, (_, _, _, operands) in enumerate(rows):
            for operand in operands:
                users.setdefault(operand, []).append(i)

        def consumers(i, depth=0):
            found = set()
            for j in users.get(rows[i][0], ()):
                if j > i and scoped[j]:
                    found.add(scoped[j])
                elif j > i and depth < 6:
                    found |= consumers(j, depth + 1)
            return found

        for i, (name, opcode, op_name, _) in enumerate(rows):
            if opcode not in DEVICE_WORK:
                continue
            for kernel, part in kernels.items():
                if name.split(".")[0] == kernel:
                    assert scope_of(op_name) == (scopes.DECODE, part), op_name
                    seen.add(kernel)
            # (inside a pipelined loop the compiler names its waits after
            # the loop itself: the reader takes those for the compiler's)
            if op_name and not (op_name.endswith("/while")
                                and opcode != "while"):
                if LAYER_CALL.search(op_name):
                    called.append(name)
                elif not scoped[i] and op_name not in NO_PART:
                    unscoped.append((name, op_name))
                elif scoped[i]:
                    # no part without its phase: the reader divides
                    # decode/'s parts by decode/'s steps
                    assert scope_of(op_name)[0] == scopes.DECODE, op_name
                continue
            nxt = next((s for s in scoped[i + 1:] if s), None)
            if opcode == "slice-done" and not consumers(i):
                # a POOL the window carries that the compiler keeps in its
                # faster memory space from step to step, copied there in
                # slices at the end of the loop's body: its consumer is
                # the NEXT step (the carry), so the reader would file the
                # wait with whatever part follows it.  The convolution's
                # memory of a model with linear layers was one (9 MB a
                # layer, PERF.md §7 row 24) until its kernel declared the
                # pool in HBM (ops/pallas_conv_tail.py): none is left
                ahead += 1
            elif opcode == "slice-done":
                waits += 1
                assert nxt in consumers(i), (name, nxt, consumers(i))
            elif not comp.startswith("main"):
                # a loop body ends in scoped work; only the entry's own
                # first and last copies have nothing scoped behind them
                assert nxt or not consumers(i), name
    assert not unscoped, unscoped
    assert len(called) <= by_call, called
    assert seen == set(kernels)
    assert any("/jit(_decode_layer)/" in op_name for rows in comps.values()
               for _, _, op_name, _ in rows)
    assert waits >= 8       # the layers' weight matrices are prefetched
    assert ahead == 0, ahead


# ---- a layer under its own jax.jit is inlined into the program -----------

# the kernels of two layers of each family's decode window and packed
# prefill, by the name of their custom call (the counts the trunks held
# when every layer was written out in the loop): an expert layer runs
# three grouped products, under a share inside one loop over pieces
LAYER_KERNELS = {
    "Qwen/Qwen3-0.6B": ({"_paged_decode_attention": 2},
                        {"_ragged_paged_attention": 2, "_paged_kv_write": 2}),
    "mistralai/Mistral-7B-Instruct-v0.1": (
        {"_paged_decode_attention": 2},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2}),
    "tiiuae/Falcon-H1-34B-Instruct": (
        {"_paged_decode_attention": 2, "_ssm_state_update": 2,
         "_conv_tail_step": 2},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2}),
    "JetBrains/Mellum2-12B-A2.5B-Instruct": (
        {"_paged_decode_attention": 2, "_moe_grouped_matmul": 6},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2,
         "_moe_grouped_matmul": 6}),
    "LGAI-EXAONE/K-EXAONE-236B-A23B+share": (
        {"_paged_decode_attention": 2, "_moe_grouped_matmul": 3},
        {"_ragged_paged_attention": 2, "_paged_kv_write": 2,
         "_moe_grouped_matmul": 3}),
    "allenai/Olmo-Hybrid-7B+last2": (
        {"_paged_decode_attention": 1, "_gdn_state_update": 1,
         "_conv_tail_step": 1},
        {"_ragged_paged_attention": 1, "_paged_kv_write": 1}),
}


@pytest.mark.parametrize("program", ["decode_multi", "forward_ragged"])
@pytest.mark.parametrize("model", sorted(LAYER_KERNELS))
def test_a_layer_under_its_own_jit_is_inlined_into_the_program(
        model, program, one_chip, monkeypatch):
    """The module a trunk lowers to CALLS one private function a kind of
    layer (``jax.jit`` inside a trace); the chip's compiler inlines every
    call before it optimises, so the compiled program holds no call to a
    layer function and the kernels it held when the layers were written
    out in the trunk's loop."""
    import collections

    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    S, place = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _two_layers(model)
    blk = ragged_block_for(cfg.cache_q_heads, cfg.cache_kv_heads,
                           cfg.head_dim, PAGE, 2, 2)
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=MAX_NUM_SEQS, steps=8, tokens=2048, blk=blk,
        prompts=PREFILL_SEQS, block_size=PAGE, num_blocks=NUM_BLOCKS,
        max_blocks=MAX_PAGES, attn_impl="pallas")[program]
    lowered = fn.lower(*args, **kwargs)
    layer = "_decode_layer" if program == "decode_multi" else "_ragged_layer"
    assert len(re.findall(rf"call @{layer}(_\d+)?\(", lowered.as_text())) == 2
    text = lowered.compile().as_text()
    assert not re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? call\(", text,
                          re.M)
    kernels = collections.Counter(
        re.sub(r"\.\d+$", "", name) for name in re.findall(
            r"%([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call", text))
    assert kernels == LAYER_KERNELS[model][program == "forward_ragged"]
