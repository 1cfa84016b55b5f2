"""Whole cells fit the chip: each served trunk of a benchmark cell at its
published widths and the depth the cell runs, beside its pages and its
seat pool (see ``tests/test_chip_compile.py`` and ``tests/chip_v5e.py``)."""

import dataclasses
import re

import jax
import pytest

from chip_v5e import (CHUNK, MAX_NUM_SEQS, MAX_PAGES, MIXED_BUDGET, PAGE,
                      PREFILL_SEQS, WIDTHS, k_exaone_share, ling_share,
                      olmo_hybrid, shapes_on)
from chip_v5e import (  # noqa: F401  (fixtures, found by name)
    _no_persistent_cache, one_chip, topo)

# a cell's three served trunks: (program, flat tokens; 0 = one ragged block)
CELL_PROGRAMS = [("decode_multi", 0), ("forward_ragged", 8192),
                 ("prefill_chunk", 0)]


def _compile_cell_program(cfg, program, tokens, num_blocks, one_chip,
                          monkeypatch):
    """One served trunk of a cell at its published widths, compiled for
    the described chip: a fused window of 64 rows and 8 steps, the top
    rung of the packed prefill or a chunk, beside ``num_blocks`` pages of
    32 tokens and the seat pool."""
    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    S, place = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    blk = ragged_block_for(cfg.cache_q_heads, cfg.cache_kv_heads,
                           cfg.head_dim, PAGE, 2, 2)
    assert blk == 128
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=MAX_NUM_SEQS, steps=8, tokens=tokens or blk,
        blk=blk, prompts=PREFILL_SEQS, chunk=CHUNK, block_size=PAGE,
        num_blocks=num_blocks, max_blocks=MAX_PAGES,
        attn_impl="pallas")[program]
    return fn.lower(*args, **kwargs).compile()


@pytest.mark.parametrize("program,tokens", CELL_PROGRAMS)
def test_the_olmo_hybrid_cell_fits_the_chip(program, tokens, one_chip,
                                            monkeypatch):
    """The cell's whole trunks at the published widths: 16 layers (12
    linear, 4 full), a fused decode window of 64 rows, the top rung of the
    packed-prefill ladder and a chunk, beside a pool of 2,560 pages of 32
    tokens for the 4 attention layers (what 0.9 of the chip leaves after
    8.2 GB of weights and 1.83 GB of state).  The chip's compiler refuses
    what does not fit 16 GB; 30 query heads on 30 KV heads reach the
    kernels as 32 on 32 and keep the 128-row ragged block."""
    cfg = olmo_hybrid(num_layers=16)
    assert (cfg.cache_q_heads, cfg.cache_kv_heads) == (32, 32)
    compiled = _compile_cell_program(cfg, program, tokens, 2560, one_chip,
                                     monkeypatch)
    mem = compiled.memory_analysis()
    weights = mem.argument_size_in_bytes - mem.alias_size_in_bytes
    assert 8.1e9 < weights < 8.3e9, weights
    # pages and pool stay in place, whole, in every program: 4 layers'
    # pages and 12 layers' seats (trunk_programs gives each program the
    # same pool: the window's 64 rows, one seat more and the trash seat;
    # a seat's three convolution rows, 90 sublanes each, stored as 96)
    pages = 4 * 2 * 2560 * PAGE * 32 * 128 * 2
    seat = 2_211_840 + 3 * 96 * 128 * 4
    assert mem.alias_size_in_bytes == pages + 12 * 66 * seat
    # beside them what a dispatch holds of its own stays under the tenth
    # of the chip the cache's sizer leaves free
    assert mem.temp_size_in_bytes < 1.4e9, mem.temp_size_in_bytes
    # 16.91 GB less the runtime's own 0.27: what the compiler itself
    # holds a program to (2,560 pages here; the sizer gives ~2,470)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.6e9
    text = compiled.as_text()
    if program == "decode_multi":
        assert "_paged_decode_attention" in text
        # the convolution's memory moves once a linear layer a step, by
        # its kernel, as the state beside it does by its own ...
        calls = {k: re.findall(rf"%{k}(?:\.\d+)? = ([^\n]*?)custom-call\(",
                               text)
                 for k in ("_conv_tail_step", "_gdn_state_update")}
        assert len(calls["_conv_tail_step"]) == 12
        assert len(calls["_gdn_state_update"]) == 12
        # ... on a pool the compiler leaves in HBM: no operand or result of
        # the call in its faster memory space, and no asynchronous copy of
        # anything of the pool's shape (it staged each layer's 9 MB there
        # and back around the gather and scatter this kernel replaced,
        # every step: PERF.md §6, PR 46)
        of_pool = r"f32\[6[456],3,(?:90,128|11520)\]"
        for out in calls["_conv_tail_step"]:
            assert not re.search(of_pool + r"\{[^}]*S\(1\)", out), out
        staged = [line for line in text.split("\n")
                  if re.search(r" (copy|slice)-start\(", line)
                  and re.search(of_pool, line)]
        assert not staged, staged[:2]


# what the three trunks of ``falcon-h1-34b-l6.reason`` held at the parent of
# PR 46 (the convolution's memory as ``(66, 3, 5120)``, stepped by XLA's
# gather, taps and scatter), compiled as below: (argument, temporary) bytes
FALCON_H1_BEFORE = {"decode_multi": (15_244_633_600, 55_074_304),
                    "forward_ragged": (15_244_704_256, 1_630_251_008),
                    "prefill_chunk": (15_244_616_704, 318_360_064)}


@pytest.mark.parametrize("program,tokens", CELL_PROGRAMS)
def test_the_falcon_h1_cell_holds_no_more_than_before(program, tokens,
                                                      one_chip, monkeypatch):
    """The served programs of the Falcon-H1 cell at the published widths
    (6 layers, a fused window of 64 rows, the top rung of the packed
    prefill and a chunk, beside the 7,785 pages the sizer gives the cell:
    PERF.md §4) hold no more of the chip with the convolution's memory as
    ``(seats, 3, 40, 128)`` than with ``(seats, 3, 5120)``: arguments and
    temporaries at or under the parent's, pages and pool whole in place.
    (The cell's ``memory_peak_bytes`` reads 381 MB higher since PR 46:
    not in these programs, PERF.md §7 row 27.)"""
    import dataclasses

    from tpuserve.models.config import get_model_config
    cfg = dataclasses.replace(
        get_model_config("tiiuae/Falcon-H1-34B-Instruct"), num_layers=6)
    compiled = _compile_cell_program(cfg, program, tokens, 7785, one_chip,
                                     monkeypatch)
    mem = compiled.memory_analysis()
    argument, temp = FALCON_H1_BEFORE[program]
    assert mem.argument_size_in_bytes <= argument
    assert mem.temp_size_in_bytes <= temp
    # 6 layers' pages (4 KV heads) and 6 layers' seats: 32 heads of 128 x
    # 256 float32 and three rows of 40 bfloat16 sublanes, stored as 40
    pages = 6 * 2 * 7785 * PAGE * 4 * 128 * 2
    seat = 32 * 128 * 256 * 4 + 3 * 40 * 128 * 2
    assert mem.alias_size_in_bytes == pages + 6 * 66 * seat
    text = compiled.as_text()
    staged = [line for line in text.split("\n")
              if re.search(r" (copy|slice)-start\(", line)
              and re.search(r"bf16\[6[456],3,(?:40,128|5120)\]", line)]
    assert not staged, staged[:2]
    if program == "decode_multi":
        for kernel in ("_conv_tail_step", "_ssm_state_update"):
            assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == 6


@pytest.mark.parametrize("program,tokens", [("decode_multi", 0),
                                            ("forward_ragged", 8192)])
def test_the_k_exaone_cell_fits_the_chip(program, tokens, one_chip,
                                         monkeypatch):
    """The cell's whole trunks at the published widths: 8 layers, 16 of
    128 experts, 19,200 vocabulary rows, a fused decode window of 64 rows
    and the top rung of the packed-prefill ladder, beside a pool of 3,072
    pages of 32 tokens (what 0.9 of the chip leaves after 11.96 GB of
    weights).  The chip's compiler refuses what does not fit 16 GB; 64
    query heads take a ragged block of 64 rows, as the engine finds."""
    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    S, place = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = k_exaone_share(num_layers=8)
    blk = ragged_block_for(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                           PAGE, 2, 2)
    assert blk == 64
    # and the accepted cells' shapes keep their 128 rows
    for hq, hkv, d in WIDTHS.values():
        assert ragged_block_for(hq, hkv, d, PAGE, 2, 2) == 128
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=MAX_NUM_SEQS, steps=8, tokens=tokens or blk,
        blk=blk, prompts=PREFILL_SEQS, block_size=PAGE, num_blocks=3072,
        max_blocks=MAX_PAGES, attn_impl="pallas")[program]
    mem = fn.lower(*args, **kwargs).compile().memory_analysis()
    weights = mem.argument_size_in_bytes - mem.alias_size_in_bytes
    assert 11.9e9 < weights < 12.1e9, weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.5e9


@pytest.mark.parametrize("program,tokens", CELL_PROGRAMS)
def test_the_ling_hybrid_cell_fits_the_chip(program, tokens, one_chip,
                                            monkeypatch):
    """``ling-3.0-flash-vl-ep8-l12.reason``'s whole trunks at the published
    widths: 12 layers (10 Kimi-delta, 2 latent; 2 dense, 10 expert layers
    of which routing group 0, 64 of 512 experts, is held), 19,648
    vocabulary rows (153.5 lane tiles: the head's last tile is half full),
    a fused decode window of 128 rows, the top rung of the packed-prefill
    ladder and a chunk, beside 16,384 latent pages of 32 tokens for the 2
    latent layers (more than 128 sequences of 3,072 tokens need) and a
    pool of 130 seats.  The chip's compiler refuses what does not fit 16
    GB; pages and pool stay in place, whole, in every program."""
    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    S, place = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ling_share(num_layers=12)
    assert cfg.kv_layers == (5, 11) and len(cfg.state_layers) == 10
    blk = ragged_block_for(cfg.cache_q_heads, 1, cfg.cache_head_dim, PAGE,
                           2, 2)
    rows, pages = 128, 16384
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=rows, steps=8, tokens=tokens or blk, blk=blk,
        prompts=PREFILL_SEQS, chunk=CHUNK, block_size=PAGE,
        num_blocks=pages, max_blocks=MAX_PAGES, attn_impl="pallas")[program]
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    weights = mem.argument_size_in_bytes - mem.alias_size_in_bytes
    assert 9.4e9 < weights < 9.55e9, weights
    seat = 2_097_152 + 3 * 96 * 128 * 4         # 12,288 channels: 96 tiles
    assert mem.alias_size_in_bytes == (2 * pages * PAGE * 640 * 2
                                       + 10 * (rows + 2) * seat)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.5e9
    if program == "decode_multi":
        text = compiled.as_text()

        def calls(kernel):
            return len(re.findall(rf"%{kernel}(?:\.\d+)? = [^\n]*?"
                                  r"custom-call\(", text))
        # one call a layer of each kind a step (the harness counts fused
        # steps by the decode kernel's)
        assert calls("_paged_decode_attention") == 2
        assert calls("_kda_state_update") == 10
        assert calls("_conv_tail_step") == 10
        assert "_gdn_state_update" not in text


# ---- a mixed ragged step: the riding engines' rungs ----------------------

def _riding(name):
    """A riding cell's model at the smallest depth that keeps its layers'
    pattern (a rung that compiles at one period compiles at every one: the
    refusals are of shapes), and its engine's ragged block."""
    from tpuserve.models.config import get_model_config
    if name == "k-exaone-share":
        return k_exaone_share(num_layers=4), 64
    model, depth = {
        "mistral-7b": ("mistralai/Mistral-7B-Instruct-v0.1", 2),
        "mellum2-12b": ("JetBrains/Mellum2-12B-A2.5B-Instruct", 4)}[name]
    return dataclasses.replace(get_model_config(model), num_layers=depth), 128


# Engine.warmup's ladder for an engine that rides (the packed prefill's
# rungs from the decode region and one block of prompt up to
# SchedulerConfig.mixed_token_budget): its foot, its middle and its top
@pytest.mark.parametrize("model,rung", [
    *(("mistral-7b", t) for t in (256, 1024, MIXED_BUDGET)),
    *(("mellum2-12b", t) for t in (256, 1024, MIXED_BUDGET)),
    *(("k-exaone-share", t) for t in (128, 1024, MIXED_BUDGET))])
def test_a_mixed_step_compiles_at_every_rung_a_riding_engine_warms(
        model, rung, one_chip, monkeypatch):
    """64 decode rows and prompt chunks on one flat token axis
    (``forward_ragged(decode_rows=True)`` at the descriptor width of the
    seats), at each rung of the mixed ladder, at the published widths:
    the ragged kernel with its decode part, the decode region's K/V by
    the row scatter and the chunks' by the page, the expert layer at the
    rung's rows."""
    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    cfg, blk = _riding(model)
    S, place = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ragged_block_for(cfg.cache_q_heads, cfg.cache_kv_heads,
                            cfg.cache_head_dim, PAGE, 2, 2) == blk
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=MAX_NUM_SEQS, tokens=rung, blk=blk,
        prompts=MAX_NUM_SEQS, block_size=PAGE, num_blocks=1024,
        max_blocks=MAX_PAGES, attn_impl="pallas")["forward_ragged"]
    text = fn.lower(*args, **{**kwargs, "decode_rows": True}
                    ).compile().as_text()
    for kernel in ("_ragged_paged_attention", "_paged_kv_write"):
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) \
            == cfg.num_layers, kernel
    from tpuserve.models.transformer import decode_region
    from tpuserve.runtime.scheduler import packed_prefill_bucket
    assert decode_region(MAX_NUM_SEQS, blk) == blk
    assert rung in {packed_prefill_bucket(r, blk)
                    for r in range(2 * blk, MIXED_BUDGET + 1, blk)}
