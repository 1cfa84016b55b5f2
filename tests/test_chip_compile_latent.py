"""Latent attention on the chip's compiler: the paged kernels' latent entry
at the published widths (128 query heads on one cached vector of 512 + 64
values a token, stored as 640 lanes) and the openPangu-Ultra-MoE cell's
three served trunks, compiled for a described v5e (see
``tests/test_chip_compile.py`` and ``tests/chip_v5e.py``)."""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_v5e import (CHUNK, LATENT, MAX_NUM_SEQS, MAX_PAGES, PAGE,
                      PREFILL_SEQS, WIDTHS, openpangu_share, shapes_on)
from chip_v5e import (  # noqa: F401  (fixtures, found by name)
    _no_persistent_cache, one_chip, topo)

ROWS = 128              # the cell's decode seats (--max-num-seqs 128)
# pages of 32 tokens the server sizes (--num-blocks 0): 0.9 of the chip's
# 15.75 GiB less 12.32 GB of weights, over 286,720 B a page: 2.90 GB
POOL = 10104
SCALE = 192 ** -0.5
# the latent decode call's (pages a chunk, pages a tile, sequences a
# program) at 128 rows: a chunk of 512 tokens contracted whole, 16 rows
LATENT_TILING = (16, 16, 16)


def _decode_call(one_chip, lanes):
    from tpuserve.ops.pallas_paged_attention import paged_decode_attention
    S, _ = shapes_on(one_chip)
    hq, _, v_lanes = LATENT

    def call(q, pages, tables, lens):
        return paged_decode_attention(q, pages, None, tables, lens, SCALE,
                                      interpret=False, v_lanes=v_lanes)
    return jax.jit(call).lower(
        S((ROWS, hq, lanes), jnp.bfloat16),
        S((POOL, PAGE, 1, lanes), jnp.bfloat16),
        S((ROWS, MAX_PAGES), jnp.int32), S((ROWS,), jnp.int32))


def test_the_latent_decode_call_compiles_under_its_name(one_chip):
    """128 rows of 128 query heads against one 640-lane head: the call is
    the decode kernel's (the benchmark's readers count fused steps by its
    name), the pool reaches it as a bitcast and never as a copy, and what
    comes back is 512 lanes wide: V was read off the landed K page."""
    hq, lanes, v_lanes = LATENT
    text = _decode_call(one_chip, lanes).compile().as_text()
    assert re.search(rf"%_paged_decode_attention(?:\.\d+)? = bf16"
                     rf"\[{ROWS},{hq},{v_lanes}\]", text)
    assert re.search(rf"bf16\[{POOL},{PAGE},{lanes}\]\S* bitcast\(", text)
    assert not re.search(rf"bf16\[{POOL},\S* copy\(", text)


def test_the_latent_decode_call_fits_what_the_footprint_says(one_chip,
                                                             monkeypatch):
    """The latent entry's own kernel (three slots of K pages, every
    chunk's pages started straight-line and waited for once) at the
    tiling the rule gives, the triple written out: handed NO more scoped
    VMEM than ``vmem_footprint`` counts, the chip's compiler still takes
    it, so the clamp's bound is at or above what the compiler needs and
    inside ``VMEM_LIMIT_BYTES``; under the call's name, the pool a
    bitcast."""
    from jax.experimental.pallas import tpu as pltpu
    from tpuserve.ops import pallas_paged_attention as ppa
    hq, lanes, v_lanes = LATENT
    pages_g, pages_t, seqs_pp = ppa.decode_tiling(PAGE, 1, MAX_PAGES, ROWS)
    pages_g, seqs_pp = ppa._clamp_to_vmem_budget(
        pages_g, seqs_pp, PAGE, 1, lanes, 2, hq, 2, v_lanes=v_lanes)
    assert (pages_g, ppa._tile_pages(pages_g, pages_t),
            seqs_pp) == LATENT_TILING
    bound = ppa.vmem_footprint(pages_g, seqs_pp, 1, PAGE, 1, lanes, 2, hq, 2,
                               decode=True, v_lanes=v_lanes)
    assert bound <= ppa.VMEM_LIMIT_BYTES
    monkeypatch.setattr(
        ppa, "compiler_params", lambda *semantics: pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=bound))
    text = _decode_call(one_chip, lanes).compile().as_text()
    assert re.search(rf"%_paged_decode_attention(?:\.\d+)? = bf16"
                     rf"\[{ROWS},{hq},{v_lanes}\]", text)
    assert re.search(rf"bf16\[{POOL},{PAGE},{lanes}\]\S* bitcast\(", text)
    # and a quarter less is refused: the bound is no loose one
    monkeypatch.setattr(
        ppa, "compiler_params", lambda *semantics: pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=bound * 3 // 4))
    jax.clear_caches()
    with pytest.raises(Exception, match="(?i)vmem"):
        _decode_call(one_chip, lanes).compile()


# (pages a chunk, pages a tile, sequences a program) of the K/V entry at
# the widths the cells run, as they stood before the latent entry had a
# kernel of its own (PR 51): a change to the latent rule must not move them
KV_TILINGS = {
    "qwen3-0.6b": (16, 8, 64),
    "llama-8b": (16, 8, 64),
    "llama-8b-tp4": (16, 16, 64),
    "falcon-h1-34b": (16, 16, 64),
    "mellum2-12b": (16, 16, 64),
    "olmo-hybrid-7b": (16, 2, 64),
}


@pytest.mark.parametrize("width", WIDTHS)
def test_the_kv_entrys_tiling_is_what_it_was(width):
    from tpuserve.ops import pallas_paged_attention as ppa
    hq, hkv, d = WIDTHS[width]
    pages_g, pages_t, seqs_pp = ppa.decode_tiling(PAGE, hkv, MAX_PAGES,
                                                  MAX_NUM_SEQS)
    pages_g, seqs_pp = ppa._clamp_to_vmem_budget(pages_g, seqs_pp, PAGE, hkv,
                                                 d, 2, hq, 2)
    assert (pages_g, ppa._tile_pages(pages_g, pages_t),
            seqs_pp) == KV_TILINGS[width]


def test_a_page_of_576_lanes_as_it_is_has_no_whole_tiles_to_copy(one_chip):
    """Why the page is stored as 640 lanes: the chip lays a 576-wide row
    out in five 128-lane tiles whatever its shape says (the HBM array IS
    640 wide), and the kernel's copy of a page may take whole tiles only:
    the compiler refuses the slice (step 0 of PR 50, PERF.md section 6)."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _decode_call(one_chip, 576).compile()


@pytest.mark.parametrize("tokens,seqs,decode_rows", [
    (8192, PREFILL_SEQS, False), (2048, ROWS, True)],
    ids=["packed-top-rung", "mixed-top-rung"])
def test_the_latent_ragged_kernel_compiles_at_its_block(
        tokens, seqs, decode_rows, one_chip, monkeypatch):
    """The packed prefill's kernel at the top rung of its ladder, and a
    mixed step's at the top rung of its own (2,048 rows, the first 128 the
    seats' decode rows, each against its own pages: the kernel WITH its
    decode part): 128 query heads of a 640-lane latent take a ragged block
    of 8 rows (1,024 query rows a dot; 16 pass the kernel's fast memory),
    one KV head's pages land as (page, 640) with no head row to pad, and
    the group of pages stays 16 deep."""
    from tpuserve.ops import pallas_ragged_attention as ragged
    from tpuserve.ops.pallas_paged_attention import _clamp_to_vmem_budget
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S, _ = shapes_on(one_chip)
    hq, lanes, v_lanes = LATENT
    blk = ragged.ragged_block_for(hq, 1, lanes, PAGE, 2, 2)
    assert blk == 8
    assert _clamp_to_vmem_budget(16, blk, PAGE, 1, lanes, 2, hq, 2,
                                 rows_per_dot=True, flat_page=True) \
        == (16, blk)
    per_seq = S((seqs,), jnp.int32)

    def call(q, pages, tables, kv, qs, ql, meta, blocks):
        return ragged.ragged_paged_attention(
            q, pages, None, tables, kv, qs, ql, meta, blocks, SCALE,
            interpret=False, blk_q=blk, decode_rows=decode_rows,
            v_lanes=v_lanes)
    text = jax.jit(call).lower(
        S((tokens, hq, lanes), jnp.bfloat16),
        S((POOL, PAGE, 1, lanes), jnp.bfloat16),
        S((seqs, MAX_PAGES), jnp.int32), per_seq, per_seq, per_seq,
        S((2,), jnp.int32), S((tokens // blk,), jnp.int32)
    ).compile().as_text()
    assert re.search(rf"%_ragged_paged_attention(?:\.\d+)? = bf16"
                     rf"\[{tokens},{hq},{v_lanes}\]", text)
    assert not re.search(rf"bf16\[{POOL},\S* copy\(", text)


@pytest.mark.parametrize("program,tokens,riding", [
    ("decode_multi", 0, 0), ("forward_ragged", 8192, 0),
    ("prefill_chunk", 0, 0), ("forward_ragged", 2048, ROWS)],
    ids=["decode_multi", "forward_ragged", "prefill_chunk", "mixed"])
def test_the_openpangu_cell_fits_the_chip(program, tokens, riding, one_chip,
                                          monkeypatch):
    """The cell's whole trunks at the published widths: the first 7 layers
    (3 dense, 4 of experts), 16 of 256 experts, 19,200 vocabulary rows; a
    fused decode window of 128 rows, the top rung of the packed-prefill
    ladder, a chunk against cached latents and (``riding``: the decode
    rows at the head of the stream) a mixed step at the top rung of its
    ladder, 2,048 rows whose attention stands WHOLE (no
    ``MLA_PACKED_ROWS`` pieces with decode rows), beside a pool of 10,104
    latent pages (2.90 GB: what 0.9 of the chip leaves after 12.32 GB of
    weights).
    The chip's compiler refuses what does not fit 16 GB.  The pool is
    aliased in and out WHOLE and EXACTLY (2 B x 640 lanes x 32 tokens x 7
    layers a page, no V pages, no copy), every attention call of the
    programs is a Pallas kernel's latent entry, and decode's keeps the
    name the benchmark's readers count steps by, once a layer."""
    from test_scopes import trunk_programs
    from tpuserve.ops.pallas_ragged_attention import ragged_block_for

    S, place = shapes_on(one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = openpangu_share(num_layers=7)
    blk = ragged_block_for(cfg.cache_q_heads, cfg.cache_kv_heads,
                           cfg.cache_head_dim, PAGE, 2, 2)
    assert (blk, cfg.cache_head_dim) == (8, 640)
    fn, args, kwargs = trunk_programs(
        cfg, S, place, rows=ROWS, steps=8, tokens=tokens or blk, blk=blk,
        prompts=riding or PREFILL_SEQS, chunk=CHUNK, block_size=PAGE,
        num_blocks=POOL, max_blocks=MAX_PAGES, attn_impl="pallas",
        decode_rows=bool(riding))[program]
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == POOL * PAGE * 640 * 2 * 7
    weights = mem.argument_size_in_bytes - mem.alias_size_in_bytes
    assert 12.3e9 < weights < 12.4e9, weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.9e9
    text = compiled.as_text()
    kernel = {"decode_multi": "_paged_decode_attention"}.get(
        program, "_ragged_paged_attention")
    assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == 7
    # no score tensor of XLA's reference attention: nothing float32 is as
    # large as rows x heads x context would be
    assert not re.search(r"f32\[\d+,128,\d{4,}\]", text)
