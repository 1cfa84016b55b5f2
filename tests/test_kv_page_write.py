"""A prefill stream's K and V go into the paged cache a page at a time
(``tpuserve/ops/pallas_kv_write.py``, interpret mode here): what any reader
can reach is bit for bit what the row scatter (``write_kv_cache``) puts
there, and the static gates leave every other caller on the scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_scopes import trunk_programs
from tpuserve.models.config import get_model_config
from tpuserve.ops.attention import (PAD_SLOT, kv_stream_by_page,
                                    write_kv_cache, write_kv_entry)
from tpuserve.ops.pallas_kv_write import KERNEL_NAME
from tpuserve.runtime.kv_cache import CacheConfig, create_kv_cache

D = 128
NUM_BLOCKS = 160


def _stream(case: str, bs: int):
    """``(T, [(first row, cache position of that row, rows, blocks)])``: a
    sequence's ``rows`` real rows lie from ``first row`` on and hold the
    positions from ``cache position`` on of a sequence whose block table is
    ``blocks`` (block ids, position // bs indexes it)."""
    blk = 4 * bs                        # the ragged block: whole pages
    if case == "ends_mid_page":
        return 2 * blk, [(0, 0, blk + bs // 2 + 3, list(range(10, 20)))]
    if case == "starts_at_prefix_hit":
        # 64 cached positions in shared blocks 3.. ; the stream holds the rest
        return blk, [(0, 64, 2 * bs + 5, list(range(3, 3 + 64 // bs + 3)))]
    if case == "chunk_continued":
        # one (1, C) chunk of a long prompt, 2,048 positions already written
        return 2 * bs, [(0, 2048, bs + 7,
                         list(range(1, 1 + 2048 // bs + 2)))]
    if case == "padding_between":
        # a short prompt, a ragged block of padding only, another prompt
        return 4 * blk, [(0, 0, bs - 1, [40]),
                         (2 * blk, 0, blk + 1, list(range(50, 56)))]
    if case == "shared_first_seen":
        # two prompts of one batch with the same two first blocks (a prefix
        # first seen in this batch): both write them, as both scatter them
        return 2 * blk, [(0, 0, 2 * bs + 9, [7, 8, 20]),
                         (blk, 0, 3 * bs, [7, 8, 30])]
    raise AssertionError(case)


CASES = ("ends_mid_page", "starts_at_prefix_hit", "chunk_continued",
         "padding_between", "shared_first_seen")


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("hkv", (4, 8))
@pytest.mark.parametrize("bs", (16, 32))
def test_pages_hold_what_the_row_scatter_writes(bs, hkv, case):
    T, seqs = _stream(case, bs)
    rng = np.random.default_rng(bs * 100 + hkv)
    slots = np.full((T,), PAD_SLOT, np.int32)
    k = rng.standard_normal((T, hkv, D)).astype(np.float32)
    v = rng.standard_normal((T, hkv, D)).astype(np.float32)
    # padding rows carry what the kernels leave there: anything
    k[rng.random(T) < 0.3] = np.nan
    reach = np.zeros((NUM_BLOCKS * bs,), bool)      # slots below a length
    owned = np.zeros((NUM_BLOCKS,), bool)
    by_pos = {}                                     # shared pages: same rows
    for first, pos0, n, blocks in seqs:
        pos = pos0 + np.arange(n)
        bt = np.asarray(blocks)
        slots[first:first + n] = bt[pos // bs] * bs + pos % bs
        reach[slots[first:first + n]] = True
        owned[bt] = True
        for r, s in zip(range(first, first + n), slots[first:first + n]):
            k[r], v[r] = by_pos.setdefault(
                int(s), (np.nan_to_num(k[r]), np.nan_to_num(v[r])))
    cache0 = {n: jnp.asarray(rng.standard_normal((NUM_BLOCKS, bs, hkv, D)),
                             jnp.bfloat16) for n in "kv"}
    want = {n: np.asarray(write_kv_cache(cache0[n], jnp.asarray(x),
                                         jnp.asarray(slots)), np.float32)
            for n, x in (("k", k), ("v", v))}
    got = jax.jit(lambda e, k, v, s: write_kv_entry(e, k, v, s, True))(
        cache0, jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16),
        jnp.asarray(slots))
    assert set(got) == {"k", "v"}
    for n in "kv":
        page = np.asarray(got[n], np.float32)
        flat = page.reshape(NUM_BLOCKS * bs, hkv, D)
        assert np.isfinite(flat).all()
        # every slot a reader can reach: the row scatter's, bit for bit
        np.testing.assert_array_equal(
            flat[reach], want[n].reshape(flat.shape)[reach])
        # a block no sequence owns (a padding-only page has none): untouched
        np.testing.assert_array_equal(
            page[~owned], np.asarray(cache0[n], np.float32)[~owned])
        # past a sequence's length inside its last page: zeros, as a
        # fresh cache holds there (nothing stale, nothing of a padding row)
        for first, pos0, n_rows, blocks in seqs:
            end = pos0 + n_rows
            if end % bs:
                tail = page[blocks[end // bs], end % bs:]
                assert not tail.any()


def _traced(program: str, *, attn_impl="pallas", kv_dtype=None,
            trunk_kw=None, **shape_kw) -> str:
    """The jaxpr of one trunk of ``tiny-qwen3`` (the Pallas kernels
    run in interpret mode on the CPU)."""
    cfg = get_model_config("tiny-qwen3")
    fn, args, kwargs = trunk_programs(cfg, attn_impl=attn_impl,
                                      **shape_kw)[program]
    if kv_dtype is not None:            # the cache: the one list of dicts
        args = list(args)
        at = next(i for i, a in enumerate(args)
                  if isinstance(a, list) and isinstance(a[0], dict))
        args[at] = jax.eval_shape(lambda: create_kv_cache(cfg, CacheConfig(
            block_size=shape_kw["block_size"], num_blocks=16,
            max_blocks_per_seq=8, dtype=kv_dtype)))
    return str(fn.trace(*args, **{**kwargs, **(trunk_kw or {})}).jaxpr)


GATES = {
    # the two trunks that own an aligned stream take the writer ...
    "packed_prefill": ("forward_ragged", dict(block_size=8, blk=8), True),
    "chunk": ("prefill_chunk", dict(block_size=8, chunk=16), True),
    # ... and every static gate sends a caller back to the row scatter
    "int8_entry": ("forward_ragged",
                   dict(block_size=8, blk=8, kv_dtype="int8"), False),
    # (since PR 53 a mixed step's prompt chunks go by the page too, behind
    # its decode region's rows, which keep the scatter: both are traced)
    "decode_rows": ("forward_ragged",
                    dict(block_size=8, blk=8,
                         trunk_kw={"decode_rows": True}), True),
    "ragged_block_under_a_page": ("forward_ragged",
                                  dict(block_size=16, blk=8), False),
    "chunk_under_a_page": ("prefill_chunk", dict(block_size=32, chunk=16),
                           False),
    "reference_attention": ("forward_ragged",
                            dict(block_size=8, blk=8,
                                 attn_impl="reference"), False),
    "decode_window": ("decode_multi", dict(block_size=8), False),
}


@pytest.mark.parametrize("gate", sorted(GATES))
def test_the_static_gates_choose_the_write(gate):
    program, kw, paged = GATES[gate]
    text = _traced(program, **kw)
    assert (KERNEL_NAME in text) is paged, gate
    # the scatter is what every other caller traced, and a mixed step's
    # decode region
    assert (paged and gate != "decode_rows") or "scatter" in text


_PLAIN = {"k": jnp.zeros((4, 8, 2, 16)), "v": jnp.zeros((4, 8, 2, 16))}
_LATENT = {"k": jnp.zeros((4, 8, 1, 256))}            # MLA: K pages alone


@pytest.mark.parametrize("what,entry,unit,impl,mesh,by_page", [
    ("whole pages of K and V", _PLAIN, 16, "pallas", None, True),
    ("whole pages of a latent entry", _LATENT, 16, "pallas", None, True),
    ("a unit that is no whole page", _PLAIN, 12, "pallas", None, False),
    ("reference attention", _PLAIN, 16, "reference", None, False),
    ("a mesh", _PLAIN, 16, "pallas", object(), False),
    ("int8 pages", {**_PLAIN, "ks": 0, "vs": 0}, 16, "pallas", None, False),
    ("an int8 latent entry", {**_LATENT, "ks": 0}, 16, "pallas", None,
     False),
])
def test_the_gate_reads_the_entry(what, entry, unit, impl, mesh, by_page):
    """What streams by page and what is still refused, each by what the
    entry and the dispatch hold: int8 pages quantize a row at a time
    (a latent entry's two slice scales too), a mesh has no such kernel."""
    assert kv_stream_by_page(entry, unit, impl, mesh=mesh) is by_page, what


@pytest.mark.parametrize("bs", (16, 32))
def test_latent_pages_hold_what_the_row_scatter_writes(bs):
    """A latent entry (K pages alone, one 576-value vector a token stored
    as 640 lanes) through the page copy: bit for bit the row scatter's on
    every slot written, zeros on a written page's padding rows and past
    the latent's own width, and no V page comes back."""
    from tpuserve.ops.attention import write_mla_entry
    T, seqs = _stream("padding_between", bs)
    rng = np.random.default_rng(bs)
    slots = np.full((T,), PAD_SLOT, np.int32)
    for first, pos, rows, blocks in seqs:
        at = pos + np.arange(rows)
        slots[first:first + rows] = np.asarray(blocks)[at // bs] * bs \
            + at % bs
    latent = jnp.asarray(rng.standard_normal((T, 576)), jnp.bfloat16)
    entry = {"k": jnp.zeros((NUM_BLOCKS, bs, 1, 640), jnp.bfloat16)}
    by_row = write_mla_entry(entry, latent, jnp.asarray(slots))
    by_page = write_mla_entry(entry, latent, jnp.asarray(slots),
                              aligned=True)
    assert set(by_page) == {"k"}
    np.testing.assert_array_equal(np.asarray(by_page["k"], np.float32),
                                  np.asarray(by_row["k"], np.float32))
    assert float(jnp.abs(by_page["k"][..., 576:]).max()) == 0.0
    assert float(jnp.abs(by_page["k"]).max()) > 0.0
