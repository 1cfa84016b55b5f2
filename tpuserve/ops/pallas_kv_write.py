"""A prefill stream's K and V into the paged cache, one copy a page.

The row scatter (``ops/attention.py`` ``write_kv_cache``) costs the chip
~65-75 ns an index whatever a row holds (1 KB or 2 KB: PERF.md §6, PR 39),
6 % of the write's HBM time.  A packed prefill or a prefill chunk is laid
out so that every ``block_size`` consecutive rows of its stream are ONE
cache page, in order, or padding (``Engine._pack_ragged``: a prompt starts
on a ragged-block boundary at a cache position that is a whole number of
blocks), and a page is one contiguous ``(block_size x Hkv, D)`` slab of the
cache.  So this kernel moves a page as what it is:

* the cache stays in HBM (``pl.ANY``) and is aliased in and out: a call
  touches only the pages it is given, no copy of the cache exists;
* the page ids are scalar-prefetched; one DMA a page for K and one for V
  straight from the fresh rows to the page, ``INFLIGHT`` pages in flight;
* a page whose id is out of range (its rows are all padding) is skipped.

A prompt's last page is written whole, so the caller zeroes the padding
rows inside it (:func:`paged_kv_write` does): they land past the
sequence's length in its own block, where every reader masks (a zero is
what a fresh cache holds there) and the next decode steps overwrite them.

The custom call is named ``_paged_kv_write``
(tests/test_chip_compile.py pins it).  Verified against the row scatter
in interpret mode (tests/test_kv_page_write.py) and compiled for the chip
in tests/test_chip_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what the kernel's custom call is called in a profiler trace
KERNEL_NAME = "_paged_kv_write"

# pages in flight for K and as many for V: 4, 8 and 16 read the same at
# 4,096 rows and more, 16 is ahead by 6-8 % at 1,024 (PERF.md §6, PR 39)
INFLIGHT = 16


def _kernel(pages_ref, *refs, n_pages: int, n_blocks: int, inflight: int):
    # refs: the fresh rows of each cache (K and V; a latent entry's K
    # alone), the caches in (aliased, unread), the caches out, semaphores
    n = (len(refs) - 1) // 3
    new, out, sems = refs[:n], refs[2 * n:3 * n], refs[-1]

    def copies(i):
        slot, page = i % inflight, pages_ref[i]
        return [pltpu.make_async_copy(new[a].at[i], out[a].at[page],
                                      sems.at[a, slot]) for a in range(n)]

    def live(i):
        return pages_ref[i] < n_blocks

    def issue(i, carry):
        done = jnp.maximum(i - inflight, 0)     # whose semaphores i takes

        @pl.when(jnp.logical_and(i >= inflight, live(done)))
        def _():
            for c in copies(done):
                c.wait()

        @pl.when(live(i))
        def _():
            for c in copies(i):
                c.start()
        return carry

    def drain(i, carry):
        @pl.when(live(i))
        def _():
            for c in copies(i):
                c.wait()
        return carry

    jax.lax.fori_loop(0, n_pages, issue, 0)
    jax.lax.fori_loop(n_pages - inflight, n_pages, drain, 0)


def paged_kv_write(k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                   k: jnp.ndarray, v: jnp.ndarray, slots: jnp.ndarray, *,
                   interpret: bool | None = None):
    """Write a page-aligned stream's K and V into their caches, in place.

    k_cache / v_cache: (num_blocks, block_size, Hkv, D); k / v: the
    stream's rows, (..., Hkv, D) with a multiple of ``block_size`` rows in
    all (a LATENT entry holds K pages alone: ``v_cache`` and ``v`` None,
    and None comes back for them); slots: one flat slot a row
    (``PAD_SLOT`` on padding).  The
    caller's word: rows ``[j * block_size, (j + 1) * block_size)`` hold
    slots ``page * block_size + 0, 1, ...`` of one page, then padding to
    the end, or padding only.  Returns the two caches, equal to the row
    scatter's on every slot written and zero on a written page's padding
    rows."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_kv_write(k_cache, v_cache, k, v, slots, interpret=interpret)


# jitted, as every kernel here: a trunk traces the body once, not once a
# layer (28 layers x 26 prefill programs of un-cached traces were +27 s of
# a warm set-up: PERF.md §6, PR 39)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_kv_write(k_cache, v_cache, k, v, slots, *, interpret: bool):
    nb, bs, hkv, d = k_cache.shape
    slots = slots.reshape(-1)
    real = (slots < nb * bs)[:, None, None]

    def paged(x, cache):
        x = jnp.where(real, x.reshape(-1, hkv, d), 0).astype(cache.dtype)
        return x.reshape(-1, bs * hkv, d)

    pages = jnp.minimum(slots[::bs] // bs, nb).astype(jnp.int32)
    n_pages = pages.shape[0]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    inflight = min(INFLIGHT, n_pages)
    caches = [k_cache] if v_cache is None else [k_cache, v_cache]
    n = len(caches)
    outs = pl.pallas_call(
        functools.partial(_kernel, n_pages=n_pages, n_blocks=nb,
                          inflight=inflight),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[any_spec] * (2 * n), out_specs=[any_spec] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n, inflight))]),
        out_shape=[jax.ShapeDtypeStruct((nb, bs * hkv, d), c.dtype)
                   for c in caches],
        # the caches (after the scalar-prefetch operand and the fresh
        # rows) are the outputs: a call writes only its pages
        input_output_aliases={1 + n + a: a for a in range(n)},
        interpret=interpret,
        name=KERNEL_NAME,
    )(pages, *(paged(x, c) for x, c in zip((k, v), caches)),
      *(c.reshape(nb, bs * hkv, d) for c in caches))
    outs = [o.reshape(k_cache.shape) for o in outs]
    return outs[0], (outs[1] if n == 2 else None)
