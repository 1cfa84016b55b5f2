"""Grouped matrix product of a sparse expert layer as a Pallas TPU kernel.

An expert layer routes every token to ``k`` of ``E`` experts.  Sorted by
expert, the ``T k`` (token, expert) rows fall into ``E`` contiguous groups
of data-dependent size, and each projection of the layer is one product
``out[rows of group e] = lhs[rows of group e] @ rhs[e]``: ``lhs`` is
``(m, K)``, ``rhs`` the stacked expert kernels ``(E, K, N)``, and
``group_sizes`` ``(E,)`` int32 sums to ``m``.  Shapes are static (always
``m`` rows; a group may be empty; nothing is padded to a per-expert
maximum and no row is dropped) and the sizes are data.

The scheme is the one of ``jax.experimental.pallas.ops.tpu.megablox``: the
row axis is cut into tiles of ``tm`` rows, and a group that starts or ends
inside a tile visits that tile with the rows of its neighbours masked at
the store.  The visits (tile, group) are laid out by
:func:`_visit_plan` from the sizes and scalar-prefetched, so the index
maps pick the expert's kernel for each visit and an expert nobody was
routed to is never read: with ``m`` rows in ``E`` groups there are at
most ``m / tm + E - 1`` visits, and the grid's middle axis is that
DYNAMIC count.  The contraction axis is tiled (``tk``) into a float32
accumulator in VMEM; the output tile stays resident over consecutive
visits of one row tile.

Where the time goes, and hence the tiles (measured on the chip, PERF.md
§6, PR 35): in decode (``m`` ~ 500 rows in groups of ~8) the product is
HBM-bound — every touched expert's ``K x N`` kernel is read once a visit
and the row tile hardly matters; in a packed prefill (4 k to 65 k rows)
it is MXU-bound, a visit of a partly owned tile still multiplies all
``tm`` rows, and a larger ``tm`` trades fewer re-reads of the kernels for
more masked rows.  :func:`tiling` is a static function of the shapes at
trace time.

The custom call is named ``_moe_grouped_matmul``: the benchmark's trace
readers match it (``benchmark/layer_metrics/moe.*``).  Verified against
:func:`grouped_matmul_reference` in interpret mode (tests/test_moe.py)
and compiled for the chip in tests/test_chip_compile_experts.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what the kernel's custom call is called in a profiler trace
KERNEL_NAME = "_moe_grouped_matmul"

# rows at and under which a product counts as the decode regime
DECODE_ROWS = 1024
_LANE = 128
_SUBLANE = 16          # bf16 rows of one packed sublane tile


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for an ``(m, k) x (E, k, n)`` product, as measured
    at Mellum 2's sizes on a v5e (PERF.md §6, PR 35).  Rows: up to 128 in
    the decode regime (16 to 128 read within 5 % of each other there: the
    time is the touched experts' kernels), 256 above it (512 and 1,024
    multiply more masked rows than they save re-reads: 1.2 to 1.8 times
    slower at 4 k to 65 k rows).  The whole contraction axis in one block
    up to 4,096 columns: split in two or three it reads 1.3 to 1.8 times
    slower.  Output columns: all of them while an expert's ``tk x tn``
    slab stays near 4 MiB in bf16, else halved."""
    tm = min(128, _round_up(m, _SUBLANE)) if m <= DECODE_ROWS else 256
    tk = k if k <= 4096 else 2048
    tn = n
    while tk * tn * 2 > (9 << 19) and tn % (2 * _LANE) == 0:
        tn //= 2
    return tm, tk, tn


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """The product row by row in ``jax.numpy``: row ``i`` times the kernel
    of the group it falls in.  For tests at small sizes (it gathers one
    kernel a row)."""
    ends = jnp.cumsum(group_sizes)
    owner = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    owner = jnp.minimum(owner, rhs.shape[0] - 1)
    return jnp.einsum("mk,mkn->mn", lhs, rhs[owner].astype(lhs.dtype),
                      preferred_element_type=jnp.float32).astype(lhs.dtype)


def _visit_plan(group_sizes, m: int, tm: int):
    """The (row tile, group) visits, in order of rows.  Returns
    ``(group_offsets (E + 1,), group_ids (V,), tile_ids (V,), visits)``
    with ``V = m / tm + E - 1`` slots of which the first ``visits`` are
    used: a group visits every tile one of its rows lies in, an empty
    group none."""
    E = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    group_tiles = jnp.where(
        group_sizes == 0, 0,
        (ends + tm - 1) // tm - starts // tm).astype(jnp.int32)
    slots = tiles_m + E - 1
    group_ids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), group_tiles,
                           total_repeat_length=slots)
    # a tile is visited once by the group that owns its first row and once
    # more by every other non-empty group that starts inside it
    starts_inside = (starts % tm != 0) & (group_sizes > 0)
    extra = jnp.zeros((tiles_m,), jnp.int32).at[
        jnp.where(starts_inside, starts // tm, tiles_m)].add(
            1, mode="drop")
    tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), extra + 1,
                          total_repeat_length=slots)
    return offsets, group_ids, tile_ids, jnp.sum(group_tiles)


def _kernel(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, rhs_ref,
            out_ref, acc_ref, *, tm: int, tn: int, tiles_k: int,
            k_rem: int):
    visit = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lhs = lhs_ref[...]
    rhs = rhs_ref[...].astype(lhs.dtype)       # int8 kernels: convert here
    if k_rem:
        # the last block of a contraction axis that tk does not divide
        # reads past the arrays: zero what lies beyond
        keep = jnp.where(k_i == tiles_k - 1, k_rem, lhs.shape[1])
        col = jax.lax.broadcasted_iota(jnp.int32, lhs.shape, 1)
        lhs = jnp.where(col < keep, lhs, jnp.zeros_like(lhs))
        row = jax.lax.broadcasted_iota(jnp.int32, rhs.shape, 0)
        rhs = jnp.where(row < keep, rhs, jnp.zeros_like(rhs))
    acc_ref[...] += jnp.dot(lhs, rhs, preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        group = group_ids_ref[visit]
        first = tile_ids_ref[visit] * tm
        rows = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + first
        mine = (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...].astype(out_ref.dtype),
                                 out_ref[...])


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: bool | None = None):
    """``out[i] = lhs[i] @ rhs[group of row i]``: lhs (m, K); rhs
    (E, K, N), bf16/f32 or int8 (converted to lhs's dtype block by block);
    group_sizes (E,) int32 summing to ``m``.  Returns (m, N) in lhs's
    dtype, at :func:`tiling`'s tiles."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = lhs.shape
    tm, tk, tn = tiling(m, k, rhs.shape[2])
    return _grouped_matmul(lhs, rhs, group_sizes.astype(jnp.int32),
                           tm=tm, tk=tk, tn=tn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "interpret"))
def _grouped_matmul(lhs, rhs, group_sizes, *, tm: int, tk: int, tn: int,
                    interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[2]
    m_pad = _round_up(m, tm)
    if m_pad != m:
        # rows past the last group are computed by no visit's mask
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    tiles_k, k_rem = pl.cdiv(k, tk), k % tk
    offsets, group_ids, tile_ids, visits = _visit_plan(group_sizes, m_pad,
                                                       tm)
    itemsize = jnp.dtype(lhs.dtype).itemsize
    blocks = (2 * (tm * tk * itemsize + tk * tn * rhs.dtype.itemsize
                   + tm * tn * itemsize) + tm * tn * 4
              + (tk * tn * itemsize if rhs.dtype != lhs.dtype else 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pl.cdiv(n, tn), visits, tiles_k),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda n_i, v, k_i, off, gid, tid:
                         (tid[v], k_i)),
            pl.BlockSpec((None, tk, tn), lambda n_i, v, k_i, off, gid, tid:
                         (gid[v], k_i, n_i)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, off, gid, tid:
                               (tid[v], n_i)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                          k_rem=k_rem),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(max(blocks + (8 << 20), 32 << 20),
                                 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n) * itemsize
            + rhs.size * rhs.dtype.itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(offsets, group_ids, tile_ids, lhs, rhs)
    return out[:m] if m_pad != m else out
