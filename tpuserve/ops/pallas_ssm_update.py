"""Decode-time Mamba-2 state update as a Pallas TPU kernel.

One decode step of a state-space head reads and writes its whole state:
``S <- exp(dt A) S + (dt x) B^T`` and ``y = S C``, with ``S`` of shape
``(H, P, N)`` in float32 — 4 MiB a row a layer at Falcon-H1-34B's sizes
(32 x 128 x 256), against a few KiB of inputs.  The step is pure HBM
traffic, so the kernel's job is to move each row's state through VMEM
exactly once, in place on the seat pool (``models/transformer.py``
``_ssm_decode``; the pool is ``runtime/kv_cache.create_ssm_state``):

* the row's seat is scalar-prefetched and indexes the pool block, so no
  gather or scatter of 4 MiB rows is ever materialised;
* the pool is aliased in and out: a step touches only its rows' seats;
* the grid is (rows, head blocks); a block of ``HEADS_PER_BLOCK`` heads
  is one contiguous 1 MiB slab of the pool, double-buffered by Pallas.

The small per-row inputs come laid out ``(B, H / hb, P, hb)`` — state rows
``p`` on sublanes, a block's heads on lanes — so that a head's ``dt x`` is
a ``(P, 1)`` column that broadcasts along the state's lane axis ``N``
without a relayout; ``y`` leaves in the same layout.

The custom call is named ``_ssm_state_update``: the benchmark's trace
readers match it (``benchmark/layer_metrics/ssm.*``).  Verified against
:func:`ssm_state_update_reference` in interpret mode
(tests/test_falcon_h1.py) and compiled for the chip in
tests/test_chip_compile_recurrent.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what the kernel's custom call is called in a profiler trace
KERNEL_NAME = "_ssm_state_update"

# heads of one grid step: 8 x (128 x 256) f32 = 1 MiB of state, 4 MiB of
# VMEM with the in and out blocks double-buffered — inside Mosaic's
# default 16 MiB scope.  Must divide the heads of one B/C group.
HEADS_PER_BLOCK = 8


def ssm_state_update_reference(state, seats, decay, dtx, bm, cm):
    """The formula, in ``jax.numpy`` on gathered rows.  state (S, H, P, N)
    f32 pool; seats (B,) int32; decay (B, H) = exp(dt A); dtx (B, H, P) =
    dt x; bm/cm (B, G, N), head h reading group ``h // (H / G)``.
    Returns (y (B, H, P) f32, the pool with the rows' seats updated)."""
    H, G = state.shape[1], bm.shape[1]
    s = state[seats]                                        # (B, H, P, N)
    bh = jnp.repeat(bm, H // G, axis=1)                     # (B, H, N)
    ch = jnp.repeat(cm, H // G, axis=1)
    s = (s * decay[:, :, None, None]
         + dtx[..., None] * bh[:, :, None, :])
    y = jnp.sum(s * ch[:, :, None, :], axis=-1)
    return y, state.at[seats].set(s)


def _kernel(seats_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
            y_ref, o_ref, *, hb: int):
    del seats_ref                       # consumed by the index maps
    brow = b_ref[0, 0]                                      # (1, N)
    crow = c_ref[0, 0]
    decay = decay_ref[0, 0]                                 # (P, hb)
    dtx = dtx_ref[0, 0]
    for i in range(hb):
        s = (s_ref[0, i] * decay[:, i:i + 1]
             + dtx[:, i:i + 1] * brow)                      # (P, N)
        o_ref[0, i] = s
        y_ref[0, 0, :, i:i + 1] = jnp.sum(s * crow, axis=1, keepdims=True)


def ssm_state_update(state, seats, decay, dtx, bm, cm, *,
                     interpret: bool | None = None):
    """Same contract as :func:`ssm_state_update_reference`, with the pool
    updated in place.  Seats of one call are distinct except for the
    trash seat that padding rows share (its contents mean nothing)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ssm_state_update(state, seats, decay, dtx, bm, cm,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def _ssm_state_update(state, seats, decay, dtx, bm, cm, *, interpret: bool):
    B, H, P = dtx.shape
    G, N = bm.shape[1:]
    hb = min(HEADS_PER_BLOCK, H // G)
    while (H // G) % hb:
        hb -= 1
    nhb = H // hb

    def cols(x):                        # (B, H, P) -> (B, H/hb, P, hb)
        return x.reshape(B, nhb, hb, P).swapaxes(2, 3)

    f32 = jnp.float32
    decay_c = cols(jnp.broadcast_to(decay.astype(f32)[..., None], (B, H, P)))
    dtx_c = cols(dtx.astype(f32))
    b4 = bm.astype(f32).reshape(B, G, 1, N)
    c4 = cm.astype(f32).reshape(B, G, 1, N)
    per_group = nhb // G                # head blocks of one B/C group

    col_spec = pl.BlockSpec((1, 1, P, hb), lambda b, h, seats: (b, h, 0, 0))
    grp_spec = pl.BlockSpec((1, 1, 1, N),
                            lambda b, h, seats: (b, h // per_group, 0, 0))
    pool_spec = pl.BlockSpec((1, hb, P, N),
                             lambda b, h, seats: (seats[b], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nhb),
        in_specs=[col_spec, col_spec, grp_spec, grp_spec, pool_spec],
        out_specs=[col_spec, pool_spec],
    )
    y_c, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nhb, P, hb), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 5 (the pool; the scalar-prefetch operand counts) is
        # output 1: a step writes only its rows' seats
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(seats.astype(jnp.int32), decay_c, dtx_c, b4, c4, state)
    return y_c.swapaxes(2, 3).reshape(B, H, P), state
