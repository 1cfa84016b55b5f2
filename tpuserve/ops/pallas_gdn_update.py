"""Decode-time gated delta-rule state update as a Pallas TPU kernel.

One decode step of a linear-attention head reads and writes its whole
state: ``S <- a S``, ``u = b (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T q``
with ``S`` of shape ``(dk, dv)`` in float32 (ops/gated_delta.py has the
recurrence) -- 2.2 MB a row a layer at Olmo-Hybrid-7B's sizes (30 x 96 x
192), against a few KB of inputs.  The step is pure HBM traffic, so the
kernel's job is to move each row's state through VMEM exactly once, in
place on the seat pool (``models/transformer.py`` ``_lin_decode``; the pool
is ``runtime/kv_cache.create_ssm_state``), as ops/pallas_ssm_update.py does
for Mamba-2's -- with one difference: a row must form ``S^T k`` BEFORE it
can write ``S``, a sum over the state's rows between the read and the write.

**The pool's layout.**  192 is not a whole number of 128-lane tiles: a
state stored ``(seats, H, dk, dv)`` would be padded to 256 lanes in HBM, a
third more bytes a step.  So the heads lie in SLABS of ``hp`` heads side by
side on the lane axis, ``(seats, H / hp, dk, hp * dv)``, with ``hp`` the
fewest heads that fill whole tiles (:func:`heads_per_slab`: 2 x 192 = 384 =
3 x 128); ``dk`` = 96 is twelve sublane tiles.  A slab is one contiguous
147 KB piece of the pool and nothing in it is padding.

* the row's seat is scalar-prefetched and indexes the pool block, so no
  gather or scatter of 2 MB rows is ever materialised;
* the pool is aliased in and out: a step touches only its rows' seats;
* one grid step a batch row: the row's whole state (all slabs, 2.2 MB) is
  one block, double-buffered by Pallas in and out (8.8 MB of VMEM), and the
  kernel walks its slabs in a static loop.

Everything in the kernel is elementwise on a ``(dk, hp * dv)`` slab or a
sum over its sublane axis, so it never has to tell a slab's heads apart by
slicing lanes: ``k`` and ``q`` come as columns ``(dk, H)`` (a head's key
down the sublanes), each spread over its head's ``dv`` lanes by a select on
the lane index; ``v``, the decay, the step size and ``o`` are rows ``(H /
hp, hp * dv)``, a slab a sublane, laid out like the state's lanes.

The custom call is named ``_gdn_state_update``: the benchmark's trace
readers match it (``benchmark/layer_metrics/lin.*``).  Verified against
:func:`gdn_state_update_reference` in interpret mode
(tests/test_olmo_hybrid.py) and compiled for the chip in
tests/test_chip_compile_recurrent.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuserve.ops.gated_delta import gated_delta_step

#: what the kernel's custom call is called in a profiler trace
KERNEL_NAME = "_gdn_state_update"

LANES = 128


def heads_per_slab(heads: int, dv: int) -> int:
    """Heads side by side on a slab's lane axis: the fewest that make
    ``hp * dv`` whole 128-lane tiles where that divides the heads, else
    two (one for an odd count) -- the tests' small sizes, which fill no
    tile either way and run the same two-head code."""
    hp = LANES // math.gcd(dv, LANES)
    if heads % hp == 0:
        return hp
    return 2 if heads % 2 == 0 else 1


def to_slabs(s, hp: int):
    """States (..., H, dk, dv) -> the pool's layout (..., H / hp, dk,
    hp * dv)."""
    *lead, H, dk, dv = s.shape
    s = s.reshape(*lead, H // hp, hp, dk, dv)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, H // hp, dk, hp * dv)


def from_slabs(s, hp: int):
    """The pool's layout (..., H / hp, dk, hp * dv) -> (..., H, dk, dv)."""
    *lead, n, dk, w = s.shape
    s = s.reshape(*lead, n, dk, hp, w // hp)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, n * hp, dk, w // hp)


def gdn_state_update_reference(state, seats, q, k, v, g, beta):
    """The formula, in ``jax.numpy`` on gathered rows.  state (S, H / hp,
    dk, hp * dv) f32 pool; seats (B,) int32; q, k (B, H, dk) f32; v (B, H,
    dv) f32; g (B, H) the log of the decay; beta (B, H).  Returns (o (B,
    H, dv) f32, the pool with the rows' seats updated)."""
    hp = q.shape[1] // state.shape[1]
    o, s = gated_delta_step(from_slabs(state[seats], hp), q, k, v, g, beta)
    return o, state.at[seats].set(to_slabs(s, hp))


def _kernel(seats_ref, q_ref, k_ref, v_ref, a_ref, b_ref, s_ref,
            o_ref, so_ref, *, hp: int, dv: int, channel: bool):
    del seats_ref                       # consumed by the index maps
    n_slab, dk = s_ref.shape[1], s_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, hp * dv), 1)

    def spread(ref, p):
        """The slab's heads' columns (dk, 1) each over its own lanes."""
        x = jnp.broadcast_to(ref[0, :, p * hp:p * hp + 1], (dk, hp * dv))
        for i in range(1, hp):
            x = jnp.where(lane >= i * dv,
                          ref[0, :, p * hp + i:p * hp + i + 1], x)
        return x

    for p in range(n_slab):
        kk = spread(k_ref, p)
        # the decay: a row over the lanes (one scalar a head), or a COLUMN
        # down the sublanes (ops/pallas_kda_update.py: a value a key
        # channel, the state's rows each at their own rate)
        s = s_ref[0, p] * (spread(a_ref, p) if channel      # (dk, hp dv)
                           else a_ref[0, p:p + 1, :])
        u = b_ref[0, p:p + 1, :] * (
            v_ref[0, p:p + 1, :] - jnp.sum(s * kk, axis=0, keepdims=True))
        s = s + kk * u
        so_ref[0, p] = s.astype(so_ref.dtype)
        o_ref[0, p:p + 1, :] = jnp.sum(s * spread(q_ref, p), axis=0,
                                       keepdims=True)


def gdn_state_update(state, seats, q, k, v, g, beta, *,
                     interpret: bool | None = None):
    """Same contract as :func:`gdn_state_update_reference`, with the pool
    updated in place.  Seats of one call are distinct except for the
    trash seat that padding rows share (its contents mean nothing)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gdn_state_update(state, seats, q, k, v, g, beta,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def _gdn_state_update(state, seats, q, k, v, g, beta, *, interpret: bool):
    return state_update_call(state, seats, q, k, v, g, beta,
                             interpret=interpret, channel=False,
                             name=KERNEL_NAME)


def state_update_call(state, seats, q, k, v, g, beta, *, interpret: bool,
                      channel: bool, name: str):
    """The ``pallas_call`` of both forms of the gate, under the caller's
    ``jax.jit`` and custom-call ``name``: ``g`` (B, H) the log of ONE
    decay a head, or with ``channel`` (B, H, dk), a decay a key channel
    (ops/pallas_kda_update.py)."""
    B, H, dk = k.shape
    dv = v.shape[-1]
    n_slab = state.shape[1]
    hp = H // n_slab
    f32 = jnp.float32

    def cols(x):                        # (B, H, dk) -> (B, dk, H)
        return jnp.swapaxes(x.astype(f32), 1, 2)

    def rows(x):                        # (B, H, dv) -> (B, H / hp, hp dv)
        return x.astype(f32).reshape(B, n_slab, hp * dv)

    def lanes(x):                       # (B, H) -> each over its dv lanes
        return rows(jnp.broadcast_to(x.astype(f32)[..., None], (B, H, dv)))

    col_spec = pl.BlockSpec((1, dk, H), lambda b, seats: (b, 0, 0))
    row_spec = pl.BlockSpec((1, n_slab, hp * dv), lambda b, seats: (b, 0, 0))
    pool_spec = pl.BlockSpec((1, n_slab, dk, hp * dv),
                             lambda b, seats: (seats[b], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[col_spec, col_spec, row_spec,
                  col_spec if channel else row_spec, row_spec, pool_spec],
        out_specs=[row_spec, pool_spec],
    )
    o, state = pl.pallas_call(
        functools.partial(_kernel, hp=hp, dv=dv, channel=channel),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, n_slab, hp * dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (the pool; the scalar-prefetch operand counts) is
        # output 1: a step writes only its rows' seats
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(seats.astype(jnp.int32), cols(q), cols(k), rows(v),
      (cols if channel else lanes)(jnp.exp(g)), lanes(beta), state)
    return o.reshape(B, H, dv), state
