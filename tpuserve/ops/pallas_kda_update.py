"""Decode-time Kimi-delta state update as a Pallas TPU kernel: the gated
delta rule with a decay for every KEY CHANNEL (ops/gated_delta.py
``kda_step``).

One decode step of a head reads and writes its whole state, ``S <- diag(a)
S``, ``u = b (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T q`` with ``S`` of
shape ``(dk, dv)`` in float32 -- 2,097,152 B a row a layer each way at
Ling-3.0-flash's sizes (32 x 128 x 128), against 50 KB of inputs: pure HBM
traffic, 5.12 us a row-layer at 819 GB/s.  The kernel's body and call are
ops/pallas_gdn_update.py's (``state_update_call``: the pool's layout in
slabs of heads, the row's seat scalar-prefetched into the pool's index map,
the pool aliased in and out, one grid step a batch row, a static loop over
the slabs) with ONE difference, its ``channel`` branch: the decay is a
vector over the state's ROWS, so it comes as a COLUMN ``(dk, H)`` down the
sublanes, as ``k`` and ``q`` do, and is spread over its head's lanes like
them -- where the scalar gate's comes as a row over the lanes.  At the published sizes ``dv`` = 128 is one lane
tile, a slab is one head (``heads_per_slab`` = 1) and nothing is packed;
the tests' small sizes run slabs of two.

The custom call is named ``_kda_state_update``, NOT ``_gdn_state_update``:
the benchmark's readers tell the two gates' kernels apart by name
(``benchmark/layer_metrics/kda.*``, ``lin.*``), and Olmo-Hybrid's programs
are what they were.  Verified against :func:`kda_state_update_reference`
in interpret mode (tests/test_ling_hybrid.py) and compiled for the chip in
tests/test_chip_compile_recurrent.py.
"""

from __future__ import annotations

import functools

import jax

from tpuserve.ops.gated_delta import kda_step
from tpuserve.ops.pallas_gdn_update import (from_slabs, state_update_call,
                                            to_slabs)

#: what the kernel's custom call is called in a profiler trace
KERNEL_NAME = "_kda_state_update"


def kda_state_update_reference(state, seats, q, k, v, g, beta):
    """The formula, in ``jax.numpy`` on gathered rows.  state (S, H / hp,
    dk, hp * dv) f32 pool; seats (B,) int32; q, k (B, H, dk) f32; v (B, H,
    dv) f32; g (B, H, dk) the log of the decay; beta (B, H).  Returns (o
    (B, H, dv) f32, the pool with the rows' seats updated)."""
    hp = q.shape[1] // state.shape[1]
    o, s = kda_step(from_slabs(state[seats], hp), q, k, v, g, beta)
    return o, state.at[seats].set(to_slabs(s, hp))


def kda_state_update(state, seats, q, k, v, g, beta, *,
                     interpret: bool | None = None):
    """Same contract as :func:`kda_state_update_reference`, with the pool
    updated in place.  Seats of one call are distinct except for the
    trash seat that padding rows share (its contents mean nothing)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _kda_state_update(state, seats, q, k, v, g, beta,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def _kda_state_update(state, seats, q, k, v, g, beta, *, interpret: bool):
    return state_update_call(state, seats, q, k, v, g, beta,
                             interpret=interpret, channel=True,
                             name=KERNEL_NAME)
