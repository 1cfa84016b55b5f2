"""Decode-time step of a recurrent mixer's short convolution as a Pallas
TPU kernel.

The depthwise causal convolution in front of a recurrent mixer (Mamba-2's
``xBC``, a gated delta-rule layer's ``[q | k | v]``; ops/ssm.py
``causal_conv``) remembers a sequence's last ``W - 1`` input rows, in the
seat pool beside the mixer's state (``runtime/kv_cache.create_ssm_state``):
138 KB a seat a layer at Olmo-Hybrid-7B's sizes (3 x 11,520 float32), 30 KB
at Falcon-H1-34B's (3 x 5,120 bfloat16).  One decode step forms ``out =
sum_i tail[i] k[i] + x k[W - 1] (+ bias)`` and leaves ``[tail[1:], x]``
behind: nothing but HBM traffic, which XLA spends four times over (a gather
of the rows' memories, their join with the new row, the taps' read of the
join, the scatter back).  This kernel moves each row's memory through VMEM
exactly once, in place on the pool, as ops/pallas_gdn_update.py and
ops/pallas_ssm_update.py move the state beside it:

* the seats are scalar-prefetched and the pool is aliased in and out: no
  gather or scatter is ever materialised, a step touches only its rows'
  seats;
* the pool is DECLARED to live in HBM (``out_shape=pltpu.HBM(...)``, which
  colours the aliased operand too; a ``BlockSpec``'s memory space never
  reaches XLA).  Left to itself the chip's compiler places a custom call's
  operand in its faster memory space when it is small enough (9 MB here;
  the 146 MB state pool beside it never is) and copies the WHOLE pool
  there and back around the call every step of a fused window, in place
  or not (PERF.md §6, PR 46).  The declared space never reaches the
  caller's types, so the window's carry holds one type at both ends;
* a row's memory lands in rows ``0 .. W - 2`` of a ``(W, C / 128, 128)``
  VMEM buffer and the new row, rounded as the pool stores it, in row ``W -
  1``: what the step leaves behind, rows ``1 .. W - 1`` of that buffer,
  goes back to the seat as ONE copy with nothing shifted;
* the copies are issued by hand, ``INFLIGHT`` rows' worth in flight (a row
  is four small ones: memory in, new row in, memory out, result out), as
  the paged decode kernel keeps its pages in flight: a block a row through
  ``BlockSpec`` pays a grid step (~0.35 us) a row, a quarter more a call
  (the probe of PR 45's builder, quoted in PERF.md §6, PR 46; not
  measured again).

**The pool's layout** is :func:`tail_slab`'s: ``(seats, W - 1, C / 128,
128)``, each row of channels as whole 128-lane tiles down the sublanes, so
that a seat's memory is one contiguous piece, every tap dense ``(8, 128)``
tiles, and the layout the chip gives the array between programs IS the
kernel's (``(seats, W - 1, C)`` is kept with the seats on the sublanes, and
every window then turns all of it round on its way in and out); where
``C`` is no multiple of 128 (the tests' small models) one slab of ``C``
lanes.  A prefill reshapes from and to it (models/transformer.py
``_read_tails``, ``_keep_tails``).

The taps are summed in float32 in the order ``causal_conv`` sums them and
the new row is rounded to the pool's dtype as ``rows[:, 1:].astype(...)``
rounds it: bit for bit :func:`conv_tail_step_reference` in interpret mode
(tests/test_seat_pool.py).  The custom call is named ``_conv_tail_step``;
compiled for the chip in tests/test_chip_compile_recurrent.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuserve.ops.ssm import causal_conv

#: what the kernel's custom call is called in a profiler trace
KERNEL_NAME = "_conv_tail_step"

LANES = 128

# rows whose copies are in flight at once.  By the probe of PR 45's
# builder (a chip run of that PR, whose file is not kept: PERF.md §6, PR
# 46) 2 is half as fast, 4 within 5 %, 16 the same; this PR's own trace
# confirms only the call's time at 8 (29.6 us at Olmo-Hybrid's sizes)
INFLIGHT = 8


def tail_slab(channels: int) -> tuple[int, int]:
    """The pool's two minor axes for a row of ``channels`` inputs: whole
    128-lane tiles down the sublanes.  Every published configuration's
    channel count is a multiple of 128; one slab of ``channels`` lanes
    serves the tests' small models alone (interpret mode: that shape is
    never compiled for the chip)."""
    if channels % LANES == 0:
        return channels // LANES, LANES
    return 1, channels


def conv_tail_step_reference(pool, seats, x, kernel, bias):
    """The formula, in ``jax.numpy`` on gathered rows.  pool (S, W - 1,
    *tail_slab(C)) the seats' memories; seats (B,) int32; x (B, C) the
    step's new input row; kernel (W, C), ``kernel[W - 1]`` weighing ``x``;
    bias (C,) or None.  Returns (the convolution's output row (B, C) f32,
    the pool with the rows' seats holding ``[memory[1:] ++ x]``)."""
    B, C = x.shape
    out, rows = causal_conv(x[:, None], pool[seats].reshape(B, -1, C),
                            kernel, bias)
    return out[:, 0], pool.at[seats].set(
        rows[:, 1:].astype(pool.dtype).reshape(B, *pool.shape[1:]))


def _kernel(seats_ref, x_hbm, k_ref, _pool_in, out_hbm, pool, buf, xbuf, obuf,
            sems, *, n_rows: int, inflight: int, biased: bool):
    W = k_ref.shape[0] - 1              # the taps, then a row for the bias

    def ins(b):
        slot = b % inflight
        return (pltpu.make_async_copy(pool.at[seats_ref[b]],
                                      buf.at[slot, pl.ds(0, W - 1)],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(x_hbm.at[b], xbuf.at[slot],
                                      sems.at[1, slot]))

    def outs(b):
        slot = b % inflight
        return (pltpu.make_async_copy(buf.at[slot, pl.ds(1, W - 1)],
                                      pool.at[seats_ref[b]],
                                      sems.at[2, slot]),
                pltpu.make_async_copy(obuf.at[slot], out_hbm.at[b],
                                      sems.at[3, slot]))

    for b in range(inflight - 1):       # at most n_rows: the caller's min
        for c in ins(b):
            c.start()

    def row(b, carry):
        slot = b % inflight
        for c in ins(b):
            c.wait()
        x = xbuf[slot]
        # causal_conv's rows, in the new row's dtype, and its order of terms
        rows = [buf[slot, i].astype(x.dtype) for i in range(W - 1)] + [x]
        acc = rows[0].astype(jnp.float32) * k_ref[0]
        for i in range(1, W):
            acc = acc + rows[i].astype(jnp.float32) * k_ref[i]
        if biased:
            acc = acc + k_ref[W]
        obuf[slot] = acc
        buf[slot, W - 1] = x.astype(buf.dtype)
        for c in outs(b):
            c.start()

        # the slot of the row before is free once its copies have left
        @pl.when(b >= 1)
        def _():
            for c in outs(b - 1):
                c.wait()

        @pl.when(b + inflight - 1 < n_rows)
        def _():
            for c in ins(b + inflight - 1):
                c.start()
        return carry

    jax.lax.fori_loop(0, n_rows, row, 0)
    for c in outs(n_rows - 1):
        c.wait()


def conv_tail_step(pool, seats, x, kernel, bias, *,
                   interpret: bool | None = None):
    """Same contract as :func:`conv_tail_step_reference`, with the pool
    updated in place.  Seats of one call are distinct except for the
    trash seat that padding rows share (its contents, and those rows'
    output, mean nothing)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _conv_tail_step(pool, seats, x, kernel, bias, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("pool",))
def _conv_tail_step(pool, seats, x, kernel, bias, *, interpret: bool):
    B, C = x.shape
    W = kernel.shape[0]
    slab = pool.shape[2:]
    f32 = jnp.float32
    inflight = min(INFLIGHT, B + 1)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # the taps' weights and, as one more row, the bias (zeros, unread,
    # where there is none): one operand whatever the mixer
    taps = jnp.concatenate([
        kernel.astype(f32),
        (jnp.zeros((C,), f32) if bias is None else bias.astype(f32))[None]
    ]).reshape(W + 1, *slab)
    out, pool = pl.pallas_call(
        functools.partial(_kernel, n_rows=B, inflight=inflight,
                          biased=bias is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[hbm,
                      pl.BlockSpec(taps.shape, lambda i, seats: (0, 0, 0)),
                      hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.VMEM((inflight, W, *slab), pool.dtype),
                            pltpu.VMEM((inflight, *slab), x.dtype),
                            pltpu.VMEM((inflight, *slab), f32),
                            pltpu.SemaphoreType.DMA((4, inflight))]),
        out_shape=[jax.ShapeDtypeStruct((B, *slab), f32),
                   # in HBM by name, and with it the operand aliased to it:
                   # not staged through the faster memory around the call
                   pltpu.HBM(pool.shape, pool.dtype)],
        # operand 3 (the pool; the scalar-prefetch operand counts) is
        # output 1: a step writes only its rows' seats
        input_output_aliases={3: 1},
        interpret=interpret,
        name=KERNEL_NAME,
    )(seats.astype(jnp.int32), x.reshape(B, *slab), taps, pool)
    return out.reshape(B, C), pool
