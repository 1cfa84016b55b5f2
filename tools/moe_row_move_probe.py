#!/usr/bin/env python
"""An expert layer's rows into expert order and back, several ways, on the
live backend: step 0 of PR 42 (PERF.md §6 has the reading).

At Mellum 2's sizes (rows of 2,304 bf16, 64 experts, 8 a token, ``T``
tokens so ``8 T`` routed rows) and K-EXAONE's share (rows of 6,144, a
piece of ``held_piece_rows`` rows for 16 of 128 experts held), one layer's
moves, each timed ``--reps`` times inside one program with the result fed
back as the next source behind an optimization barrier (without it XLA
gathers only the rows the feedback takes):

``xla_fwd``     ``(tiles, 128)`` slices gathered by ``order // k`` (each
                token's row ``k`` times, in expert order): the only form
                the trunk had before PR 42;
``xla_plain``   ``x[idx]`` as written (refused by the compiler at one rung
                of the ladder, inside the layer);
``xla_back`` / ``xla_back_plain``  the two forms by ``back``, a
                permutation of ``T k`` rows;
``xla_inverse`` ``zeros.at[order].set(arange)``, which builds ``back``;
``trunk_fwd`` / ``trunk_back``  ``transformer._gather_rows`` as it stands
                (it picks a form from the shapes);
``dma_fwd`` / ``dma_back``  one DMA a row from scalar-prefetched indices,
                the mover this probe was written to try (below; NOT in the
                server: it lost).  Mosaic refuses a one-row slice of a
                tiled array ("must be aligned to tiling (8)": the MXU's
                layout pairs two rows a word, eight a tile), so the source
                is first packed by XLA into rows a copy can take whole,
                ``(R, slab, 128)`` uint32 with ``slab`` whole 8-sublane
                tiles (9 -> 16 at 2,304: 8 KB copied for 4.6), each row
                lands in a staged VMEM tile and the tile is turned into
                the MXU's layout by strided loads, shifts and a bitcast;
``dma_only``    the same call without the turn (descriptors and waits
                alone; its output is junk); ``pack``: the packing alone;
``slab{8,16,32}``  the stand-alone mover in the shape of
                ``ops/pallas_kv_write.py``: packed rows HBM to HBM, that
                many copies in flight (``slab16+unpack``: with XLA's pass
                back to ``(rows, W)`` bf16, which a layer would pay).

Prints one JSON line a (case, variant): ms a call, ns a gathered row, GB/s
of the gathered rows' bytes read and written, and whether the result is
``x[idx]`` bit for bit.  Refuses a machine without a TPU unless ``--cpu``
(a rehearsal at tiny sizes: its times mean nothing).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def dma_mover():
    """``(pack_rows, dma_gather_rows)``: the row mover that was tried."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    #: rows of an output tile, staged and turned together
    TM = 256
    _LANE = 128
    _SUBLANE = 8
    _PAIR = 16             # rows of one packed 16-bit tile
    _UNROLL = 8            # row copies issued a trip of the issuing loop

    def _slab(width: int) -> tuple[int, int]:
        """``(chunks, slab)``: 128-word chunks of a packed row, and the
        sublanes a row's copy takes."""
        chunks = width // (2 * _LANE)
        return chunks, -(-chunks // _SUBLANE) * _SUBLANE


    def pack_rows(x: jnp.ndarray) -> jnp.ndarray:
        """``(R, W)`` 16-bit rows as ``(R, slab, 128)`` uint32: word ``j`` is
        column ``j`` below column ``j + W/2``."""
        R, W = x.shape
        chunks, slab = _slab(W)
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        words = u[:, :W // 2] | (u[:, W // 2:] << 16)
        words = words.reshape(R, chunks, _LANE)
        return jnp.pad(words, ((0, 0), (0, slab - chunks), (0, 0)))


    def _kernel(idx_ref, src, out_ref, stage, sems, *, tiles: int, chunks: int,
                slab: int, turn: bool):
        i = pl.program_id(0)
        slot = i % 2

        def issue(tile, into):
            def rows(r8, carry):
                for r in range(_UNROLL):        # by hand: the lowering takes
                    r = r8 * _UNROLL + r        # unroll=1 or the whole loop
                    pltpu.make_async_copy(
                        src.at[idx_ref[tile * TM + r]],
                        stage.at[into, pl.ds(pl.multiple_of(r * slab, slab),
                                             slab)],
                        sems.at[into]).start()
                return carry
            jax.lax.fori_loop(0, TM // _UNROLL, rows, 0)

        @pl.when(i == 0)
        def _():
            issue(0, 0)

        @pl.when(i + 1 < tiles)
        def _():
            issue(i + 1, 1 - slot)

        # one wait for the tile's TM copies: the semaphore counts their bytes
        pltpu.make_async_copy(stage.at[slot], stage.at[slot],
                              sems.at[slot]).wait()
        if not turn:            # the probe's DMA-only reading
            return
        half = chunks * _LANE
        low = jnp.uint32(0xFFFF)
        high = jnp.uint32(0xFFFF0000)

        def group(g, carry):
            # 16 output rows: the even ones' words and the odd ones', a chunk
            # of 128 columns at a time
            base = pl.multiple_of(g * _PAIR * slab, _PAIR * slab)
            at = pl.ds(pl.multiple_of(g * _PAIR, _PAIR), _PAIR)
            for c in range(chunks):
                even = stage[slot, pl.ds(base + c, _SUBLANE, stride=2 * slab), :]
                odd = stage[slot, pl.ds(base + slab + c, _SUBLANE,
                                        stride=2 * slab), :]
                out_ref[at, pl.ds(c * _LANE, _LANE)] = pltpu.bitcast(
                    (even & low) | (odd << 16), out_ref.dtype)
                out_ref[at, pl.ds(half + c * _LANE, _LANE)] = pltpu.bitcast(
                    (even >> 16) | (odd & high), out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, TM // _PAIR, group, 0)


    @functools.partial(jax.jit, static_argnames=("interpret", "turn"))
    def dma_gather_rows(x, idx, *, interpret: bool, turn: bool = True):
        """``x[idx]`` by :func:`_kernel`: x (R, W) of a 16-bit dtype with ``W``
        a multiple of 256, idx (n,) int32 in ``[0, R)``."""
        n, (_, W) = idx.shape[0], x.shape
        chunks, slab = _slab(W)
        tiles = -(-n // TM)
        if tiles * TM != n:
            idx = jnp.pad(idx, (0, tiles * TM - n))
        out = pl.pallas_call(
            functools.partial(_kernel, tiles=tiles, chunks=chunks, slab=slab,
                              turn=turn),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(tiles,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((TM, W), lambda i, idx: (i, 0)),
                scratch_shapes=[pltpu.VMEM((2, TM * slab, _LANE), jnp.uint32),
                                pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((tiles * TM, W), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=32 << 20),
            interpret=interpret,
            name="_probe_row_move",
        )(idx, pack_rows(x))
        return out[:n] if tiles * TM != n else out

    return pack_rows, dma_gather_rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="*",
                    default=[64, 128, 256, 1024, 2048, 4096, 8192])
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--inflight", type=int, nargs="*", default=[8, 16, 32])
    ap.add_argument("--only", nargs="*", default=None,
                    help="variants to run (default: all)")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tpuserve.models.transformer import _gather_rows as trunk_gather
    from tpuserve.models.transformer import held_piece_rows

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu:
        print("no TPU: this probe measures on the chip only", file=sys.stderr)
        return 1
    K = 8
    tokens = args.tokens if on_tpu else [128, 160]
    # (name, width, source rows, experts the picks spread over, rows moved)
    cases = []
    for T in tokens:
        cases.append(("mellum2", 2304 if on_tpu else 256, T, 64, T * K))
    for T in tokens:
        cases.append(("k-exaone-held", 6144 if on_tpu else 512, T, 16,
                      held_piece_rows(T * K, 16, 128)))
    dtype = jnp.bfloat16
    pack_rows, dma_gather_rows = dma_mover()

    def xla_tiles(x, idx):              # the trunk's one form before PR 42
        R, W = x.shape
        return x.reshape(R, W // 128, 128)[idx].reshape(idx.shape[0], W)

    # ---- the stand-alone HBM-to-HBM mover of packed rows ----
    def slab_kernel(idx_ref, src, out, sems, *, n, inflight):
        def copy(i):
            return pltpu.make_async_copy(src.at[idx_ref[i]], out.at[i],
                                         sems.at[i % inflight])

        def issue(i, carry):
            @pl.when(i >= inflight)
            def _():
                copy(i - inflight).wait()
            copy(i).start()
            return carry

        def drain(i, carry):
            copy(i).wait()
            return carry
        jax.lax.fori_loop(0, n, issue, 0)
        jax.lax.fori_loop(n - inflight, n, drain, 0)

    def slab_move(packed, idx, inflight):
        n = idx.shape[0]
        inflight = min(inflight, n)
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            functools.partial(slab_kernel, n=n, inflight=inflight),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,), in_specs=[any_spec],
                out_specs=any_spec,
                scratch_shapes=[pltpu.SemaphoreType.DMA((inflight,))]),
            out_shape=jax.ShapeDtypeStruct((n,) + packed.shape[1:],
                                           packed.dtype),
            interpret=not on_tpu, name="_probe_slab_move")(idx, packed)

    def unpack(words, W):
        chunks = W // 256
        w = words[:, :chunks].reshape(words.shape[0], W // 2)
        both = jnp.concatenate([w & 0xFFFF, w >> 16], axis=1)
        return jax.lax.bitcast_convert_type(both.astype(jnp.uint16), dtype)

    def timed(step, x, n_iter):
        """``step(x) -> (rows moved, W)``; the first source rows of the
        result are the next call's source."""
        R = x.shape[0]

        @jax.jit
        def fn(x):
            # the barrier keeps the whole result: without it XLA gathers
            # only the rows the slice below takes (an eighth of them)
            return jax.lax.fori_loop(
                0, args.reps, lambda _, x: jax.lax.optimization_barrier(
                    step(x))[:R].astype(x.dtype), x)
        for _ in range(2):                      # compile + settle
            x = fn(x)
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        for _ in range(n_iter):
            x = fn(x)
        jax.block_until_ready(x)
        return (time.perf_counter() - t0) / (n_iter * args.reps)

    def bits(a):
        return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))

    rng = np.random.default_rng(0)
    for name, W, T, E, n in cases:
        picks = rng.integers(0, E, (T * K,))
        order = np.argsort(picks, kind="stable").astype(np.int32)
        back_np = np.zeros_like(order)
        back_np[order] = np.arange(order.shape[0], dtype=np.int32)
        fwd = jnp.asarray((order // K)[:n] if n <= T * K
                          else np.resize(order // K, n))
        back = jnp.asarray(back_np)
        order_j = jnp.asarray(order)
        x_fwd = jax.random.normal(jax.random.PRNGKey(1), (T, W), dtype)
        x_back = jax.random.normal(jax.random.PRNGKey(2), (T * K, W), dtype)

        def dma(idx, turn=True):
            return lambda x: dma_gather_rows(
                x, idx, interpret=not on_tpu, turn=turn)

        def slab_unpacked(x):
            return unpack(slab_move(pack_rows(x), fwd, 16), W)

        packed = pack_rows(x_fwd)     # the slab mover's own carry

        variants = [
            ("xla_fwd", x_fwd, fwd, lambda x: xla_tiles(x, fwd)),
            ("xla_plain", x_fwd, fwd, lambda x: x[fwd]),
            ("trunk_fwd", x_fwd, fwd, lambda x: trunk_gather(x, fwd)),
            ("dma_fwd", x_fwd, fwd, dma(fwd)),
            ("dma_only", x_fwd, None, dma(fwd, turn=False)),
            ("pack", x_fwd, None, lambda x: jax.lax.bitcast_convert_type(
                pack_rows(x).reshape(T, -1)[:, :W // 2],
                dtype).reshape(T, W)),
        ]
        for w in args.inflight:
            variants.append((f"slab{w}", packed, None,
                             lambda p, w=w: slab_move(p, fwd, w)))
        variants.append(("slab16+unpack", x_fwd, fwd, slab_unpacked))
        if name == "mellum2":
            variants += [
                ("xla_back", x_back, back, lambda x: xla_tiles(x, back)),
                ("xla_back_plain", x_back, back, lambda x: x[back]),
                ("trunk_back", x_back, back,
                 lambda x: trunk_gather(x, back)),
                ("dma_back", x_back, back, dma(back)),
                # a permutation's inverse is the next one's source
                ("xla_inverse", order_j, None,
                 lambda o: jnp.zeros_like(o).at[o].set(
                     jnp.arange(o.shape[0], dtype=o.dtype))),
            ]
        for label, x, idx, step in variants:
            if args.only and label not in args.only:
                continue
            rows = T * K if x is x_back or x is order_j else n
            line = {"case": name, "width": W, "tokens": T, "rows": rows,
                    "variant": label}
            try:
                same = None
                if idx is not None:
                    same = bool(np.array_equal(bits(jax.jit(step)(x)),
                                               bits(x[idx])))
                sec = timed(step, x, args.iters)
            except Exception as e:      # a variant the compiler refuses
                line["error"] = repr(e)[:300]
                print(json.dumps(line), flush=True)
                continue
            line.update({
                "ms": round(sec * 1e3, 4),
                "ns_per_row": round(sec * 1e9 / rows, 2),
                "GBps_read_and_written": round(
                    2 * rows * W * 2 / sec / 1e9, 1),
                "as_x_idx": same,
                "device": jax.devices()[0].device_kind})
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
