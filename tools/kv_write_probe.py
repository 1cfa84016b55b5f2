#!/usr/bin/env python
"""A prompt's K and V into the paged cache, three ways, on the live backend.

One layer's K and V pages at the benchmark configurations' KV widths
(Qwen3: 3,821 blocks of 32 tokens, 8 KV heads of 128; Mellum 2: 5,450
blocks, 4 KV heads), a packed prefill stream of ``T`` rows (prompts of the
batch pool's lengths, each starting on a 128-row boundary, blocks drawn at
random), the cache donated:

(a) ``rows``   today's ``write_kv_cache``: one XLA scatter index a token row;
(b) ``pages``  the same ``scatter`` at page granularity: the cache viewed
               ``(num_blocks, 32 x Hkv, D)``, one index a page;
(c) ``dma``    a Pallas writer: cache in ``pl.ANY`` aliased to the output,
               page ids scalar-prefetched, one HBM-to-HBM copy a page for K
               and one for V, ``--inflight`` of them in flight.  The one
               that was kept: ``ops/pallas_kv_write.py``, called here as
               the trunks call it.

(b) and (c) write whole pages, so a prompt's padding rows inside its last
page are zeroed first (``+zero``: that select is timed with the write; (c)
always does it).
Prints one JSON line a (width, T, variant): ms a call (K and V), ns a
(row, K-or-V), GB/s of the real rows' bytes read and written; and whether
(b) and (c) leave the cache bit for bit as (a) does.  Refuses a machine
without a TPU unless ``--cpu`` (a rehearsal at tiny sizes: its times mean
nothing).  PERF.md §6 (PR 39) has the reading this was written for.
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--inflight", type=int, nargs="*", default=[4, 8, 16])
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.ops import pallas_kv_write
    from tpuserve.ops.attention import PAD_SLOT, write_kv_cache

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu:
        print("no TPU: this probe measures on the chip only", file=sys.stderr)
        return 1
    bs = 32 if on_tpu else 4
    blk = 128 if on_tpu else 8
    D = 128
    widths = ([("qwen3", 3821, 8), ("mellum2", 5450, 4)] if on_tpu
              else [("tiny", 64, 2)])
    sizes = (1024, 4096, 8192) if on_tpu else (64,)
    dtype = jnp.bfloat16

    def stream(T, nb, rng):
        """Slots of a packed stream: prompts of the pool's spread, each on
        a ``blk`` boundary, the rest padding."""
        slots = np.full((T,), PAD_SLOT, np.int32)
        free = rng.permutation(nb)
        cursor = used = 0
        while cursor < T:
            n = int(np.clip(rng.lognormal(np.log(512), 0.8), 32, 3072))
            n = min(n if on_tpu else max(3, n // 64), T - cursor)
            blocks = free[used:used + -(-n // bs)]
            used += len(blocks)
            t = np.arange(n)
            slots[cursor:cursor + n] = blocks[t // bs] * bs + t % bs
            cursor += -(-n // blk) * blk
        return slots

    # ---- (a) one index a row ----
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def rows(kc, vc, k, v, slots):
        return write_kv_cache(kc, k, slots), write_kv_cache(vc, v, slots)

    def page_view(kc, k, slots, zero):
        nb, _, hkv, d = kc.shape
        if zero:
            k = jnp.where((slots != PAD_SLOT)[:, None, None], k, 0)
        return (kc.reshape(nb, bs * hkv, d), k.reshape(-1, bs * hkv, d),
                slots[::bs] // bs)

    # ---- (b) one index a page ----
    @functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(5,))
    def pages(kc, vc, k, v, slots, zero):
        out = []
        for c, x in ((kc, k), (vc, v)):
            flat, new, ids = page_view(c, x, slots, zero)
            out.append(flat.at[ids].set(new, mode="drop").reshape(c.shape))
        return tuple(out)

    # ---- (c) one HBM-to-HBM copy a page: the writer the trunks call
    # (ops/pallas_kv_write.py; it zeroes the padding rows itself), with
    # its pages in flight as this probe's knob (read when it is traced)
    def dma(inflight):
        def write(kc, vc):
            pallas_kv_write.INFLIGHT = inflight
            pallas_kv_write._paged_kv_write.clear_cache()
            return pallas_kv_write.paged_kv_write(kc, vc, k, v, slots)
        return write

    REPS = 16       # writes a dispatch, as a trunk's layers: the host's
                    # enqueue (~0.1 ms) must not be what is timed

    def timed(write, kc, vc, n):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def fn(kc, vc):
            return jax.lax.fori_loop(0, REPS, lambda _, c: write(*c),
                                     (kc, vc))
        for _ in range(2):                      # compile + settle
            kc, vc = fn(kc, vc)
        jax.block_until_ready((kc, vc))
        t0 = time.perf_counter()
        for _ in range(n):
            kc, vc = fn(kc, vc)
        jax.block_until_ready((kc, vc))
        return (time.perf_counter() - t0) / (n * REPS), kc, vc

    rng = np.random.default_rng(0)
    for name, nb, hkv in widths:
        for T in sizes:
            slots_np = stream(T, nb, rng)
            real = int((slots_np != PAD_SLOT).sum())
            slots = jnp.asarray(slots_np)
            k = jax.random.normal(jax.random.PRNGKey(1), (T, hkv, D), dtype)
            v = jax.random.normal(jax.random.PRNGKey(2), (T, hkv, D), dtype)
            variants = [("rows", lambda kc, vc: rows(kc, vc, k, v, slots)),
                        ("pages", lambda kc, vc: pages(kc, vc, k, v, slots,
                                                       False)),
                        ("pages+zero", lambda kc, vc: pages(kc, vc, k, v,
                                                            slots, True))]
            variants += [(f"dma{w}+zero", dma(w)) for w in args.inflight]
            want = None
            for label, fn in variants:
                kc = jnp.zeros((nb, bs, hkv, D), dtype)
                vc = jnp.zeros((nb, bs, hkv, D), dtype)
                try:
                    sec, kc, vc = timed(fn, kc, vc, args.iters)
                except Exception as e:      # a variant the compiler refuses
                    print(json.dumps({"widths": name, "T": T,
                                      "variant": label,
                                      "error": repr(e)[:300]}), flush=True)
                    continue
                got = tuple(np.asarray(jax.lax.bitcast_convert_type(
                    c, jnp.uint16)) for c in (kc, vc))
                if want is None:
                    want = got
                # from a zeroed cache the zeroed padding rows leave no
                # trace, so the whole cache is comparable; without the
                # select only the real rows' slots are
                if label.endswith("+zero") or label == "rows":
                    same = all(np.array_equal(a, b)
                               for a, b in zip(want, got))
                else:
                    live = slots_np[slots_np != PAD_SLOT]
                    same = all(np.array_equal(
                        a.reshape(nb * bs, hkv, D)[live],
                        b.reshape(nb * bs, hkv, D)[live])
                        for a, b in zip(want, got))
                row_bytes = hkv * D * jnp.dtype(dtype).itemsize
                print(json.dumps({
                    "widths": name, "kv_heads": hkv, "T": T,
                    "real_rows": real, "variant": label,
                    "ms": round(sec * 1e3, 4),
                    "ns_per_row": round(sec * 1e9 / (2 * T), 2),
                    "ns_per_real_row": round(sec * 1e9 / (2 * real), 2),
                    "GBps_read_and_written": round(
                        2 * 2 * real * row_bytes / sec / 1e9, 1),
                    "as_rows": bool(same),
                    "device": jax.devices()[0].device_kind}), flush=True)
                del kc, vc
    return 0


if __name__ == "__main__":
    sys.exit(main())
