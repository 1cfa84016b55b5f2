#!/usr/bin/env python
"""One (B, L) prefill against one packed flat-axis dispatch of the SAME
prompts, on the live backend: device time of the whole step and of the
attention kernel alone, per REAL prompt token.

The batches are what the closed-loop cells hand ``_run_prefill``: runs of
consecutive prompts of the benchmark pool's window order (pairs and
triples of unlike lengths, the chunk-route prompts left out), at the
widths of both configurations.  The trunk is cut to ``--layers`` layers
(every layer costs the same; the cut keeps compiles short) and the result
is one JSON line per batch plus one per width for the chunk route's
window kernel, so the three prefill attention kernels can be compared per
real token.  Refuses a machine without a TPU unless ``--cpu`` (a rehearsal
at tiny widths: its times mean nothing).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import traffic
    from tpuserve.models import transformer
    from tpuserve.models.config import get_model_config
    from tpuserve.models.weights import init_params
    from tpuserve.ops.attention import PAD_SLOT
    from tpuserve.ops.pallas_chunked_prefill import paged_window_attention
    from tpuserve.ops.pallas_flash_attention import flash_prefill_attention
    from tpuserve.ops.pallas_ragged_attention import (ragged_block,
                                                      ragged_paged_attention)
    from tpuserve.runtime.kv_cache import CacheConfig, create_kv_cache
    from tpuserve.runtime.scheduler import packed_prefill_bucket
    from tpuserve.utils import compile_cache, next_power_of_2
    compile_cache.configure()

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu:
        print("no TPU: this probe measures on the chip only", file=sys.stderr)
        return 1
    attn = "pallas" if on_tpu else "reference"
    blk = ragged_block()
    page = 32 if on_tpu else 8
    mix = traffic.load_mix(os.path.join(ROOT, "benchmark", "traffic",
                                        "batch-closed.json"))
    order = [p for p, _ in traffic.sizes_for(mix, 1, "window", mix["pool"])]
    if not on_tpu:
        order = [max(4, p // 32) for p in order]
    chunk = 2048 if on_tpu else 64
    short = [p for p in order if p <= chunk]
    # consecutive prompts of the window order, as admission takes them
    batches = [short[0:1], short[1:3], short[3:5], short[5:7],
               short[7:10], short[10:13], short[13:16], short[16:24]]
    models = (["Qwen/Qwen3-0.6B", "mistralai/Mistral-7B-Instruct-v0.1"]
              if on_tpu else ["tiny-qwen3"])

    def timed(fn, n):
        jax.block_until_ready(fn())          # compile + settle
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    for model in models:
        cfg = dataclasses.replace(get_model_config(model),
                                  num_layers=args.layers)
        params = init_params(cfg, seed=0)
        mb = 128 if on_tpu else 16
        ccfg = CacheConfig(block_size=page, num_blocks=8 * mb + 8,
                           max_blocks_per_seq=mb)
        state = {"kv": create_kv_cache(cfg, ccfg)}
        Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        scale = cfg.attn_scale
        rng = np.random.default_rng(0)

        def tables(lens):
            """Sequence i owns blocks [1 + i*mb, 1 + (i+1)*mb)."""
            bt = np.zeros((8, mb), np.int32)
            for i in range(len(lens)):
                bt[i] = 1 + i * mb + np.arange(mb)
            return bt

        def slots(bt_row, n):
            t = np.arange(n)
            return (bt_row[t // page] * page + t % page).astype(np.int32)

        for lens in batches:
            n_real = int(sum(lens))
            ids = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
                   for n in lens]
            bt = tables(lens)
            # ---- (B, L): power-of-two batch x power-of-two length ----
            B = next_power_of_2(len(lens))
            L = max(next_power_of_2(max(lens)), 32)
            tok = np.zeros((B, L), np.int32)
            sl = np.full((B, L), PAD_SLOT, np.int32)
            pl_ = np.ones((B,), np.int32)
            for i, x in enumerate(ids):
                tok[i, :len(x)] = x
                sl[i, :len(x)] = slots(bt[i], len(x))
                pl_[i] = len(x)
            a_tok, a_pl, a_sl = map(jnp.asarray, (tok, pl_, sl))

            def run_bl():
                logits, state["kv"] = transformer.prefill(
                    params, cfg, a_tok, a_pl, a_sl, state["kv"],
                    attn_impl=attn)
                return logits
            ms_bl = timed(run_bl, args.iters)
            logits_bl = np.asarray(run_bl(), np.float32)[:len(lens)]
            # ---- packed: one flat axis, each prompt block-aligned ----
            starts, cursor = [], 0
            for n in lens:
                starts.append(cursor)
                cursor += -(-n // blk) * blk
            T = packed_prefill_bucket(cursor, blk)
            f_tok = np.zeros((T,), np.int32)
            f_pos = np.zeros((T,), np.int32)
            f_sl = np.full((T,), PAD_SLOT, np.int32)
            f_seq = np.zeros((T,), np.int32)
            kv_lens = np.zeros((8,), np.int32)
            q_starts = np.full((8,), T, np.int32)
            q_lens = np.zeros((8,), np.int32)
            last = np.zeros((8,), np.int32)
            bseq = np.full((T // blk,), -1, np.int32)
            for i, (x, s) in enumerate(zip(ids, starts)):
                n = len(x)
                f_tok[s:s + n] = x
                f_pos[s:s + n] = np.arange(n)
                f_sl[s:s + n] = slots(bt[i], n)
                f_seq[s:s + n] = i
                kv_lens[i], q_starts[i], q_lens[i] = n, s, n
                last[i] = s + n - 1
                bseq[s // blk:(s + -(-n // blk) * blk) // blk] = i
            meta = np.zeros((2,), np.int32)
            packed = list(map(jnp.asarray, (
                f_tok, f_pos, f_sl, f_seq, bt, kv_lens, q_starts, q_lens,
                meta, bseq, last)))

            def run_packed():
                logits, state["kv"] = transformer.forward_ragged(
                    params, cfg, *packed, state["kv"], ragged_blk=blk,
                    attn_impl=attn, decode_rows=False)
                return logits
            ms_pk = timed(run_packed, args.iters)
            logits_pk = np.asarray(run_packed(), np.float32)[:len(lens)]
            row = {"model": model, "layers": args.layers, "lens": lens,
                   "real_tokens": n_real, "BL": [B, L], "T": T,
                   "step_ms_BL": ms_bl, "step_ms_packed": ms_pk,
                   "step_us_per_real_tok_BL": 1e3 * ms_bl / n_real,
                   "step_us_per_real_tok_packed": 1e3 * ms_pk / n_real,
                   "logits_max_abs_diff": float(
                       np.abs(logits_bl - logits_pk).max()),
                   "argmax_equal": bool(
                       (logits_bl.argmax(-1) == logits_pk.argmax(-1)).all())}
            if on_tpu:
                # ---- the attention kernels alone, one layer's worth ----
                q4 = jnp.asarray(rng.standard_normal((B, L, Hq, D)),
                                 jnp.bfloat16)
                k4 = jnp.asarray(rng.standard_normal((B, L, Hkv, D)),
                                 jnp.bfloat16)
                kc = state["kv"][0]["k"]
                vc = state["kv"][0]["v"]
                qf = jnp.asarray(rng.standard_normal((T, Hq, D)),
                                 jnp.bfloat16)
                sw = cfg.layer_window(0)
                ms_flash = timed(lambda: flash_prefill_attention(
                    q4, k4, k4, a_pl, scale, sliding_window=sw), 20)
                ms_ragged = timed(lambda: ragged_paged_attention(
                    qf, kc, vc, *packed[4:8], packed[8], packed[9], scale,
                    blk_q=blk, sliding_window=sw, decode_rows=False), 20)
                row.update(attn_us_per_real_tok_flash=1e3 * ms_flash / n_real,
                           attn_us_per_real_tok_ragged=1e3 * ms_ragged
                           / n_real, attn_ms_flash=ms_flash,
                           attn_ms_ragged=ms_ragged)
            print(json.dumps(row), flush=True)
        if on_tpu:
            # ---- the chunk route: a 3,072-token prompt as 2,048 + 1,024
            # through the window kernel, against the same prompt as one
            # packed sequence through the ragged kernel
            kc, vc = state["kv"][0]["k"], state["kv"][0]["v"]
            bt1 = jnp.asarray(tables([3072])[:1])
            sw = cfg.layer_window(0)
            ms_win = 0.0
            for ctx, c in ((0, 2048), (2048, 1024)):
                qw = jnp.asarray(rng.standard_normal((1, c, Hq, D)),
                                 jnp.bfloat16)
                ms_win += timed(lambda: paged_window_attention(
                    qw, kc, vc, bt1, jnp.asarray([ctx], jnp.int32),
                    jnp.asarray([c], jnp.int32), scale,
                    sliding_window=sw), 20)
            T = 3072
            kvl = np.zeros((8,), np.int32); kvl[0] = T
            qs = np.full((8,), T, np.int32); qs[0] = 0
            ql = np.zeros((8,), np.int32); ql[0] = T
            qf = jnp.asarray(rng.standard_normal((T, Hq, D)), jnp.bfloat16)
            ms_rag = timed(lambda: ragged_paged_attention(
                qf, kc, vc, jnp.asarray(tables([T])), jnp.asarray(kvl),
                jnp.asarray(qs), jnp.asarray(ql),
                jnp.zeros((2,), jnp.int32),
                jnp.zeros((T // blk,), jnp.int32), scale, blk_q=blk,
                sliding_window=sw, decode_rows=False), 20)
            q4 = jnp.asarray(rng.standard_normal((1, 4096, Hq, D)),
                             jnp.bfloat16)
            k4 = jnp.asarray(rng.standard_normal((1, 4096, Hkv, D)),
                             jnp.bfloat16)
            ms_fl = timed(lambda: flash_prefill_attention(
                q4, k4, k4, jnp.asarray([T], jnp.int32), scale,
                sliding_window=sw), 20)
            print(json.dumps({
                "model": model, "prompt": T,
                "attn_us_per_real_tok_window_2048_1024": 1e3 * ms_win / T,
                "attn_us_per_real_tok_ragged_T3072": 1e3 * ms_rag / T,
                "attn_us_per_real_tok_flash_1x4096": 1e3 * ms_fl / T}),
                flush=True)
        del params, state
    return 0


if __name__ == "__main__":
    sys.exit(main())
