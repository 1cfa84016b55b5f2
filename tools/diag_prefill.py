#!/usr/bin/env python
"""Decompose prefill time on the live backend: where does TTFT go?

Times, at the headline shapes (Qwen3-0.6B, batch 64 x 128 tokens):
  full       — transformer.prefill exactly as the engine dispatches it
  attn       — the prefill attention kernel alone, run num_layers times
  kv_writes  — the paged-KV scatter alone (2 x num_layers scatters of
               B*T rows), the suspect if XLA lowers it poorly
  sample     — greedy sample_tokens on (B, vocab) logits
  rtt        — a 4-byte device round-trip

Each is run 3x after a warmup execution; the median is reported.
Caveat: the standalone ops are separate dispatches — inside the fused
prefill they overlap/fuse, so the parts can sum past the whole
(unattributed_ms < 0 means fusion is winning, not measurement error).
One JSON line, so the TTFT budget (BASELINE p50 <= 150 ms) gets an
attribution, not just a total.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _median3(fn):
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return sorted(times)[1]


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserve.models import transformer
    from tpuserve.models.config import get_model_config
    from tpuserve.models.weights import load_or_init
    from tpuserve.ops import sampling as sampling_ops
    from tpuserve.ops.attention import PAD_SLOT
    from tpuserve.runtime.kv_cache import CacheConfig, create_kv_cache
    from tpuserve.utils import compile_cache
    compile_cache.configure()

    backend = jax.default_backend()
    if backend == "tpu":
        model, B, T = "qwen3-0.6b", 64, 128
        attn_impl = "pallas"
    else:
        model, B, T = "tiny-qwen3", 8, 16
        attn_impl = "reference"
    cfg = get_model_config(model)
    params = load_or_init(cfg, None, 0)
    block = 32
    cache_cfg = CacheConfig(block_size=block, num_blocks=B * (T // block + 2),
                            max_blocks_per_seq=T // block + 2)
    kv = create_kv_cache(cfg, cache_cfg)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(B, T)),
                         jnp.int32)
    lens = jnp.full((B,), T, jnp.int32)
    slots = jnp.asarray(
        np.arange(B * T, dtype=np.int32).reshape(B, T))

    out = {"metric": "prefill_decomposition", "backend": backend,
           "model": cfg.name, "batch": B, "prompt_len": T}

    # rtt floor
    one = jnp.zeros((), jnp.int32) + 1
    jax.device_get(one)
    out["rtt_ms"] = round(1000 * _median3(lambda: jax.device_get(one + 1)), 2)

    # full prefill — the cache is DONATED through each call, so chain the
    # returned tree into the next run exactly like the engine does
    state = {"kv": kv, "logits": None}

    def run_full():
        state["logits"], state["kv"] = transformer.prefill(
            params, cfg, tokens, lens, slots, state["kv"],
            attn_impl=attn_impl)
        jax.block_until_ready(state["logits"])
    run_full()                                   # compile
    out["full_ms"] = round(1000 * _median3(run_full), 1)

    # sample on (B, V)
    logits = state["logits"]
    keys = jnp.zeros((B, 2), jnp.uint32)
    temp = jnp.zeros((B,), jnp.float32)
    tk = jnp.zeros((B,), jnp.int32)
    tp = jnp.ones((B,), jnp.float32)

    def run_sample():
        toks = sampling_ops.sample_tokens(logits, keys, temp, tk, tp,
                                          mode="greedy")
        jax.device_get(toks)
    run_sample()
    out["sample_ms"] = round(1000 * _median3(run_sample), 2)

    # attention alone, summed over layers: one layer's shapes x num_layers
    q = jnp.asarray(rng.standard_normal(
        (B, T, cfg.num_heads, cfg.head_dim)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal(
        (B, T, cfg.num_kv_heads, cfg.head_dim)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(
        (B, T, cfg.num_kv_heads, cfg.head_dim)), jnp.bfloat16)
    scale = cfg.head_dim ** -0.5
    if attn_impl == "pallas":
        from tpuserve.ops.pallas_flash_attention import flash_prefill_attention
        attn = lambda: flash_prefill_attention(q, k, v, lens, scale)
    else:
        from tpuserve.ops import attention as attn_ops
        attn = lambda: attn_ops.prefill_attention(q, k, v, lens, scale)

    def run_attn():
        o = None
        for _ in range(cfg.num_layers):
            o = attn()
        jax.block_until_ready(o)
    run_attn()
    out["attn_all_layers_ms"] = round(1000 * _median3(run_attn), 1)

    # KV scatter writes alone: 2 scatters x num_layers at one layer's
    # shape — chained through the donated buffer like the trunk does
    from tpuserve.ops.attention import write_kv_cache
    wstate = {"ck": state["kv"][0]["k"]}

    def run_writes():
        ck = wstate["ck"]
        for _ in range(cfg.num_layers):
            ck = write_kv_cache(ck, k, slots)
            ck = write_kv_cache(ck, v, slots)
        jax.block_until_ready(ck)
        wstate["ck"] = ck
    run_writes()
    out["kv_writes_all_layers_ms"] = round(1000 * _median3(run_writes), 1)

    out["unattributed_ms"] = round(
        out["full_ms"] - out["attn_all_layers_ms"]
        - out["kv_writes_all_layers_ms"], 1)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
