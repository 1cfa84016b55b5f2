"""Chunked prefill: fixed-size chunks against the paged cache must produce
exactly what one-shot prefill produces."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.models.config import get_model_config
from tpuserve.ops import attention as attn_ops
from tpuserve.runtime.engine import Engine, EngineConfig
from tpuserve.runtime.kv_cache import CacheConfig
from tpuserve.runtime.request import SamplingParams
from tpuserve.runtime.scheduler import Scheduler, SchedulerConfig


def test_chunked_attention_matches_dense():
    rng = np.random.default_rng(0)
    B, T, Hq, Hkv, D = 2, 24, 4, 2, 8
    bs, nblocks = 4, 32
    scale = D ** -0.5
    q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    lens = jnp.asarray([T, T - 5], jnp.int32)
    want = attn_ops.prefill_attention(q, k, v, lens, scale)

    # write all K/V into a paged cache, then attend chunk by chunk
    k_cache = jnp.zeros((nblocks, bs, Hkv, D), jnp.float32)
    v_cache = jnp.zeros((nblocks, bs, Hkv, D), jnp.float32)
    max_blocks = T // bs
    bt = np.stack([np.arange(max_blocks), max_blocks + np.arange(max_blocks)])
    slots = (bt[..., None] * bs + np.arange(bs)).reshape(B, T)
    k_cache = attn_ops.write_kv_cache(k_cache, k, jnp.asarray(slots))
    v_cache = attn_ops.write_kv_cache(v_cache, v, jnp.asarray(slots))

    C = 8
    for start in range(0, T, C):
        ctx = jnp.asarray([start, start], jnp.int32)
        chunk_lens = jnp.clip(lens - start, 0, C)
        got = attn_ops.chunked_prefill_attention(
            q[:, start:start + C], k_cache, v_cache, jnp.asarray(bt),
            ctx, chunk_lens, scale)
        for b in range(B):
            n = int(chunk_lens[b])
            np.testing.assert_allclose(
                np.asarray(got[b, :n]), np.asarray(want[b, start:start + n]),
                rtol=2e-5, atol=2e-5, err_msg=f"chunk@{start} b={b}")


def _engine(chunk_size, model_cfg):
    return Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=128,
                                       max_blocks_per_seq=24),
                     scheduler=SchedulerConfig(max_num_seqs=4,
                                               prefill_chunk_size=chunk_size),
                     enable_prefix_caching=False),
        model_cfg=model_cfg)


@pytest.fixture(scope="module")
def fp32_cfg():
    return dataclasses.replace(get_model_config("tiny-qwen3"),
                               dtype="float32")


def test_chunked_equals_oneshot_generation(fp32_cfg):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in (20, 33, 7)]
    params = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    ref = _engine(4096, fp32_cfg).generate(prompts, params)
    chunked = _engine(8, fp32_cfg).generate(prompts, params)
    for r, c in zip(ref, chunked):
        assert r.output_token_ids == c.output_token_ids
    # the 7-token prompt stays on the one-shot path even with chunking on
    assert chunked[2].num_prefilled == 0
    # the long prompts actually went through the chunked path
    assert chunked[0].num_prefilled == 20 and chunked[1].num_prefilled == 33


def test_chunk_scheduling_counts(fp32_cfg):
    eng = _engine(8, fp32_cfg)
    rng = np.random.default_rng(2)
    eng.add_request(prompt_token_ids=rng.integers(1, 200, size=20).tolist(),
                    params=SamplingParams(max_tokens=2, temperature=0.0,
                                          ignore_eos=True))
    # 20 tokens at chunk 8 -> 3 chunk steps, first token on the last
    outs = eng.step()
    assert outs == [] and eng.stats.num_prefill_steps == 1
    outs = eng.step()
    assert outs == [] and eng.stats.num_prefill_steps == 2
    outs = eng.step()
    assert len(outs) == 1 and outs[0].new_token_ids
    assert eng.stats.ttft_count == 1
    while eng.has_work():
        eng.step()
    assert eng.block_manager.num_seqs() == 0


def test_chunked_request_abort_frees_blocks(fp32_cfg):
    eng = _engine(8, fp32_cfg)
    free0 = eng.block_manager.num_free_blocks
    rid = eng.add_request(
        prompt_token_ids=list(range(1, 21)),
        params=SamplingParams(max_tokens=2, ignore_eos=True))
    eng.step()                      # first chunk: blocks allocated
    assert eng.block_manager.num_free_blocks < free0
    assert eng.abort_request(rid)
    assert eng.block_manager.num_free_blocks == free0
    assert not eng.has_work()


def test_abort_mid_chunk_publishes_no_garbage_prefix(fp32_cfg):
    """Blocks of never-written chunks must not enter the prefix cache."""
    eng = Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=128,
                                       max_blocks_per_seq=24),
                     scheduler=SchedulerConfig(max_num_seqs=4,
                                               prefill_chunk_size=8),
                     enable_prefix_caching=True),
        model_cfg=fp32_cfg)
    prompt = list(range(1, 21))
    rid = eng.add_request(prompt_token_ids=prompt,
                          params=SamplingParams(max_tokens=2,
                                                ignore_eos=True))
    eng.step()                       # chunk 1 of 3 written
    assert eng.abort_request(rid)
    shared, cached = eng.block_manager.lookup_prefix(prompt)
    assert cached == 0, "aborted partial prefill leaked cached prefix blocks"


def test_mid_chunk_request_resumes_from_any_queue_position(fp32_cfg):
    """A preemption victim appendlefted ahead of a mid-chunk request must not
    starve it (the livelock found in review)."""
    eng = _engine(8, fp32_cfg)
    long_prompt = list(range(1, 21))
    eng.add_request(prompt_token_ids=long_prompt,
                    params=SamplingParams(max_tokens=2, ignore_eos=True))
    eng.step()                       # chunk 1: long req mid-chunk, in waiting
    # simulate a preemption victim landing at the head of the queue
    from tpuserve.runtime.request import Request, RequestState
    victim = Request(request_id="victim", prompt_token_ids=[1, 2, 3],
                     params=SamplingParams(max_tokens=2, ignore_eos=True))
    victim.state = RequestState.PREEMPTED
    eng.requests["victim"] = victim
    eng._detok["victim"] = eng._detok[next(iter(eng._detok))].__class__(
        eng.tokenizer)
    eng.scheduler.waiting.appendleft(victim)
    batch = eng.scheduler.schedule()
    assert batch.kind == "prefill_chunk"
    assert batch.requests[0].num_prefilled > 0     # the mid-chunk req won
    eng.scheduler.waiting.appendleft(batch.requests[0])
    while eng.has_work():
        eng.step()
    assert eng.block_manager.num_seqs() == 0


def test_long_prompt_behind_short_head_still_chunks(fp32_cfg):
    """A long prompt queued behind a short one must go through the chunked
    path, not get batched into a giant one-shot prefill bucket."""
    eng = _engine(8, fp32_cfg)
    p = SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True)
    eng.add_request(prompt_token_ids=[1, 2, 3], params=p)          # short head
    eng.add_request(prompt_token_ids=list(range(1, 21)), params=p) # long, 20 > 8
    batch = eng.scheduler.schedule()
    assert batch.kind == "prefill"
    assert len(batch.requests) == 1          # the long one was NOT batched in
    eng.scheduler.waiting.appendleft(batch.requests[0])
    while eng.has_work():
        eng.step()
    long_req = [r for r in eng.requests.values()
                if len(r.prompt_token_ids) == 20][0]
    assert long_req.num_prefilled == 20      # chunked path was used


def test_prefix_cache_compute_skip(fp32_cfg):
    """A repeated prompt reuses cached KV: one chunk step computes only the
    uncached tail, and outputs are identical to a cold run."""
    eng = Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=128,
                                       max_blocks_per_seq=24),
                     scheduler=SchedulerConfig(max_num_seqs=4,
                                               prefill_chunk_size=64),
                     enable_prefix_caching=True),
        model_cfg=fp32_cfg)
    prompt = list(range(1, 23))      # 22 tokens = 5 full blocks + tail
    p = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    cold = eng.generate([prompt], p)[0].output_token_ids
    steps_before = eng.stats.num_prefill_steps
    hits_before = eng.block_manager.prefix_hits
    warm = eng.generate([prompt], p)[0].output_token_ids
    assert warm == cold
    assert eng.block_manager.prefix_hits == hits_before + 1
    # warm run: exactly one chunk step over the uncached tail
    assert eng.stats.num_prefill_steps == steps_before + 1
    assert eng.block_manager.num_seqs() == 0


def test_preempted_request_reprefills_from_cache(fp32_cfg):
    """After preemption, the re-prefill hits the request's own freed hashed
    blocks and skips recomputing them (recompute-with-cache)."""
    eng = Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=12,
                                       max_blocks_per_seq=10),
                     scheduler=SchedulerConfig(max_num_seqs=3),
                     enable_prefix_caching=True),
        model_cfg=fp32_cfg)
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    outs = eng.generate([[1, 2, 3, 4, 5, 6, 7, 8],
                         [9, 8, 7, 6, 5],
                         [4, 4, 4]], p)
    for r in outs:
        assert len(r.output_token_ids) == 10
    assert eng.block_manager.num_seqs() == 0


def test_a_chunked_prompt_by_page_serves_the_row_scatters_tokens(
        fp32_cfg, row_scatter_only):
    """A prompt of two chunks (the second ends mid-page) and eight decode
    steps, Pallas kernels on: greedy tokens with the chunks' K and V
    written a page at a time and with the row scatter are the same."""
    prompt = np.random.default_rng(5).integers(1, 200, size=27).tolist()
    params = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)
    runs = []
    for by_page in (True, False):
        if not by_page:
            row_scatter_only()
        eng = Engine(
            EngineConfig(model="tiny-qwen3", attn_impl="pallas",
                         cache=CacheConfig(block_size=4, num_blocks=128,
                                           max_blocks_per_seq=24,
                                           dtype="float32"),
                         scheduler=SchedulerConfig(max_num_seqs=4,
                                                   prefill_chunk_size=16),
                         enable_prefix_caching=False),
            model_cfg=fp32_cfg)
        out = eng.generate([prompt], params)[0]
        assert eng.stats.num_prefill_steps == 2             # 16 + 11
        assert eng.stats.prefill_tokens_total == 27
        assert eng.stats.prefill_kv_tokens_paged_total == 27 * by_page
        runs.append(out.output_token_ids)
    assert len(runs[0]) == 9 and runs[0] == runs[1]
