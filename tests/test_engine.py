"""Engine end-to-end on CPU: continuous batching, stops, preemption,
prefix caching, determinism."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.runtime import (
    CacheConfig, Engine, EngineConfig, FinishReason, SamplingParams,
    SchedulerConfig)


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=8, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2),
    )
    return Engine(cfg)


def test_generate_batch(engine):
    # ignore_eos: random weights may well argmax the eos id (test_eos_stops
    # covers that); this test is about the batch running to its length
    reqs = engine.generate(["Hello world", "The quick brown fox", "a"],
                           SamplingParams(max_tokens=8, temperature=0.0,
                                          ignore_eos=True))
    assert len(reqs) == 3
    for r in reqs:
        assert len(r.output_token_ids) == 8
        assert r.finish_reason == FinishReason.LENGTH
        assert r.first_token_time is not None


def test_greedy_deterministic_across_batsizes(engine):
    a = engine.generate(["Hello world"], SamplingParams(max_tokens=6, temperature=0.0))[0]
    b = engine.generate(["Hello world", "zzz"], SamplingParams(max_tokens=6, temperature=0.0))[0]
    assert a.output_token_ids == b.output_token_ids


def test_sampled_modes(engine):
    # ignore_eos + fixed seeds: the sampled stream may legitimately hit the
    # eos id, and unseeded requests derive keys from process-randomized
    # hash() — this test checks mode plumbing, not termination.
    reqs = engine.generate(
        ["abc", "def"],
        [SamplingParams(max_tokens=4, temperature=0.7, seed=7,
                        ignore_eos=True),
         SamplingParams(max_tokens=4, temperature=0.9, top_k=20, top_p=0.9,
                        seed=9, ignore_eos=True)])
    for r in reqs:
        assert len(r.output_token_ids) == 4
        assert all(0 <= t < 512 for t in r.output_token_ids)


def test_eos_stops(engine):
    # tiny-qwen3 eos_token_id = 1; force it by making every token eos
    reqs = engine.generate(["q"], SamplingParams(max_tokens=50, temperature=0.0))
    r = reqs[0]
    # either hits eos naturally or max_tokens; both must terminate cleanly
    assert r.finished or r.finish_reason is not None


def test_ignore_eos_runs_to_length(engine):
    r = engine.generate(["q"], SamplingParams(max_tokens=5, temperature=0.0,
                                              ignore_eos=True))[0]
    assert len(r.output_token_ids) == 5


def test_empty_prompt_rejected(engine):
    with pytest.raises(ValueError):
        engine.add_request(prompt_token_ids=[])


def test_too_long_prompt_rejected(engine):
    with pytest.raises(ValueError):
        engine.add_request(prompt_token_ids=list(range(10000)))


def test_abort(engine):
    rid = engine.add_request(prompt="hello", params=SamplingParams(max_tokens=4))
    assert engine.abort_request(rid)
    assert not engine.abort_request(rid)           # already gone
    assert not engine.has_work()
    engine.requests.pop(rid, None)


def test_prefix_cache_reuses_blocks(engine):
    prompt = list(range(10, 26))                    # 16 tokens = 4 full blocks
    engine.generate([prompt], SamplingParams(max_tokens=2, temperature=0.0))
    q_before = engine.block_manager.prefix_hits
    engine.generate([prompt], SamplingParams(max_tokens=2, temperature=0.0))
    assert engine.block_manager.prefix_hits > q_before


def test_preemption_under_tiny_cache():
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=10, max_blocks_per_seq=8),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=64,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        enable_prefix_caching=False,
    )
    eng = Engine(cfg)
    reqs = eng.generate([[1, 2, 3, 4, 5, 6, 7]] * 3,
                        SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True))
    for r in reqs:
        assert len(r.output_token_ids) == 12
    # cache pressure should have forced at least one preemption
    assert eng.stats.preemptions >= 1
    assert eng.block_manager.num_seqs() == 0       # everything freed


def test_stop_string():
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8),
    )
    eng = Engine(cfg)
    # ByteTokenizer decodes ids 3..258 as bytes; force a stop after any text
    r = eng.generate(["hi"], SamplingParams(max_tokens=30, temperature=0.0,
                                            ignore_eos=True, stop=("",)))[0]
    # empty stop string matches immediately after first token
    assert len(r.output_token_ids) == 1
    assert r.finish_reason == FinishReason.STOP


def test_warmup_compiles(engine):
    engine.warmup(prefill_buckets=[8], decode_buckets=[2])


@pytest.mark.parametrize("mixed", [False, True],
                         ids=["phase_split", "mixed"])
def test_warmup_blocks_on_every_queued_chain(mixed, monkeypatch):
    """Warmup must not return with device work still queued, or the first
    real request pays for the backlog as TTFT.  Every chain it queued has
    to be blocked on: the KV cache (all model executables donate it
    through) AND each sampler / token-select output, which never touch the
    cache and so sit on chains of their own."""
    import jax

    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=8, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2,
                                  mixed_batching=mixed,
                                  mixed_token_budget=32)))
    queued = []
    sample = eng._exec_sample
    monkeypatch.setattr(
        eng, "_exec_sample",
        lambda *a, **k: (queued.append(sample(*a, **k)), queued[-1])[1])
    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (blocked.extend(jax.tree.leaves(x)), real(x))[1])
    eng.warmup(prefill_buckets=[8], decode_buckets=[2])
    blocked_ids = {id(x) for x in blocked}
    assert queued, "warmup queued no sampler chain: the test sees nothing"
    for leaf in jax.tree.leaves((eng.kv_cache, queued)):
        assert id(leaf) in blocked_ids, "warmup left a queued chain unblocked"
    assert eng._warm_tails == []


def test_generate_params_length_mismatch(engine):
    with pytest.raises(ValueError):
        engine.generate(["a", "b"], [SamplingParams(max_tokens=2)])


def test_penalties_and_seed_and_logprobs(engine):
    p = SamplingParams(max_tokens=6, temperature=0.8, seed=42,
                       repetition_penalty=1.3, presence_penalty=0.2,
                       logprobs=3, ignore_eos=True)
    a = engine.generate(["seeded"], p)[0]
    b = engine.generate(["seeded"], p)[0]
    # per-request seed => reproducible regardless of batch composition
    assert a.output_token_ids == b.output_token_ids
    assert len(a.logprobs) == 6
    assert all(len(e["top"]) == 3 for e in a.logprobs)
    assert all(e["logprob"] <= 0.0 for e in a.logprobs)


def test_prefill_batch_does_not_overcommit_blocks():
    """Admission must reserve blocks per picked request (regression for
    collective over-admission crashing allocate())."""
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=8, max_blocks_per_seq=8),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=128,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        enable_prefix_caching=False,
    )
    eng = Engine(cfg)
    # each needs 3+1 blocks; only 8 total -> must admit one at a time, not crash
    outs = eng.generate([[1] * 12, [2] * 12], SamplingParams(max_tokens=2, temperature=0.0))
    assert all(len(r.output_token_ids) == 2 for r in outs)


def test_stop_string_truncated_from_output():
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8),
    )
    eng = Engine(cfg)
    # Greedy from this prompt generates a deterministic id stream; find what
    # text it produces, then stop on a substring of it.
    free = eng.generate(["hi"], SamplingParams(max_tokens=10, temperature=0.0,
                                               ignore_eos=True))[0]
    if len(free.output_text) >= 2:
        stop_s = free.output_text[1]
        r = eng.generate(["hi"], SamplingParams(max_tokens=10, temperature=0.0,
                                                ignore_eos=True, stop=(stop_s,)))[0]
        assert stop_s not in r.output_text
        assert r.finish_reason == FinishReason.STOP


def test_logit_bias_forces_and_bans_tokens(engine):
    # +100 on one token makes greedy pick it every step; -100 bans it
    forced = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True,
                            logit_bias={7: 100.0})
    out = engine.generate(["bias me"], forced)[0]
    assert out.output_token_ids == [7] * 5

    base = engine.generate(["bias me"],
                           SamplingParams(max_tokens=5, temperature=0.0,
                                          ignore_eos=True))[0]
    banned = engine.generate(["bias me"],
                             SamplingParams(max_tokens=5, temperature=0.0,
                                            ignore_eos=True,
                                            logit_bias={
                                                base.output_token_ids[0]: -100.0}))[0]
    assert banned.output_token_ids[0] != base.output_token_ids[0]


def test_logit_bias_under_pipelined_windows():
    # bias batches are ineligible for fused windows (sampling is fused
    # in-window); the engine must fall back and still honor the bias
    from tpuserve.runtime import Engine, EngineConfig, CacheConfig
    eng = Engine(EngineConfig(
        model="tiny-qwen3", multi_step=4, pipeline_decode=True,
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16)))
    out = eng.generate(["x"], SamplingParams(max_tokens=6, temperature=0.0,
                                             ignore_eos=True,
                                             logit_bias={9: 100.0}))[0]
    assert out.output_token_ids == [9] * 6
    assert eng.block_manager.num_seqs() == 0


def test_min_tokens_suppresses_eos():
    """min_tokens masks EOS until the floor is reached: a model config
    whose greedy argmax IS an eos token must keep generating, and the
    windowed/pipelined engine must agree with the single-step one."""
    import dataclasses
    from tpuserve.models.config import get_model_config

    # pick a prompt whose greedy stream has a token first occurring
    # mid-stream (repetitive streams would stop the baseline too early)
    probe = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16)))
    prompt, eos = None, None
    for cand in ("m", "hello", "abc", "Zq9", "prompt!", "x y z"):
        ids = probe.generate([cand], SamplingParams(
            max_tokens=10, temperature=0.0,
            ignore_eos=True))[0].output_token_ids
        hit = [t for i, t in enumerate(ids)
               if 2 <= i <= 4 and t not in ids[:i]]
        if hit:
            prompt, eos = cand, hit[0]
            break
    assert prompt is not None, "no probe prompt yields a usable eos token"
    mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                             eos_token_id=eos)

    def run(**kw):
        eng = Engine(EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=64,
                              max_blocks_per_seq=16), **kw), model_cfg=mc)
        return eng.generate([prompt], SamplingParams(max_tokens=10,
                                                     temperature=0.0,
                                                     min_tokens=6))[0]

    # without min_tokens the stream stops at the eos (position 2)
    short = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64,
                          max_blocks_per_seq=16)), model_cfg=mc).generate(
        [prompt], SamplingParams(max_tokens=10, temperature=0.0))[0]
    assert short.finish_reason == FinishReason.STOP
    assert 3 <= len(short.output_token_ids) <= 5     # stopped at the eos

    plain = run()
    assert len(plain.output_token_ids) >= 6
    # the masked steps must not emit the eos token
    assert eos not in plain.output_token_ids[:5]

    piped = run(multi_step=4, pipeline_decode=True)
    assert piped.output_token_ids == plain.output_token_ids


def test_min_tokens_suppresses_stop_strings():
    """vLLM semantics: stop strings must not terminate the stream before
    min_tokens (text still streams)."""
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16))
    # empty stop string matches after every token — without suppression the
    # stream would stop at 1 token (see test_stop_string)
    r = Engine(cfg).generate(
        ["hi"], SamplingParams(max_tokens=12, temperature=0.0,
                               ignore_eos=True, stop=("",),
                               min_tokens=5))[0]
    assert len(r.output_token_ids) == 5
    assert r.finish_reason == FinishReason.STOP


def test_min_tokens_single_step_pipeline_gate():
    """The single-step pipelined path's mask-lift boundary runs one step
    stale; the gate must hold the sync path one step LONGER (slack=1) so
    the mask cannot lift early."""
    import dataclasses
    from tpuserve.models.config import get_model_config

    probe = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16)))
    ids = probe.generate(["abc"], SamplingParams(
        max_tokens=10, temperature=0.0, ignore_eos=True))[0].output_token_ids
    hit = [t for i, t in enumerate(ids) if 2 <= i <= 4 and t not in ids[:i]]
    if not hit:
        import pytest
        pytest.skip("greedy stream too repetitive for an eos probe")
    mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                             eos_token_id=hit[0])

    def run(pipe):
        eng = Engine(EngineConfig(
            model="tiny-qwen3", multi_step=1, pipeline_decode=pipe,
            cache=CacheConfig(block_size=4, num_blocks=64,
                              max_blocks_per_seq=16)), model_cfg=mc)
        return eng.generate(["abc"], SamplingParams(
            max_tokens=10, temperature=0.0, min_tokens=6))[0]

    piped, plain = run(True), run(False)
    assert piped.output_token_ids == plain.output_token_ids
    assert len(piped.output_token_ids) >= 6


def test_stop_token_ids():
    """vLLM stop_token_ids: listed ids finish the stream like EOS (token
    emitted, STOP reason), work under fused windows, respect min_tokens,
    and apply even with ignore_eos."""
    cfg = lambda **kw: EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16),
        **kw)
    base = Engine(cfg()).generate(
        ["stop here"], SamplingParams(max_tokens=10, temperature=0.0,
                                      ignore_eos=True))[0].output_token_ids
    stop_tok = base[3]

    r = Engine(cfg()).generate(
        ["stop here"], SamplingParams(max_tokens=10, temperature=0.0,
                                      ignore_eos=True,
                                      stop_token_ids=(stop_tok,)))[0]
    assert r.finish_reason == FinishReason.STOP
    assert r.output_token_ids[-1] == stop_tok
    assert len(r.output_token_ids) <= 4

    # same under pipelined fused windows
    rw = Engine(cfg(multi_step=4, pipeline_decode=True)).generate(
        ["stop here"], SamplingParams(max_tokens=10, temperature=0.0,
                                      ignore_eos=True,
                                      stop_token_ids=(stop_tok,)))[0]
    assert rw.output_token_ids == r.output_token_ids

    # min_tokens masks the stop id until the floor
    rm = Engine(cfg()).generate(
        ["stop here"], SamplingParams(max_tokens=10, temperature=0.0,
                                      ignore_eos=True, min_tokens=7,
                                      stop_token_ids=(stop_tok,)))[0]
    assert len(rm.output_token_ids) >= 7
    assert stop_tok not in rm.output_token_ids[:6]


def test_mixed_feature_batch_composes():
    """One batch mixing logit_bias, min_tokens, stop_token_ids, and a
    plain request: batch-level gates route everyone through the sync path
    and each request's feature must still apply independently."""
    eng = Engine(EngineConfig(
        model="tiny-qwen3", multi_step=4, pipeline_decode=True,
        cache=CacheConfig(block_size=4, num_blocks=96, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=8, min_prefill_bucket=8,
                                  min_decode_bucket=2)))
    base = eng.generate(["p0"], SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True))[0].output_token_ids
    stop_tok = base[3]
    outs = eng.generate(
        ["p0", "p0", "p2", "p3"],    # req 1 shares p0's stream -> stop_tok occurs
        [SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True,
                        logit_bias={11: 100.0}),
         SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True,
                        stop_token_ids=(stop_tok,)),
         SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True,
                        min_tokens=8),
         SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)])
    assert outs[0].output_token_ids == [11] * 8            # bias forces
    assert outs[1].output_token_ids[-1] == stop_tok        # stop id fires
    assert len(outs[1].output_token_ids) <= 4
    assert len(outs[2].output_token_ids) == 8              # floor reached
    # the plain request must be unaffected by its batchmates
    plain = eng.generate(["p3"], SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True))[0]
    assert outs[3].output_token_ids == plain.output_token_ids
    assert eng.block_manager.num_seqs() == 0


# ---------------------------------------------------------------------------
# int8 KV cache (CacheConfig dtype="int8"): quantize-on-write, dequantize
# in the attention reads — halves KV bandwidth on the bandwidth-bound
# decode path (VERDICT r3 weak #4)
# ---------------------------------------------------------------------------

def _int8_engine(attn_impl):
    return Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16,
                          dtype="int8"),
        scheduler=SchedulerConfig(max_num_seqs=8, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        attn_impl=attn_impl))


def test_int8_kv_reference_pallas_parity():
    """Both attention impls read the SAME quantized cache, so greedy
    streams must agree token for token (the dequantized values are
    bit-identical; only the attention arithmetic differs)."""
    prompts = ["Hello world", "The quick brown fox", "zq"]
    p = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    ref = _int8_engine("reference").generate(prompts, p)
    pal = _int8_engine("pallas").generate(prompts, p)
    for a, b in zip(ref, pal):
        assert a.output_token_ids == b.output_token_ids


def test_int8_kv_deterministic_and_close_to_fp(engine):
    """int8 KV generation is deterministic, and quantization noise leaves
    the greedy stream mostly unchanged vs the fp cache."""
    prompts = ["Hello world", "determinism check"]
    p = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    e = _int8_engine("reference")
    a = e.generate(prompts, p)
    b = e.generate(prompts, p)
    for x, y in zip(a, b):
        assert x.output_token_ids == y.output_token_ids
    fp = engine.generate(prompts, p)
    matches = sum(t1 == t2
                  for x, y in zip(a, fp)
                  for t1, t2 in zip(x.output_token_ids, y.output_token_ids))
    total = sum(len(x.output_token_ids) for x in a)
    assert matches / total >= 0.75, f"int8 KV diverged: {matches}/{total}"


def test_int8_kv_long_prompt_chunked():
    """Long prompts route through chunked prefill; the int8 window path
    must serve them (reference impl on CPU; the Pallas window kernel has
    its own interpret-mode parity test)."""
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=128,
                          max_blocks_per_seq=32, dtype="int8"),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  min_prefill_bucket=8, min_decode_bucket=2,
                                  prefill_chunk_size=16)))
    long_prompt = "x" * 50            # > chunk size -> chunked path
    out = eng.generate([long_prompt],
                       SamplingParams(max_tokens=6, temperature=0.0,
                                      ignore_eos=True))[0]
    assert len(out.output_token_ids) == 6


def test_auto_num_blocks(monkeypatch):
    """CacheConfig.num_blocks == 0 sizes the cache from device memory
    minus actual weight bytes (vLLM gpu_memory_utilization analog);
    int8-quantized weights buy a larger cache.  A small injected budget
    (TPUSERVE_HBM_BYTES) keeps both sides below the block cap so the
    quantized-vs-fp comparison actually discriminates."""
    # A budget small enough that BOTH sizes land below the scheduler-
    # addressable cap (32 x 17 blocks) — at the cap the quantized-vs-fp
    # comparison would be vacuous.
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(512 << 10))

    def mk(quant=None, share=1.0):
        return Engine(EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=0,
                              max_blocks_per_seq=16),
            scheduler=SchedulerConfig(max_num_seqs=32, min_prefill_bucket=8,
                                      min_decode_bucket=2),
            quantization=quant, hbm_share=share))
    eng = mk()
    n = eng.cache_cfg.num_blocks
    assert 16 <= n < 1 << 17
    assert eng.block_manager.num_blocks == n
    # the auto-sized engine actually serves
    out = eng.generate(["auto"], SamplingParams(max_tokens=4,
                                                temperature=0.0,
                                                ignore_eos=True))[0]
    assert len(out.output_token_ids) == 4
    # quantized weights leave strictly more room below the cap
    assert mk("int8").cache_cfg.num_blocks > n
    # an engine sharing the chip budgets proportionally less
    assert mk(share=0.5).cache_cfg.num_blocks < n


def test_int8_kv_composes_with_multistep_and_spec():
    """The TPU capture runs kv-int8 under fused multi-step windows (and
    spec4 may compose too): the scanned decode body must quantize-write and
    dequantize-read the int8 cache identically to single-step decode."""
    def mk(multi_step=None, spec=None):
        from tpuserve.runtime.spec import SpecConfig
        return Engine(EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, dtype="int8"),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=2),
            multi_step=multi_step, pipeline_decode=False,
            speculative=SpecConfig(num_draft_tokens=spec) if spec else None))
    prompts = [[1, 2, 3, 4] * 4, [9, 8, 7, 6, 5]]
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    base = mk().generate(prompts, p)
    multi = mk(multi_step=4).generate(prompts, p)
    spec = mk(spec=3).generate(prompts, p)
    for a, b, c in zip(base, multi, spec):
        assert a.output_token_ids == b.output_token_ids
        assert a.output_token_ids == c.output_token_ids


def test_auto_num_blocks_rejects_overcommitted_weights(monkeypatch):
    """Weights that don't fit the budget fail LOUDLY at boot, not as a
    mysterious 480-token max_seq_len with constant preemption."""
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(64 << 10))   # 64 KiB
    with pytest.raises(ValueError, match="exceed the memory budget"):
        Engine(EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=0,
                              max_blocks_per_seq=16),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=2)))


# ---------------------------------------------------------------------------
# No guessed device facts on the chip
# ---------------------------------------------------------------------------

def test_hbm_limit_is_not_guessed_on_a_tpu(engine, monkeypatch):
    """A TPU that reports no memory limit is an error, not 16 GiB; the
    explicit override stays the way to say it; off the TPU a small fixed
    budget stands in (CPU tests)."""
    import jax
    monkeypatch.delenv("TPUSERVE_HBM_BYTES", raising=False)
    assert engine._device_hbm_limit() == 1 << 30         # CPU: no stats
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPUSERVE_HBM_BYTES"):
        engine._device_hbm_limit()
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(3 << 30))
    assert engine._device_hbm_limit() == 3 << 30


def test_python_block_manager_on_a_tpu_is_logged_as_an_error(monkeypatch,
                                                            caplog):
    """impl="auto" that ends on the pure-Python manager on a TPU host is a
    broken deployment: error level, not a passing warning."""
    import jax

    import tpuserve.native as native
    from tpuserve.runtime.block_manager import (BlockManager,
                                                create_block_manager)
    monkeypatch.delenv("TPUSERVE_BLOCK_MANAGER", raising=False)
    monkeypatch.delenv("TPUSERVE_STRICT_BLOCKS", raising=False)
    monkeypatch.setattr(native, "native_available", lambda: False)
    with caplog.at_level("ERROR", "tpuserve.block_manager"):
        assert isinstance(create_block_manager(8, 4), BlockManager)
        assert not caplog.records                        # CPU: a choice
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert isinstance(create_block_manager(8, 4), BlockManager)
    assert [r.levelname for r in caplog.records] == ["ERROR"]
    with pytest.raises(RuntimeError, match="library unavailable"):
        create_block_manager(8, 4, impl="native")
