"""Multi-step (fused-window) decode: transformer.decode_multi +
Engine._run_decode_multi.

The windowed path must be token-for-token identical to the single-step
path: same greedy argmax, same seeded sampling streams (the per-row key
construction folds the step index the same way), same stop semantics
(tokens past EOS / max_tokens are dropped at emit).  Equivalence is
asserted engine-vs-engine with identical seeds (identical random weights
— float32 on CPU so logits match bitwise).
"""

import dataclasses

import pytest

from tpuserve.models.config import get_model_config
from tpuserve.runtime.engine import Engine, EngineConfig
from tpuserve.runtime.kv_cache import CacheConfig
from tpuserve.runtime.request import FinishReason, SamplingParams
from tpuserve.runtime.scheduler import SchedulerConfig


def _engine(multi_step=None, num_blocks=64, max_blocks_per_seq=16,
            **eng_kw):
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=num_blocks,
                          max_blocks_per_seq=max_blocks_per_seq,
                          dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=4),
        attn_impl="reference", multi_step=multi_step, **eng_kw)
    mc = dataclasses.replace(get_model_config("tiny-qwen3"), dtype="float32")
    return Engine(cfg, model_cfg=mc)


PROMPTS = [[5, 6, 7], [11, 12, 13, 14, 15, 16, 17], [200, 201]]


def _ids(reqs):
    return [r.output_token_ids for r in reqs]


def test_greedy_window_matches_single_step():
    # max_tokens=10 is not a multiple of the window (4): the final window
    # overruns and the extra tokens must be dropped at emit
    params = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    base = _engine(multi_step=1).generate(PROMPTS, params)
    multi = _engine(multi_step=4).generate(PROMPTS, params)
    assert _ids(multi) == _ids(base)
    assert all(len(r.output_token_ids) == 10 for r in multi)


def test_seeded_sampling_window_matches_single_step():
    params = [SamplingParams(max_tokens=9, temperature=0.8, seed=s,
                             ignore_eos=True) for s in (1, 2, 3)]
    base = _engine(multi_step=1).generate(PROMPTS, params)
    multi = _engine(multi_step=4).generate(PROMPTS, params)
    assert _ids(multi) == _ids(base)


def test_mixed_greedy_and_sampled_batch():
    params = [SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True),
              SamplingParams(max_tokens=8, temperature=0.9, seed=7,
                             ignore_eos=True),
              SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)]
    base = _engine(multi_step=1).generate(PROMPTS, params)
    multi = _engine(multi_step=4).generate(PROMPTS, params)
    assert _ids(multi) == _ids(base)


def test_truncation_stays_on_fused_window():
    """top-k/top-p run INSIDE the window (window_sample mode="full") —
    the common production sampling configs must keep fused-window
    throughput — and the stream must be token-identical to the
    single-step sorting sampler with the same seeds."""
    eng = _engine(multi_step=4)
    params = SamplingParams(max_tokens=6, temperature=0.9, top_k=5, seed=1,
                            ignore_eos=True)
    reqs = eng.generate(PROMPTS[:1], params)
    assert len(reqs[0].output_token_ids) == 6
    # 6 tokens: 1 prefill + 5 decode; windowed = ceil(5/4)*4 = 8 device
    # steps.  Single-step fallback would count exactly 5 — the overrun
    # proves the WINDOW served the truncated request.
    assert eng.stats.num_decode_steps == 8
    base = _engine(multi_step=1).generate(PROMPTS[:1], params)
    assert _ids(reqs) == _ids(base)


def test_mixed_truncation_batch_window_matches_single_step():
    params = [
        SamplingParams(max_tokens=7, temperature=0.9, top_p=0.8, seed=11,
                       ignore_eos=True),
        SamplingParams(max_tokens=7, temperature=0.7, top_k=3, seed=12,
                       ignore_eos=True),
        SamplingParams(max_tokens=7, temperature=0.8, min_p=0.05, seed=13,
                       ignore_eos=True),
    ]
    base = _engine(multi_step=1).generate(PROMPTS, params)
    multi = _engine(multi_step=4).generate(PROMPTS, params)
    assert _ids(multi) == _ids(base)


def test_logprobs_stay_on_fused_window_and_match_single_step():
    """Sampled-token logprobs compute INSIDE the window (decode_multi
    logprobs_n) — 1:1 with output tokens, same values/top-N as the
    per-step recorder, and the window path must actually serve it."""
    eng = _engine(multi_step=4)
    params = SamplingParams(max_tokens=6, temperature=0.0, logprobs=3,
                            ignore_eos=True)
    reqs = eng.generate(PROMPTS[:1], params)
    assert len(reqs[0].output_token_ids) == 6
    assert len(reqs[0].logprobs) == 6
    # 6 tokens: 1 prefill + 5 decode; windowed = ceil(5/4)*4 = 8 device
    # steps, single-step fallback = exactly 5 — the overrun proves the
    # WINDOW served the logprobs request
    assert eng.stats.num_decode_steps == 8
    base = _engine(multi_step=1).generate(PROMPTS[:1], params)
    for w, b in zip(reqs[0].logprobs, base[0].logprobs):
        assert w["token_id"] == b["token_id"]
        assert abs(w["logprob"] - b["logprob"]) < 1e-5
        assert [t for t, _ in w["top"]] == [t for t, _ in b["top"]]
        for (_, wl), (_, bl) in zip(w["top"], b["top"]):
            assert abs(wl - bl) < 1e-5


def test_penalties_stay_on_fused_window_and_match_single_step():
    """Presence/frequency/repetition penalties run INSIDE the window via
    the on-device count carry — token-identical to the per-step
    penalizer (counts re-derived from host history each step)."""
    params = [
        SamplingParams(max_tokens=9, temperature=0.0, presence_penalty=0.8,
                       frequency_penalty=0.5, ignore_eos=True),
        SamplingParams(max_tokens=9, temperature=0.8, seed=6,
                       repetition_penalty=1.3, top_p=0.9, ignore_eos=True),
        SamplingParams(max_tokens=9, temperature=0.7, seed=7,
                       frequency_penalty=1.1, ignore_eos=True),
    ]
    base = _engine(multi_step=1).generate(PROMPTS, params)
    eng = _engine(multi_step=4)
    multi = eng.generate(PROMPTS, params)
    assert _ids(multi) == _ids(base)
    # 9 tokens: 1 prefill + 8 decode = two full 4-step windows per seq;
    # the single-step fallback would count exactly 8 once... overrun-free
    # here, so prove the window path via dispatch count: 8 device steps
    # from 2 windows (a fallback would ALSO be 8) — instead assert via
    # latency stats absence and window counters
    assert eng.stats.num_decode_steps == 8


def test_penalties_window_proof_by_overrun():
    """max_tokens chosen so the window overruns — the overrun only
    happens when the WINDOW served the penalized request."""
    eng = _engine(multi_step=4)
    p = SamplingParams(max_tokens=6, temperature=0.0, presence_penalty=0.9,
                       ignore_eos=True)
    reqs = eng.generate(PROMPTS[:1], p)
    assert len(reqs[0].output_token_ids) == 6
    assert eng.stats.num_decode_steps == 8     # ceil(5/4)*4, not 5
    base = _engine(multi_step=1).generate(PROMPTS[:1], p)
    assert _ids(reqs) == _ids(base)


def test_logit_bias_stays_on_fused_window_and_matches():
    """logit_bias rides the window as a dense per-row bias (same
    executable family as penalties, zeros when only one is in play) —
    token-identical to the per-step scatter path, including combined
    bias+penalty batches."""
    params = [
        SamplingParams(max_tokens=6, temperature=0.0,
                       logit_bias={5: 100.0}, ignore_eos=True),
        SamplingParams(max_tokens=6, temperature=0.8, seed=9, top_p=0.9,
                       logit_bias={7: 4.0, 11: -100.0}, ignore_eos=True),
        SamplingParams(max_tokens=6, temperature=0.0,
                       logit_bias={3: 2.5}, presence_penalty=0.7,
                       ignore_eos=True),
    ]
    base = _engine(multi_step=1).generate(PROMPTS, params)
    eng = _engine(multi_step=4)
    multi = eng.generate(PROMPTS, params)
    assert _ids(multi) == _ids(base)
    # +100 bias pins the greedy stream to token 5 — proves bias applied
    assert all(t == 5 for t in multi[0].output_token_ids)
    # overrun proves the WINDOW served it: 1 prefill + ceil(5/4)*4 = 8
    assert eng.stats.num_decode_steps == 8


def test_min_tokens_floor_lifts_mid_window():
    """min_tokens rides the window: the EOS/stop mask applies per scan
    step while the row is below its floor and LIFTS on the exact step
    it crosses (floor_remaining) — token-identical to the per-step
    masked path, including floors that end mid-window."""
    params = [
        # floor 6 with window 4: crossing happens inside window 2
        SamplingParams(max_tokens=10, temperature=0.0, min_tokens=6),
        SamplingParams(max_tokens=10, temperature=0.8, seed=8, top_p=0.9,
                       min_tokens=3, stop_token_ids=[9]),
        SamplingParams(max_tokens=10, temperature=0.0),   # no floor
    ]
    base = _engine(multi_step=1).generate(PROMPTS, params)
    eng = _engine(multi_step=4)
    multi = eng.generate(PROMPTS, params)
    assert _ids(multi) == _ids(base)
    for m in multi[:2]:
        assert len(m.output_token_ids) >= 3   # floors respected


def test_min_tokens_under_pipelined_windows_not_stale():
    """Pipelined windows: floor_remaining is built from host lengths
    that lag the in-flight window — the staleness flush (slack =
    pending.steps) must resolve it first or the floor over-masks past
    its end.  Stream must equal the unpipelined engine's."""
    params = [SamplingParams(max_tokens=12, temperature=0.0, min_tokens=7),
              SamplingParams(max_tokens=12, temperature=0.8, seed=2,
                             min_tokens=6, stop_token_ids=[9])]
    plain = _engine(multi_step=4,
                    pipeline_decode=False).generate(PROMPTS[:2], params)
    piped = _engine(multi_step=4,
                    pipeline_decode=True).generate(PROMPTS[:2], params)
    assert _ids(piped) == _ids(plain)


def test_penalties_under_pipelined_windows_not_stale():
    """Pipelined decode chains window N+1 off window N's device tokens
    BEFORE the host sees them — penalty counts built from host history
    would miss a full window of the request's own tokens (round-5
    review).  The engine must resolve the in-flight window first; the
    stream must equal the unpipelined engine's."""
    params = SamplingParams(max_tokens=12, temperature=0.0,
                            presence_penalty=0.9, frequency_penalty=0.6,
                            ignore_eos=True)
    plain = _engine(multi_step=4,
                    pipeline_decode=False).generate(PROMPTS[:2], params)
    piped = _engine(multi_step=4,
                    pipeline_decode=True).generate(PROMPTS[:2], params)
    assert _ids(piped) == _ids(plain)


def test_logprobs_with_sampling_and_eos_mid_window():
    """Seeded temperature + logprobs on the window path, with a stream
    finishing mid-window: entries stay 1:1 with consumed tokens and
    match the single-step path."""
    params = [SamplingParams(max_tokens=9, temperature=0.8, seed=4,
                             logprobs=2, ignore_eos=True),
              SamplingParams(max_tokens=3, temperature=0.0, logprobs=1,
                             ignore_eos=True)]
    base = _engine(multi_step=1).generate(PROMPTS[:2], params)
    multi = _engine(multi_step=4).generate(PROMPTS[:2], params)
    assert _ids(multi) == _ids(base)
    for m, b in zip(multi, base):
        assert len(m.logprobs) == len(m.output_token_ids)
        assert [e["token_id"] for e in m.logprobs] == \
               [e["token_id"] for e in b.logprobs]


def test_window_counts_device_steps():
    eng = _engine(multi_step=4)
    eng.generate(PROMPTS[:1], SamplingParams(max_tokens=8, temperature=0.0,
                                             ignore_eos=True))
    # 8 tokens: 1 from prefill, 7 from ceil(7/4)=2 windows = 8 device steps
    assert eng.stats.num_decode_steps == 8


def test_capacity_fallback_near_full_cache():
    # pool sized so the 4-token window reserve fails part-way: the engine
    # must fall back to single-step (which preempts) and still finish
    eng = _engine(multi_step=4, num_blocks=14, max_blocks_per_seq=8)
    params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    reqs = eng.generate(PROMPTS, params)
    assert all(len(r.output_token_ids) == 12 for r in reqs)
    base = _engine(multi_step=1, num_blocks=14,
                   max_blocks_per_seq=8).generate(PROMPTS, params)
    assert _ids(reqs) == _ids(base)


def test_length_cap_mid_window():
    # max_seq_len = (num_blocks-1)*block_size bounded by max_blocks_per_seq
    # capacity; a request that hits the cap mid-window must stop exactly at
    # the cap with FinishReason.LENGTH, extra window tokens dropped
    eng = _engine(multi_step=4, num_blocks=10, max_blocks_per_seq=8)
    params = SamplingParams(max_tokens=1000, temperature=0.0, ignore_eos=True)
    [req] = eng.generate(PROMPTS[:1], params)
    assert req.finish_reason == FinishReason.LENGTH
    assert req.num_tokens <= eng.max_seq_len
    # engine fully drained, blocks freed
    assert eng.block_manager.num_seqs() == 0


def test_auto_resolution_off_on_cpu():
    assert _engine(multi_step=None)._multi_step == 1
    assert _engine(multi_step=6)._multi_step == 6


def test_chunked_prefill_pallas_matches_reference():
    """Long prompts route through prefill_chunk; with attn_impl=pallas the
    paged window kernel (interpret mode on CPU) must produce the same
    stream as the reference attention."""
    from tpuserve.runtime.scheduler import SchedulerConfig

    def build(attn_impl):
        cfg = EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=4,
                                      prefill_chunk_size=8),
            attn_impl=attn_impl, enable_prefix_caching=False)
        mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                                 dtype="float32")
        return Engine(cfg, model_cfg=mc)

    long_prompt = [list(range(1, 21))]       # 20 tokens > chunk size 8
    params = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    ref = build("reference").generate(long_prompt, params)
    pal = build("pallas").generate(long_prompt, params)
    assert _ids(pal) == _ids(ref)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_randomized_stream_equivalence_under_pressure(seed):
    """Randomized workload — mixed prompt lengths (some routed to chunked
    prefill), staggered arrivals, tight block budget (preemptions), prefix
    caching on, greedy + seeded sampling mixed — must produce identical
    streams with multi_step=4 and multi_step=1.  This is the interaction
    surface where windowed reservations could corrupt state."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_req = 6
    prompts = []
    for i in range(n_req):
        L = int(rng.integers(2, 20))
        # shared prefix for some: exercises prefix-cache hits
        base = [7, 8, 9, 10] if i % 2 == 0 else []
        prompts.append(base + rng.integers(1, 400, size=L).tolist())
    params = []
    for i in range(n_req):
        if i % 3 == 0:
            params.append(SamplingParams(max_tokens=int(rng.integers(3, 15)),
                                         temperature=0.8, seed=100 + i,
                                         ignore_eos=True))
        else:
            params.append(SamplingParams(max_tokens=int(rng.integers(3, 15)),
                                         temperature=0.0, ignore_eos=True))

    def run(multi_step):
        cfg = EngineConfig(
            model="tiny-qwen3",
            # 12 blocks is tight enough that every seed preempts in BOTH
            # modes (asserted below) — the windowed-reservation interaction
            # this test exists for
            cache=CacheConfig(block_size=4, num_blocks=12,
                              max_blocks_per_seq=12, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=4,
                                      prefill_chunk_size=8),
            attn_impl="reference", multi_step=multi_step,
            enable_prefix_caching=True)
        mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                                 dtype="float32")
        eng = Engine(cfg, model_cfg=mc)
        # staggered arrivals: one request enqueued per engine step
        rids, pending = [], list(zip(prompts, params))
        while pending or eng.has_work():
            if pending:
                pr, pa = pending.pop(0)
                rids.append(eng.add_request(prompt_token_ids=pr, params=pa))
            eng.step()
        return [eng.requests.pop(r).output_token_ids for r in rids], \
            eng.stats.preemptions

    ids1, preempt1 = run(1)
    ids4, preempt4 = run(4)
    assert preempt1 > 0 and preempt4 > 0, (
        "workload no longer preempts — the test is vacuous; tighten "
        "num_blocks")
    assert ids4 == ids1


def test_window_with_pallas_kernels():
    """decode_multi scans the decode trunk with the Pallas paged-attention
    kernel inside (interpret mode on CPU) — the exact composition the TPU
    path runs; must match the reference engine token-for-token."""
    def build(attn_impl, multi_step):
        cfg = EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=4),
            attn_impl=attn_impl, multi_step=multi_step)
        mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                                 dtype="float32")
        return Engine(cfg, model_cfg=mc)

    params = SamplingParams(max_tokens=7, temperature=0.0, ignore_eos=True)
    ref = build("reference", 1).generate(PROMPTS, params)
    pal = build("pallas", 3).generate(PROMPTS, params)
    assert _ids(pal) == _ids(ref)


# ---------------------------------------------------------------------------
# Pipelined windows: window W+1 dispatched from W's device-resident last
# column before W's host sync (Engine._pending_window)
# ---------------------------------------------------------------------------

def test_pipelined_window_matches_single_step():
    params = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    base = _engine(multi_step=1).generate(PROMPTS, params)
    piped = _engine(multi_step=4, pipeline_decode=True).generate(PROMPTS,
                                                                 params)
    assert _ids(piped) == _ids(base)
    assert all(len(r.output_token_ids) == 10 for r in piped)


def test_pipelined_window_seeded_sampling():
    params = [SamplingParams(max_tokens=9, temperature=0.8, seed=s,
                             ignore_eos=True) for s in (1, 2, 3)]
    base = _engine(multi_step=1).generate(PROMPTS, params)
    piped = _engine(multi_step=4, pipeline_decode=True).generate(PROMPTS,
                                                                 params)
    assert _ids(piped) == _ids(base)


def test_pipelined_window_zombie_rows_on_eos():
    """A request that hits EOS inside window W is only discovered at W's
    flush — after window W+1 (containing its row) was already dispatched.
    That zombie row's tokens must be dropped whole, its blocks freed
    exactly once, and every other stream must be unaffected."""
    probe = _engine(multi_step=1).generate(
        PROMPTS, SamplingParams(max_tokens=12, temperature=0.0,
                                ignore_eos=True))
    # make a token that actually occurs mid-stream the EOS: request 0
    # then stops mid-window while the others keep decoding
    eos = probe[0].output_token_ids[5]

    def run(multi_step, pipeline):
        cfg = EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=4),
            attn_impl="reference", multi_step=multi_step,
            pipeline_decode=pipeline)
        mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                                 dtype="float32", eos_token_id=eos)
        eng = Engine(cfg, model_cfg=mc)
        outs = eng.generate(PROMPTS,
                            SamplingParams(max_tokens=12, temperature=0.0))
        return outs, eng

    base, _ = run(1, False)
    assert any(r.finish_reason == FinishReason.STOP for r in base), (
        "probe EOS token never fired — test is vacuous")
    piped, eng = run(4, True)
    assert _ids(piped) == _ids(base)
    assert [r.finish_reason for r in piped] == [r.finish_reason for r in base]
    assert eng.block_manager.num_seqs() == 0          # no leaked blocks
    assert eng._pending_window is None
    assert eng.stats.window_overrun_tokens > 0        # zombies were counted


def test_pipelined_window_staggered_arrivals():
    """Fresh prefills join mid-stream: their first window input is a
    host-known token mixed (via _select_tokens) with the in-flight
    window's device tokens."""
    params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

    def run(multi_step, pipeline):
        eng = _engine(multi_step=multi_step, pipeline_decode=pipeline)
        rids, pending = [], [list(p) for p in PROMPTS]
        while pending or eng.has_work():
            if pending:
                rids.append(eng.add_request(prompt_token_ids=pending.pop(0),
                                            params=params))
            eng.step()
        return [eng.requests.pop(r).output_token_ids for r in rids]

    assert run(4, True) == run(1, False)


def test_pipelined_window_abort_in_flight():
    """Abort while a window is in flight: the aborted row is dropped at
    flush, the engine drains, and other requests are unaffected."""
    params = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    eng = _engine(multi_step=4, pipeline_decode=True)
    rids = [eng.add_request(prompt_token_ids=p, params=params)
            for p in PROMPTS]
    for _ in range(3):
        eng.step()
    assert eng._pending_window is not None
    assert eng.abort_request(rids[1])
    while eng.has_work():
        eng.step()
    assert eng.block_manager.num_seqs() == 0
    done = [eng.requests[r] for r in rids]
    assert done[1].finish_reason == FinishReason.ABORT
    base = _engine(multi_step=1).generate(PROMPTS, params)
    for i in (0, 2):                       # unaffected streams match base
        assert done[i].output_token_ids == base[i].output_token_ids


def test_pipelined_window_capacity_fallback():
    eng = _engine(multi_step=4, pipeline_decode=True, num_blocks=14,
                  max_blocks_per_seq=8)
    params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    reqs = eng.generate(PROMPTS, params)
    base = _engine(multi_step=1, num_blocks=14,
                   max_blocks_per_seq=8).generate(PROMPTS, params)
    assert _ids(reqs) == _ids(base)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_randomized_pipelined_equivalence_under_pressure(seed):
    """The randomized pressure workload (chunked prefills, staggered
    arrivals, preemptions, prefix caching, mixed sampling) must produce
    identical streams with pipelined windows on."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_req = 6
    prompts = []
    for i in range(n_req):
        L = int(rng.integers(2, 20))
        base = [7, 8, 9, 10] if i % 2 == 0 else []
        prompts.append(base + rng.integers(1, 400, size=L).tolist())
    params = []
    for i in range(n_req):
        if i % 3 == 0:
            params.append(SamplingParams(max_tokens=int(rng.integers(3, 15)),
                                         temperature=0.8, seed=100 + i,
                                         ignore_eos=True))
        else:
            params.append(SamplingParams(max_tokens=int(rng.integers(3, 15)),
                                         temperature=0.0, ignore_eos=True))

    def run(multi_step, pipeline):
        cfg = EngineConfig(
            model="tiny-qwen3",
            cache=CacheConfig(block_size=4, num_blocks=12,
                              max_blocks_per_seq=12, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                      min_decode_bucket=4,
                                      prefill_chunk_size=8),
            attn_impl="reference", multi_step=multi_step,
            pipeline_decode=pipeline, enable_prefix_caching=True)
        mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                                 dtype="float32")
        eng = Engine(cfg, model_cfg=mc)
        rids, pending = [], list(zip(prompts, params))
        while pending or eng.has_work():
            if pending:
                pr, pa = pending.pop(0)
                rids.append(eng.add_request(prompt_token_ids=pr, params=pa))
            eng.step()
        return [eng.requests.pop(r).output_token_ids for r in rids]

    assert run(4, True) == run(1, False)


# ------------------------------------------------- adaptive window sizing

def test_adaptive_shrinks_on_busy_arrival():
    # an arrival landing while decode is busy must shrink subsequent
    # windows to min_multi_step (bounding the arrival's admission wait)
    eng = _engine(multi_step=8, min_multi_step=2)
    p = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    eng.add_request(prompt_token_ids=[5, 6, 7], params=p)
    eng.step()                                   # prefill
    d0 = eng.stats.num_decode_steps
    eng.step()                                   # full window: idle arrivals
    assert eng.stats.num_decode_steps - d0 == 8
    assert eng.stats.latency_windows == 0
    eng.add_request(prompt_token_ids=[8, 9], params=p)   # busy arrival
    while eng.has_work():
        eng.step()
    assert eng.stats.latency_windows > 0


def test_adaptive_tokens_match_fixed():
    # shrinking windows must not change greedy token streams
    p = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    fixed = _engine(multi_step=8, adaptive_multi_step=False)
    r1 = fixed.add_request(prompt_token_ids=[5, 6, 7], params=p)
    fixed.step()
    r2 = fixed.add_request(prompt_token_ids=[8, 9], params=p)
    while fixed.has_work():
        fixed.step()
    adaptive = _engine(multi_step=8, min_multi_step=2)
    a1 = adaptive.add_request(prompt_token_ids=[5, 6, 7], params=p)
    adaptive.step()
    a2 = adaptive.add_request(prompt_token_ids=[8, 9], params=p)
    while adaptive.has_work():
        adaptive.step()
    assert adaptive.stats.latency_windows > 0
    assert adaptive.requests[a1].output_token_ids == \
        fixed.requests[r1].output_token_ids
    assert adaptive.requests[a2].output_token_ids == \
        fixed.requests[r2].output_token_ids


def test_adaptive_seeded_sampling_matches_fixed():
    p = SamplingParams(max_tokens=10, temperature=0.8, seed=7,
                       ignore_eos=True)
    fixed = _engine(multi_step=8, adaptive_multi_step=False)
    f1 = fixed.add_request(prompt_token_ids=[5, 6, 7], params=p)
    fixed.step()
    fixed.add_request(prompt_token_ids=[8, 9], params=p)
    while fixed.has_work():
        fixed.step()
    adaptive = _engine(multi_step=8, min_multi_step=2)
    a1 = adaptive.add_request(prompt_token_ids=[5, 6, 7], params=p)
    adaptive.step()
    adaptive.add_request(prompt_token_ids=[8, 9], params=p)
    while adaptive.has_work():
        adaptive.step()
    assert adaptive.stats.latency_windows > 0
    assert adaptive.requests[a1].output_token_ids == \
        fixed.requests[f1].output_token_ids


def test_adaptive_hold_expires_back_to_full_windows():
    eng = _engine(multi_step=8, min_multi_step=2,
                  adaptive_window_hold_s=0.0)    # hold expires immediately
    p = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    eng.add_request(prompt_token_ids=[5, 6, 7], params=p)
    eng.step()
    eng.add_request(prompt_token_ids=[8, 9], params=p)
    while eng.has_work():
        eng.step()
    assert eng.stats.latency_windows == 0        # expired before any window


def test_adaptive_idle_burst_keeps_full_windows():
    # burst admission into an IDLE engine must not trip latency mode:
    # a closed-loop burst keeps its full windows
    eng = _engine(multi_step=8, min_multi_step=2)
    p = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    for pr in PROMPTS:
        eng.add_request(prompt_token_ids=pr, params=p)
    while eng.has_work():
        eng.step()
    assert eng.stats.latency_windows == 0
