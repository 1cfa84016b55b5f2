"""Pipelined decode (device-resident token feed, 1-step-lagged host
bookkeeping) must be observationally identical to the synchronous loop."""

import dataclasses

import numpy as np
import pytest

from tpuserve.models.config import get_model_config
from tpuserve.runtime.engine import Engine, EngineConfig
from tpuserve.runtime.kv_cache import CacheConfig
from tpuserve.runtime.request import SamplingParams
from tpuserve.runtime.scheduler import SchedulerConfig


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_model_config("tiny-qwen3"),
                               dtype="float32")


def _engine(cfg, pipeline, num_blocks=128, max_num_seqs=4):
    return Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=num_blocks,
                                       max_blocks_per_seq=24),
                     scheduler=SchedulerConfig(max_num_seqs=max_num_seqs),
                     enable_prefix_caching=False,
                     pipeline_decode=pipeline),
        model_cfg=cfg)


def _run(cfg, pipeline, params_list, prompts):
    eng = _engine(cfg, pipeline)
    return eng.generate(prompts, params_list), eng


def test_greedy_equivalence(cfg):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in (5, 12, 3)]
    p = SamplingParams(max_tokens=7, temperature=0.0, ignore_eos=True)
    a, ea = _run(cfg, True, p, prompts)
    b, eb = _run(cfg, False, p, prompts)
    for x, y in zip(a, b):
        assert x.output_token_ids == y.output_token_ids
    assert ea.block_manager.num_seqs() == eb.block_manager.num_seqs() == 0
    assert ea._pending is None


def test_seeded_sampling_equivalence(cfg):
    prompts = [[1, 2, 3, 4], [9, 8, 7]]
    ps = [SamplingParams(max_tokens=6, temperature=0.9, seed=11,
                         ignore_eos=True),
          SamplingParams(max_tokens=6, temperature=0.7, top_k=20, top_p=0.9,
                         seed=22, ignore_eos=True)]
    a, _ = _run(cfg, True, ps, prompts)
    b, _ = _run(cfg, False, ps, prompts)
    for x, y in zip(a, b):
        assert x.output_token_ids == y.output_token_ids


def test_eos_equivalence(cfg):
    # no ignore_eos: greedy streams may hit eos; both paths must agree
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 200, size=6).tolist() for _ in range(4)]
    p = SamplingParams(max_tokens=30, temperature=0.0)
    a, _ = _run(cfg, True, p, prompts)
    b, _ = _run(cfg, False, p, prompts)
    for x, y in zip(a, b):
        assert x.output_token_ids == y.output_token_ids
        assert x.finish_reason == y.finish_reason


def test_penalties_fall_back_to_sync(cfg):
    p = SamplingParams(max_tokens=5, temperature=0.8, seed=1,
                       presence_penalty=0.5, ignore_eos=True)
    a, eng = _run(cfg, True, p, [[1, 2, 3]])
    b, _ = _run(cfg, False, p, [[1, 2, 3]])
    assert a[0].output_token_ids == b[0].output_token_ids
    assert eng._pending is None


def test_abort_while_in_flight(cfg):
    eng = _engine(cfg, True)
    p = SamplingParams(max_tokens=50, temperature=0.0, ignore_eos=True)
    r1 = eng.add_request(prompt_token_ids=[1, 2, 3], params=p)
    r2 = eng.add_request(prompt_token_ids=[4, 5], params=p)
    for _ in range(4):
        eng.step()
    assert eng._pending is not None
    assert eng.abort_request(r1)
    while eng.has_work():
        eng.step()
    assert eng.block_manager.num_seqs() == 0
    out2 = eng.requests[r2]
    assert len(out2.output_token_ids) == 50


def test_preemption_under_pipeline(cfg):
    # tiny cache so decode appends force preemption while pipelined
    eng = Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=10,
                                       max_blocks_per_seq=8),
                     scheduler=SchedulerConfig(max_num_seqs=3),
                     enable_prefix_caching=False, pipeline_decode=True),
        model_cfg=cfg)
    p = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    outs = eng.generate([[1, 2, 3, 4, 5], [6, 7, 8, 9], [1, 9, 2]], p)
    for r in outs:
        assert len(r.output_token_ids) == 12
    assert eng.block_manager.num_seqs() == 0


def test_mixed_prefill_decode_interleaving(cfg):
    """New requests joining mid-stream (fresh prefill) merge with in-flight
    pipelined requests correctly."""
    eng = _engine(cfg, True)
    p = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    eng.add_request(prompt_token_ids=[1, 2, 3], params=p)
    for _ in range(3):
        eng.step()
    eng.add_request(prompt_token_ids=[4, 5, 6, 7], params=p)
    while eng.has_work():
        eng.step()
    ref = _engine(cfg, False)
    a = ref.generate([[1, 2, 3]], p)[0].output_token_ids
    b = ref.generate([[4, 5, 6, 7]], p)[0].output_token_ids
    got = {r.prompt_token_ids[0]: r.output_token_ids
           for r in eng.requests.values()}
    assert got[1] == a
    assert got[4] == b


# --------------------------------------------------------------------------
# A prefill's first token is read one dispatch later (Engine._defer_first /
# _flush_first): it stays on the device, feeds the next fused window's rows
# there, and the host reads it behind that dispatch.  Token for token what
# the synchronous engine emits, on every prefill route.
# --------------------------------------------------------------------------

LONG = list(range(3, 43))               # 40 tokens: over the 16-token chunk
GREEDY = SamplingParams(max_tokens=11, temperature=0.0, ignore_eos=True)
SEEDED = SamplingParams(max_tokens=11, temperature=0.8, top_k=40, seed=5,
                        ignore_eos=True)


def _fused(pipeline, route="packed", model="tiny-qwen3", **sched):
    """A fused-window engine (windows of 4).  ``route`` is the prefill
    layout the engine OBSERVES, not an option: pages in the model's dtype
    pack the batch on one flat axis; bfloat16 pages under a float32 model
    keep the (B, L) grid; "chunk" adds a 16-token chunk size, so LONG
    takes the chunk route."""
    mcfg = dataclasses.replace(get_model_config(model), dtype="float32")
    if route == "chunk":
        sched["prefill_chunk_size"] = 16
    eng = Engine(
        EngineConfig(model=model,
                     cache=CacheConfig(
                         block_size=4, num_blocks=160, max_blocks_per_seq=24,
                         dtype="bfloat16" if route == "grid" else "float32"),
                     scheduler=SchedulerConfig(
                         **{"max_num_seqs": 4, "min_prefill_bucket": 8,
                            "min_decode_bucket": 2, **sched}),
                     enable_prefix_caching=False, multi_step=4,
                     pipeline_decode=pipeline),
        model_cfg=mcfg)
    assert eng._packed_prefill == (route != "grid")
    return eng


FIRST_TOKEN_CASES = {
    "packed-greedy": ("packed", GREEDY, {}, "tiny-qwen3"),
    "packed-seeded": ("packed", SEEDED, {}, "tiny-qwen3"),
    "grid-greedy": ("grid", GREEDY, {}, "tiny-qwen3"),
    "grid-seeded": ("grid", SEEDED, {}, "tiny-qwen3"),
    "chunk-greedy": ("chunk", GREEDY, {}, "tiny-qwen3"),
    "chunk-seeded": ("chunk", SEEDED, {}, "tiny-qwen3"),
    "packed-logprobs": ("packed", dataclasses.replace(GREEDY, logprobs=3),
                        {}, "tiny-qwen3"),
    "chunk-logprobs": ("chunk", dataclasses.replace(SEEDED, logprobs=2),
                       {}, "tiny-qwen3"),
    # one prompt a prefill batch: three prefills in a row, then a window
    "prefills-in-a-row": ("packed", GREEDY, {"max_prefill_seqs": 1},
                          "tiny-qwen3"),
    "falcon-h1-greedy": ("packed", GREEDY, {}, "tiny-falcon-h1"),
    "falcon-h1-seeded": ("packed", SEEDED, {}, "tiny-falcon-h1"),
}


@pytest.mark.parametrize("case", sorted(FIRST_TOKEN_CASES))
def test_deferred_first_token_is_token_identical(case):
    route, params, sched, model = FIRST_TOKEN_CASES[case]
    prompts = [[5, 6, 7, 8, 9], [11, 12, 13], LONG]
    runs = []
    for pipeline in (True, False):
        eng = _fused(pipeline, route, model, **sched)
        outs = eng.generate(prompts, params)
        # a second wave joins a running batch: prefill behind a window
        eng.add_request(prompt_token_ids=[21, 22, 23, 24], params=params)
        for _ in range(3):
            eng.step()
        late = eng.add_request(prompt_token_ids=[31, 32], params=params)
        while eng.has_work():
            eng.step()
        outs += [r for r in eng.requests.values() if r not in outs]
        assert eng.requests[late].output_token_ids
        assert eng.block_manager.num_seqs() == 0
        assert eng._pending_first is None and eng._pending_window is None
        runs.append((eng, outs))
    (ea, a), (eb, b) = runs
    assert len(a) == len(b) == 5
    for x, y in zip(a, b):
        assert x.output_token_ids == y.output_token_ids
        assert len(x.output_token_ids) == params.max_tokens
        assert x.output_text == y.output_text
        assert x.finish_reason == y.finish_reason
        if params.logprobs is not None:
            assert len(x.logprobs) == params.max_tokens
            for ex, ey in zip(x.logprobs, y.logprobs):
                assert ex["token_id"] == ey["token_id"]
                assert ex["logprob"] == pytest.approx(ey["logprob"], abs=1e-5)
                assert [t for t, _ in ex["top"]] == [t for t, _ in ey["top"]]
    # the pipelined engine read every first token behind a later dispatch;
    # the synchronous one read each at once, through the same path
    sa, sb = ea.stats, eb.stats
    assert sa.prefill_first_token_deferred == 5
    assert sa.prefill_first_token_flushed_early == 0
    assert sb.prefill_first_token_deferred == 0
    assert sb.prefill_first_token_flushed_early == 5
    assert sa.ttft_count == sb.ttft_count == 5
    if case == "prefills-in-a-row":
        assert sa.num_prefill_steps >= 5


BYTE_A = ord("A") + 3                   # the byte tokenizer's id of "A"
ENDS_ON_FIRST = {
    # logit_bias is static for a request, so it rides the deferred read
    "eos": dict(logit_bias={2: 100.0}),
    "stop-string": dict(logit_bias={BYTE_A: 100.0}, stop=["A"],
                        ignore_eos=True),
    "max-tokens-1": dict(max_tokens=1, ignore_eos=True),
}


@pytest.mark.parametrize("case", sorted(ENDS_ON_FIRST))
def test_request_ending_on_its_first_token(case):
    """Such a row is baked into the window enqueued before the host knew:
    dropped whole at that window's flush and counted as overrun.
    ``max_tokens`` = 1 is host-known, so it gets no window at all."""
    short = SamplingParams(**{"max_tokens": 9, "temperature": 0.0,
                              **ENDS_ON_FIRST[case]})
    runs = []
    for pipeline in (True, False):
        eng = _fused(pipeline)
        rid = eng.add_request(prompt_token_ids=[5, 6, 7], params=short)
        other = eng.add_request(prompt_token_ids=[8, 9, 10, 11],
                                params=GREEDY)
        while eng.has_work():
            eng.step()
        assert eng.block_manager.num_seqs() == 0
        runs.append((eng, eng.requests[rid], eng.requests[other]))
    (ea, ra, oa), (eb, rb, ob) = runs
    assert len(ra.output_token_ids) == 1
    assert ra.output_token_ids == rb.output_token_ids
    assert ra.finish_reason == rb.finish_reason
    assert ra.output_text == rb.output_text
    assert oa.output_token_ids == ob.output_token_ids
    assert ea.stats.prefill_first_token_deferred == 2
    zombie = ea.stats.window_overrun_tokens - eb.stats.window_overrun_tokens
    # the window of 4 that carried the finished row, or none
    assert zombie == (0 if case == "max-tokens-1" else 4)


EARLY = {
    "penalised": dict(presence_penalty=0.5, temperature=0.8, seed=3),
    "min-tokens": dict(min_tokens=3, temperature=0.0),
    "guided": dict(guided="regex", guided_schema="[ab]{3,6}X",
                   temperature=0.0),
}


@pytest.mark.parametrize("case", sorted(EARLY))
def test_what_the_host_must_know_is_flushed_early(case):
    """Penalty counts and the min_tokens floor read host history, a guided
    row's FSM mirror advances by the token: such a row's first token is
    read BEFORE the next dispatch, and counted so."""
    params = SamplingParams(**{"max_tokens": 8, **EARLY[case]})
    runs = []
    for pipeline in (True, False):
        eng = _fused(pipeline)
        out = eng.generate([[5, 6, 7, 8]], params)[0]
        assert eng.block_manager.num_seqs() == 0
        runs.append((eng, out))
    (ea, a), (_, b) = runs
    assert a.output_token_ids == b.output_token_ids
    assert a.output_text == b.output_text
    assert a.finish_reason == b.finish_reason
    assert ea.stats.prefill_first_token_flushed_early == 1
    assert ea.stats.prefill_first_token_deferred == 0


def test_abort_with_a_first_token_pending(monkeypatch):
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    eng = _fused(True)
    r1 = eng.add_request(prompt_token_ids=[1, 2, 3], params=GREEDY)
    r2 = eng.add_request(prompt_token_ids=[4, 5], params=GREEDY)
    assert eng.step() == []                 # the prefill: nothing read yet
    assert eng._pending_first is not None
    assert eng.abort_request(r1)
    while eng.has_work():
        eng.step()
    assert eng.block_manager.num_seqs() == 0
    assert eng.requests[r1].output_token_ids == []
    ref = _fused(False).generate([[4, 5]], GREEDY)[0]
    assert eng.requests[r2].output_token_ids == ref.output_token_ids


def test_preemption_with_a_first_token_pending(monkeypatch):
    """A pool too small for the window: _try_reserve_window fails with a
    first token still on the device, the single-step path reads it before
    it pre-empts, and the replay is token-identical."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")
    # 3 + 3 + 2 blocks of the 9 usable hold the prompts; a window of 4
    # behind the pending token needs 4 + 4 + 3
    prompts = [list(range(1, 13)), list(range(20, 32)), list(range(40, 48))]
    params = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    runs = []
    for pipeline in (True, False):
        eng = Engine(
            EngineConfig(model="tiny-qwen3",
                         cache=CacheConfig(block_size=4, num_blocks=10,
                                           max_blocks_per_seq=8),
                         scheduler=SchedulerConfig(max_num_seqs=3),
                         enable_prefix_caching=False, multi_step=4,
                         pipeline_decode=pipeline))
        runs.append((eng, eng.generate(prompts, params)))
        assert eng.block_manager.num_seqs() == 0
    (ea, a), (eb, b) = runs
    assert ea.stats.preemptions > 0
    assert ea.stats.prefill_first_token_flushed_early > 0
    for x, y in zip(a, b):
        assert x.output_token_ids == y.output_token_ids
        assert len(x.output_token_ids) == 12


def test_the_first_token_counters_are_exported():
    from tpuserve.server.metrics import ServerMetrics
    from tpuserve.server.runner import AsyncEngineRunner
    eng = _fused(True)
    eng.generate([[5, 6, 7], [8, 9]], GREEDY)
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=4, temperature=0.0,
                                             min_tokens=2))
    assert eng.stats.prefill_first_token_deferred == 2
    assert eng.stats.prefill_first_token_flushed_early == 1
    runner = AsyncEngineRunner(eng, ServerMetrics("tiny-qwen3"))
    runner._update_gauges()
    text = runner.metrics.render().decode()
    for name, value in (
            ("tpuserve_prefill_first_tokens_deferred_total", 2),
            ("tpuserve_prefill_first_tokens_flushed_early_total", 1)):
        line = next(ln for ln in text.splitlines() if ln.startswith(name))
        assert float(line.rsplit(" ", 1)[1]) == value
