"""Crash-only request salvage (server/runner.py + Engine.salvage_requeue):
a faulted engine step costs the POISON request, not the batch.

Acceptance pins (ISSUE 4): with a fault injected into a decode dispatch
carrying N in-flight streams plus one poison request, exactly the poison
request fails with a per-request error and the other N complete with
greedy tokens identical to a fault-free run.
"""

import queue
import time

import pytest

from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SamplingParams, SchedulerConfig
from tpuserve.runtime.faults import InjectedFault
from tpuserve.server.runner import AsyncEngineRunner

PARAMS = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
PROMPTS = [[5, 6, 7], [9, 10, 11], [12, 13, 14], [20, 21, 22]]


@pytest.fixture(autouse=True)
def _strict_blocks(monkeypatch):
    """Salvage tests run with the block-refcount cross-check armed
    (runtime/block_manager.py check_integrity): a recovery path that
    leaks or double-frees KV blocks fails the cycle it happens."""
    monkeypatch.setenv("TPUSERVE_STRICT_BLOCKS", "1")


def _mk(faults=None, **over):
    cfg = dict(multi_step=4, pipeline_decode=True,
               scheduler=SchedulerConfig(max_num_seqs=8,
                                         min_prefill_bucket=8,
                                         min_decode_bucket=2))
    cfg.update(over)
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=128,
                          max_blocks_per_seq=16),
        faults=faults, seed=0, **cfg))
    runner = AsyncEngineRunner(eng)
    runner.start()
    return eng, runner


def _run_all(runner, submits, timeout=120):
    """Drain every submit; returns ({rid: tokens}, {rid: error})."""
    tokens, errors = {}, {}
    deadline = time.monotonic() + timeout
    for rid, q in submits:
        toks = []
        while True:
            item = q.get(timeout=max(deadline - time.monotonic(), 0.001))
            if item is None:
                break
            if isinstance(item, Exception):
                errors[rid] = item
                continue
            toks.extend(item.new_token_ids)
        tokens[rid] = toks
        getattr(runner.engine, "requests", {}).pop(rid, None)
    return tokens, errors


@pytest.fixture(scope="module")
def reference():
    """Fault-free greedy tokens for PROMPTS — the identity baseline every
    salvage scenario is compared against."""
    eng, runner = _mk()
    subs = [runner.submit(prompt_token_ids=p, params=PARAMS,
                          request_id=f"req-{i}")
            for i, p in enumerate(PROMPTS)]
    tokens, errors = _run_all(runner, subs)
    runner.shutdown()
    assert not errors
    assert all(len(t) == PARAMS.max_tokens for t in tokens.values())
    return tokens


def test_one_shot_fault_salvages_every_stream(reference):
    """A transient decode fault mid-flight: every stream is re-queued
    through the preemption re-prefill path and replayed token-identically —
    nobody fails, nothing hangs."""
    eng, runner = _mk(faults="decode_dispatch:raise:1.0:count=1")
    subs = [runner.submit(prompt_token_ids=p, params=PARAMS,
                          request_id=f"req-{i}")
            for i, p in enumerate(PROMPTS)]
    tokens, errors = _run_all(runner, subs)
    runner.shutdown()
    assert not errors
    assert tokens == reference
    assert eng.stats.requests_salvaged > 0
    assert eng.stats.requests_poisoned == 0
    assert eng.block_manager.num_seqs() == 0


def test_poison_request_isolated_by_bisection(reference):
    """ACCEPTANCE: a request that faults EVERY dispatch it rides in is
    bisected out — it alone fails with a per-request error; the other N
    streams complete with fault-free-identical greedy tokens."""
    eng, runner = _mk(faults="decode_dispatch:raise:1.0:match=poison")
    subs = [runner.submit(prompt_token_ids=p, params=PARAMS,
                          request_id=f"req-{i}")
            for i, p in enumerate(PROMPTS)]
    prid, pq = runner.submit(prompt_token_ids=[30, 31, 32], params=PARAMS,
                             request_id="poison-0")
    tokens, errors = _run_all(runner, subs + [(prid, pq)])
    runner.shutdown()
    # exactly the poison request failed, with a clean per-request error
    assert set(errors) == {prid}
    assert "poison" in str(errors[prid]) or "salvage" in str(errors[prid])
    # ...and every other stream is token-identical to the fault-free run
    assert {rid: tokens[rid] for rid in reference} == reference
    assert eng.stats.requests_poisoned == 1
    assert eng.stats.requests_salvaged > 0
    assert eng.block_manager.num_seqs() == 0


def test_mixed_dispatch_fault_salvages(reference):
    """The ragged mixed trunk is a fault site of its own: a one-shot
    mixed-dispatch fault salvages every stream token-identically."""
    eng, runner = _mk(faults="mixed_dispatch:raise:1.0:count=1",
                      multi_step=1, pipeline_decode=False,
                      scheduler=SchedulerConfig(
                          max_num_seqs=8, min_prefill_bucket=8,
                          min_decode_bucket=2, mixed_batching=True))
    subs = [runner.submit(prompt_token_ids=p, params=PARAMS,
                          request_id=f"req-{i}")
            for i, p in enumerate(PROMPTS)]
    tokens, errors = _run_all(runner, subs)
    runner.shutdown()
    assert not errors
    # mixed greedy streams are pinned token-identical to phase-split
    # (tests/test_mixed.py), so the fault-free reference carries over
    assert tokens == reference
    assert eng.stats.requests_salvaged > 0


def test_salvage_requeue_rescues_orphaned_prefill_batch():
    """A prefill batch's requests sit in NEITHER queue between the
    scheduler pop and mark_running; a fault there must not leak them (the
    old fail-all path leaked their blocks)."""
    eng, _ = _mk_engine_only()
    rids = [eng.add_request(prompt_token_ids=p, params=PARAMS)
            for p in PROMPTS[:2]]
    boom = {"armed": True}
    # the batched prefill's exec hook on this engine's route
    hook = "_exec_forward_ragged" if eng._packed_prefill else "_exec_prefill"
    orig = getattr(eng, hook)

    def exploding(*a, **k):
        if boom.pop("armed", None):
            raise InjectedFault("injected prefill fault")
        return orig(*a, **k)

    setattr(eng, hook, exploding)
    with pytest.raises(InjectedFault):
        eng.step()
    # orphaned: popped from waiting, never marked running
    assert eng.scheduler.num_running == 0
    requeued = eng.salvage_requeue()
    assert set(requeued) == set(rids)
    while eng.has_work():
        eng.step()
    for rid in rids:
        assert len(eng.requests.pop(rid).output_token_ids) == \
            PARAMS.max_tokens
    assert eng.block_manager.num_seqs() == 0


def _mk_engine_only():
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=128,
                          max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=8, min_prefill_bucket=8,
                                  min_decode_bucket=2),
        seed=0))
    return eng, None


def test_fault_storm_falls_back_to_fail_all():
    """Past MAX_FAULTS_PER_WINDOW the runner stops salvaging and fails
    everything at once (the pre-salvage crash-only behaviour), counting an
    engine restart — bounded thrash under a persistent whole-engine
    fault."""
    eng, runner = _mk(faults="decode_dispatch:raise:1.0")
    runner.MAX_FAULTS_PER_WINDOW = 0          # every fault is "too many"
    rid, q = runner.submit(prompt_token_ids=[5, 6, 7], params=PARAMS)
    items = []
    while True:
        item = q.get(timeout=60)
        if item is None:
            break
        items.append(item)
    runner.shutdown()
    assert any(isinstance(i, Exception) for i in items)
    assert eng.stats.engine_restarts >= 1
    assert eng.stats.requests_salvaged == 0
    assert eng.block_manager.num_seqs() == 0


def test_salvage_budget_bounds_retry_loops():
    """The per-request fault budget (max_salvages CONSECUTIVE faulted
    attempts without progress) fails a request with a clean error instead
    of retrying forever — here budget 0 means the very first fault
    exhausts it, before bisection even starts."""
    eng, runner = _mk(faults="kv_alloc:raise:1.0:count=1")
    runner.max_salvages = 0
    rid, q = runner.submit(prompt_token_ids=[5, 6, 7], params=PARAMS)
    err = None
    while True:
        item = q.get(timeout=60)
        if item is None:
            break
        if isinstance(item, Exception):
            err = item
    runner.shutdown()
    assert err is not None and "salvage budget" in str(err)
    assert eng.stats.requests_poisoned == 1
    assert eng.block_manager.num_seqs() == 0


def test_window_flush_fault_with_a_first_token_pending(reference):
    """The window's flush faults while a later prefill's first token is
    still on the device (read after the window's, so still unread):
    salvage drops both records with the rest of the in-flight state,
    leaves no block held (strict mode checks every cycle) and replays
    both streams token-identically."""
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=128,
                          max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=8, min_prefill_bucket=8,
                                  min_decode_bucket=2),
        faults="window_flush:raise:1.0:count=1", seed=0, multi_step=4,
        pipeline_decode=True))
    eng.add_request(prompt_token_ids=PROMPTS[0], params=PARAMS,
                    request_id="req-0")
    eng.step()                              # prefill: first token pending
    eng.step()                              # window 1, then the token read
    assert eng._pending_window is not None and eng._pending_first is None
    eng.add_request(prompt_token_ids=PROMPTS[1], params=PARAMS,
                    request_id="req-1")
    eng.step()                              # prefill behind window 1
    assert eng._pending_window is not None and eng._pending_first is not None
    with pytest.raises(InjectedFault):
        eng.step()                          # window 2 enqueued; flush faults
    assert eng._pending_first is not None
    assert set(eng.salvage_requeue()) == {"req-0", "req-1"}
    assert eng._pending_first is None and eng._pending_window is None
    assert eng.block_manager.num_seqs() == 0
    while eng.has_work():
        eng.step()
    assert eng.block_manager.num_seqs() == 0
    for rid in ("req-0", "req-1"):
        assert eng.requests[rid].output_token_ids == reference[rid]
