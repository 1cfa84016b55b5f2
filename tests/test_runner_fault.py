"""Engine-loop fault isolation (AsyncEngineRunner) composed with pipelined
fused windows: a device fault mid-stream must fail the in-flight requests,
drop the orphaned pending window cleanly, and leave the runner serving.

The reference gets crash recovery from K8s restart semantics alone
(SURVEY.md §5 failure detection); the runner adds in-process isolation so
one poisoned batch doesn't take the pod down.
"""

import time

import pytest

from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SamplingParams, SchedulerConfig
from tpuserve.server.runner import AsyncEngineRunner


@pytest.fixture()
def runner():
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=64, max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=8, min_prefill_bucket=8,
                                  min_decode_bucket=2),
        multi_step=4, pipeline_decode=True))
    r = AsyncEngineRunner(eng)
    r.start()
    yield r
    r.shutdown()


def test_runner_fault_mid_window_fails_request_and_recovers(runner):
    eng = runner.engine
    params = SamplingParams(max_tokens=64, temperature=0.0, ignore_eos=True)
    rid, q = runner.submit(prompt_token_ids=[5, 6, 7], params=params)
    # wait until the pipelined window machinery is actually in flight
    deadline = time.monotonic() + 30
    while eng._pending_window is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng._pending_window is not None

    # poison the next window dispatch (device fault analog)
    orig = eng._exec_decode_multi

    def boom(*a, **k):
        raise RuntimeError("injected device fault")

    eng._exec_decode_multi = boom
    try:
        # the in-flight request must fail with the runner's engine-failure
        # marker, not hang
        items = []
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            item = q.get(timeout=30)
            if item is None:
                break
            items.append(item)
        errs = [i for i in items if isinstance(i, Exception)]
        assert errs, f"no failure surfaced to the client: {items[-3:]}"
    finally:
        eng._exec_decode_multi = orig

    # engine drained: no leaked window, no leaked blocks, no leaked queues
    deadline = time.monotonic() + 10
    while eng.has_work() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng._pending_window is None
    assert eng.block_manager.num_seqs() == 0

    # the runner must keep serving after the fault
    outs, _ = runner.generate_sync(
        prompt_token_ids=[9, 10, 11],
        params=SamplingParams(max_tokens=6, temperature=0.0,
                              ignore_eos=True),
        timeout=60)
    assert sum(len(o.new_token_ids) for o in outs) == 6
