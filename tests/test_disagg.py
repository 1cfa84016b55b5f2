"""Disaggregated prefill/decode: KV handoff correctness vs a colocated
engine (the llm-d topology of the reference, rebuilt with device-to-device
page transfer — see tpuserve/parallel/disagg.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.parallel.disagg import (DisaggregatedEngine, extract_seq_kv,
                                      insert_seq_kv)
from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                              SamplingParams, SchedulerConfig)


def _cfg(**kw):
    return EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=64,
                                  min_prefill_bucket=8, min_decode_bucket=2),
        **kw)


def test_extract_insert_roundtrip():
    src = [{"k": jnp.arange(32 * 4 * 2 * 4, dtype=jnp.float32).reshape(32, 4, 2, 4),
            "v": jnp.ones((32, 4, 2, 4), jnp.float32)}]
    pages, src = extract_seq_kv(src, [3, 7])
    dst = [{"k": jnp.zeros((16, 4, 2, 4), jnp.float32),
            "v": jnp.zeros((16, 4, 2, 4), jnp.float32)}]
    dst = insert_seq_kv(dst, pages, [5, 9])
    np.testing.assert_array_equal(np.asarray(dst[0]["k"][5]), np.asarray(src[0]["k"][3]))
    np.testing.assert_array_equal(np.asarray(dst[0]["k"][9]), np.asarray(src[0]["k"][7]))
    assert float(dst[0]["k"][0].sum()) == 0.0


def test_disagg_matches_colocated():
    """Same prompts, same greedy params: the disaggregated pipeline must
    produce exactly the colocated engine's tokens."""
    colocated = Engine(_cfg())
    p = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    prompts = ["Hello world", "abcdefgh", "xy"]
    ref = colocated.generate(prompts, p)

    disagg = DisaggregatedEngine(_cfg(), _cfg())
    out = disagg.generate(prompts, p)
    for r, o in zip(ref, out):
        assert r.output_token_ids == o.output_token_ids
    assert disagg.stats.kv_transfers == 3
    assert disagg.stats.kv_bytes_transferred > 0
    # both pools fully drained
    assert disagg.prefill.block_manager.num_seqs() == 0
    assert disagg.decode.block_manager.num_seqs() == 0


def test_disagg_finish_at_prefill():
    disagg = DisaggregatedEngine(_cfg(), _cfg())
    out = disagg.generate(["one token only"],
                          SamplingParams(max_tokens=1, temperature=0.0,
                                         ignore_eos=True))
    assert len(out) == 1 and len(out[0].output_token_ids) == 1
    assert disagg.stats.kv_transfers == 0       # finished before migration


def test_disagg_streaming_steps():
    disagg = DisaggregatedEngine(_cfg(), _cfg())
    disagg.add_request(prompt="stream", params=SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    seen = 0
    while disagg.has_work():
        seen += len(disagg.step())
    assert seen == 4


def test_disagg_admission_control_many_requests():
    """More requests than decode max_num_seqs: must not overflow the decode
    batch (regression for unbounded migration)."""
    disagg = DisaggregatedEngine(_cfg(), _cfg())
    p = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    out = disagg.generate([[i + 1, i + 2, i + 3] for i in range(10)], p)
    assert len(out) == 10
    assert all(len(r.output_token_ids) == 4 for r in out)


def test_disagg_decode_pool_too_small_rejected_at_intake():
    # A prompt the decode pool can never admit must be rejected at
    # add_request — surfacing it later as a step() failure would take down
    # every other in-flight request.
    tiny_decode = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=2, max_blocks_per_seq=8),
        enable_prefix_caching=False)
    disagg = DisaggregatedEngine(_cfg(), tiny_decode)
    with pytest.raises(ValueError, match="decode pool capacity"):
        disagg.add_request(prompt_token_ids=[1, 2, 3, 4, 5, 6, 7, 8],
                           params=SamplingParams(max_tokens=4, ignore_eos=True))
    # nothing leaked into either pool
    assert not disagg.has_work()
    assert disagg.prefill.block_manager.num_seqs() == 0


@pytest.mark.parametrize("prefill_pipelined", [False, True],
                         ids=["decode-pool", "both-pools"])
def test_disagg_with_pipelined_windows_matches_colocated(prefill_pipelined):
    """The decode pool running the TPU-default decode shape (pipelined
    fused windows) must still match the plain colocated engine: adopted
    sequences enter windows with host-known first tokens, and the pool
    drains its in-flight window at the end.  A pipelined PREFILL pool
    leaves a first token on the device; the handoff reads it first."""
    colocated = Engine(_cfg())
    p = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)
    prompts = ["Hello world", "abcdefgh", "xy"]
    ref = colocated.generate(prompts, p)

    pipelined = _cfg(multi_step=4, pipeline_decode=True)
    disagg = DisaggregatedEngine(
        pipelined if prefill_pipelined else _cfg(), pipelined)
    out = disagg.generate(prompts, p)
    for r, o in zip(ref, out):
        assert r.output_token_ids == o.output_token_ids
    assert disagg.decode._pending_window is None
    assert disagg.prefill._pending_first is None
    assert disagg.prefill.block_manager.num_seqs() == 0
    assert disagg.decode.block_manager.num_seqs() == 0


def test_disagg_zombie_only_window_drains():
    """Regression (r3 review, CONFIRMED deadlock): when every row of the
    decode pool's in-flight pipelined window has finished (abort / EOS
    discovered at flush), the scheduler goes idle while the window flush is
    still owed.  step() gated on scheduler.has_work() never flushed it, so
    has_work() stayed True and generate()/the runner spun forever."""
    disagg = DisaggregatedEngine(
        _cfg(), _cfg(multi_step=4, pipeline_decode=True))
    p = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    rid = disagg.add_request(prompt_token_ids=[5, 6, 7], params=p)
    # run until the decode pool has a window in flight
    for _ in range(200):
        disagg.step()
        if disagg.decode._pending_window is not None:
            break
    assert disagg.decode._pending_window is not None
    # abort the only request: the in-flight window is now zombie-only
    assert disagg.abort_request(rid)
    for _ in range(50):
        if not disagg.has_work():
            break
        disagg.step()
    assert not disagg.has_work(), (
        "disagg engine failed to drain a zombie-only pending window")
    assert disagg.decode._pending_window is None
    assert disagg.decode.block_manager.num_seqs() == 0


def test_insert_rejects_kv_format_mismatch():
    """An int8 pool's pages must not scatter into a bf16 pool (raw codes
    would masquerade as values, scales silently dropped) — the mismatch is
    a loud ValueError instead."""
    import dataclasses

    import pytest

    from tpuserve.models.config import get_model_config
    from tpuserve.parallel.disagg import extract_seq_kv, insert_seq_kv
    from tpuserve.runtime.kv_cache import CacheConfig, create_kv_cache

    cfg = dataclasses.replace(get_model_config("tiny-qwen3"),
                              dtype="float32")
    ccfg = CacheConfig(block_size=4, num_blocks=16, max_blocks_per_seq=8)
    int8_cache = create_kv_cache(cfg, dataclasses.replace(ccfg, dtype="int8"))
    fp_cache = create_kv_cache(cfg, ccfg)
    pages, int8_cache = extract_seq_kv(int8_cache, [1, 2])
    with pytest.raises(ValueError, match="mismatch"):
        insert_seq_kv(fp_cache, pages, [3, 4])
    # matching formats round-trip fine
    int8_cache = insert_seq_kv(int8_cache, pages, [5, 6])


def test_disagg_sliding_window_migration_correct():
    """Windowed models migrate FULL prompt KV (the prefill side never
    window-releases — released tables would ship block 0's unrelated KV
    and poison the decode pool's prefix cache); decode output matches a
    colocated engine."""
    from tpuserve.parallel.disagg import DisaggregatedEngine
    from tpuserve.runtime.engine import Engine, EngineConfig
    from tpuserve.runtime.kv_cache import CacheConfig
    from tpuserve.runtime.request import SamplingParams
    from tpuserve.runtime.scheduler import SchedulerConfig

    cfg = EngineConfig(
        model="tiny-mistral",
        cache=CacheConfig(block_size=4, num_blocks=128,
                          max_blocks_per_seq=16, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=2),
        attn_impl="reference", pipeline_decode=False)
    prompts = [list(range(2, 22)), [7, 8, 9] * 5]   # 20 tokens > window 8
    p = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    # identical construction on both sides (DisaggregatedEngine builds its
    # own engines, so a model_cfg override here would compare different
    # param dtypes)
    plain = Engine(cfg).generate(prompts, p)
    d = DisaggregatedEngine(cfg, cfg)
    assert d.prefill.config.window_release is False
    assert d.decode.config.window_release is True
    outs = d.generate(prompts, p)
    for a, b in zip(plain, outs):
        assert a.output_token_ids == b.output_token_ids


def test_disagg_guided_choice_plan_follows_migration():
    """A guided_choice request whose FIRST token opens a committed
    canonical-suffix plan (non-ASCII choice: prefill emits a partial-rune
    byte token) must keep its plan across the prefill->decode handoff —
    dropping it strands dangling bytes in ctx and silently unconstrains
    the output (round-4 review finding)."""
    import json
    disagg = DisaggregatedEngine(_cfg(), _cfg())
    choices = ["ünïcödé", "Ωmega"]
    outs = disagg.generate(
        ["x"], [SamplingParams(max_tokens=40, temperature=0.0,
                               guided="choice",
                               guided_schema=json.dumps(choices))])
    (r,) = outs
    assert r.output_text in choices, r.output_text
    # the scenario is only exercised if prefill really opened a plan
    assert disagg.prefill.stats.guided_plans >= 1
    # plan state fully reclaimed on both pools
    assert not disagg.prefill._guided_plan and not disagg.decode._guided_plan
