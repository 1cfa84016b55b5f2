"""Ragged mixed prefill+decode batching (scheduler mixed mode): one fused
flat-token dispatch per cycle, no phase split.

Token-identity contract pinned here: with fixed seeds, mixed-mode output
streams are identical to the phase-split scheduler for greedy and for
seeded temperature sampling (Gumbel-argmax is robust to the sub-1e-5
numeric differences between differently-shaped executables).  Top-p/top-k
truncation inherits the pre-existing caveat that already separates the
phase-split engine's OWN chunked and batched prefill routes: the nucleus
cutoff amplifies ulp-level logit differences into different streams
(test_topp_routes_share_caveat demonstrates both).
"""

import dataclasses

import numpy as np
import pytest

from tpuserve.models.config import get_model_config
from tpuserve.runtime.engine import Engine, EngineConfig
from tpuserve.runtime.kv_cache import CacheConfig
from tpuserve.runtime.request import SamplingParams
from tpuserve.runtime.scheduler import SchedulerConfig


@pytest.fixture(scope="module")
def fp32_cfg():
    return dataclasses.replace(get_model_config("tiny-qwen3"),
                               dtype="float32")


def _engine(fp32_cfg, mixed, *, budget=16, prefix=False, max_seqs=4,
            num_blocks=128, multi_step=None, attn_impl="auto",
            kv_dtype="bfloat16", **sched_kw):
    """``kv_dtype="float32"`` where a test compares a route that attends
    over fresh K/V (batched prefill) with one that reads them back from the
    cache (chunked, mixed): through bf16 pages the two differ by ~1e-3,
    enough to flip a greedy near-tie of these random weights."""
    return Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=num_blocks,
                                       max_blocks_per_seq=24,
                                       dtype=kv_dtype),
                     scheduler=SchedulerConfig(
                         max_num_seqs=max_seqs, mixed_batching=mixed,
                         mixed_token_budget=budget, **sched_kw),
                     enable_prefix_caching=prefix, multi_step=multi_step,
                     attn_impl=attn_impl),
        model_cfg=fp32_cfg)


def _prompts(seed=3, lens=(20, 33, 7, 5)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=n).tolist() for n in lens]


def _ids(reqs):
    return [r.output_token_ids for r in reqs]


def test_mixed_greedy_token_identical(fp32_cfg):
    """Greedy streams are token-identical to the phase-split scheduler,
    across prompts that batch-prefill, chunk (longer than the mixed
    budget — multiple mixed steps per prompt), and ride decode rows."""
    prompts = _prompts()
    params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    ref = _engine(fp32_cfg, False,
                  kv_dtype="float32").generate(prompts, params)
    eng = _engine(fp32_cfg, True, kv_dtype="float32")
    mix = eng.generate(prompts, params)
    assert _ids(ref) == _ids(mix)
    assert eng.stats.num_mixed_steps > 0
    assert eng.block_manager.num_seqs() == 0


def test_mixed_seeded_sampling_token_identical(fp32_cfg):
    """Seeded temperature sampling matches the phase-split streams: the
    per-row (salt, step) key derivation is batch-composition-independent
    and Gumbel argmax tolerates cross-executable ulp noise."""
    prompts = _prompts()
    params = SamplingParams(max_tokens=8, temperature=1.1, seed=123,
                            ignore_eos=True)
    ref = _engine(fp32_cfg, False).generate(prompts, params)
    mix = _engine(fp32_cfg, True).generate(prompts, params)
    assert _ids(ref) == _ids(mix)


def test_mixed_greedy_with_sampling_extras(fp32_cfg):
    """Penalties / logit_bias / min_tokens all run through the same
    host-side per-step _sample in mixed mode — greedy streams stay
    identical."""
    prompts = _prompts(seed=5)
    params = SamplingParams(max_tokens=6, temperature=0.0,
                            repetition_penalty=1.3,
                            logit_bias={7: 4.0, 11: -6.0},
                            min_tokens=3, ignore_eos=True)
    ref = _engine(fp32_cfg, False).generate(prompts, params)
    mix = _engine(fp32_cfg, True).generate(prompts, params)
    assert _ids(ref) == _ids(mix)


def test_mixed_prefix_caching_identical(fp32_cfg):
    """The mixed path keeps the chunked path's prefix-cache compute skip
    (first chunk starts at the cached offset) with identical output."""
    prompts = _prompts(seed=9, lens=(22, 22, 6))
    params = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    eng = _engine(fp32_cfg, True, prefix=True)
    cold = eng.generate(prompts[:1], params)[0].output_token_ids
    hits_before = eng.block_manager.prefix_hits
    warm = eng.generate(prompts[:1], params)[0].output_token_ids
    assert warm == cold
    assert eng.block_manager.prefix_hits > hits_before


def test_topp_routes_share_caveat(fp32_cfg):
    """Documents the token-identity scope: top-p nucleus cutoffs amplify
    ulp-level logit differences between DIFFERENT prefill executables
    into different streams — already true between the phase-split
    engine's own batched and chunked prefill routes, so mixed mode
    inherits (not introduces) the caveat.  Mixed mode itself stays
    deterministic: same seed, same stream, every run."""
    prompts = _prompts()
    params = SamplingParams(max_tokens=6, temperature=0.8, top_p=0.9,
                            seed=7, ignore_eos=True)
    batched = _engine(fp32_cfg, False).generate(prompts, params)
    chunked = _engine(fp32_cfg, False,
                      prefill_chunk_size=8).generate(prompts, params)
    assert _ids(batched) != _ids(chunked)      # pre-existing caveat
    m1 = _engine(fp32_cfg, True).generate(prompts, params)
    m2 = _engine(fp32_cfg, True).generate(prompts, params)
    assert _ids(m1) == _ids(m2)                # mixed is deterministic


def test_mixed_guided_json_identical(fp32_cfg):
    """Guided decoding (FSM mask or substitution — both host-side per
    step) rides mixed steps unchanged."""
    prompts = _prompts(seed=11, lens=(18, 6))
    params = SamplingParams(max_tokens=10, temperature=0.0, guided="json")
    ref = _engine(fp32_cfg, False).generate(prompts, params)
    mix = _engine(fp32_cfg, True).generate(prompts, params)
    assert _ids(ref) == _ids(mix)


def test_mixed_with_fused_windows(fp32_cfg):
    """multi_step > 1 + mixed mode: prefill-free cycles run fused decode
    windows, mixed steps slot between them (flushing the pending window
    first) — streams still match the phase-split engine at the same
    window size."""
    prompts = _prompts(seed=13)
    params = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)
    ref = _engine(fp32_cfg, False, multi_step=4).generate(prompts, params)
    eng = _engine(fp32_cfg, True, multi_step=4)
    mix = eng.generate(prompts, params)
    assert _ids(ref) == _ids(mix)
    assert eng.stats.num_mixed_steps > 0


def test_mixed_pallas_interpret_matches_reference(fp32_cfg):
    """The ragged Pallas kernel serves the whole engine path under
    interpret mode: mixed generation with attn_impl=pallas must be
    token-identical (greedy) to the reference ragged trunk."""
    prompts = _prompts(seed=17, lens=(19, 6, 9))
    params = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    ref = _engine(fp32_cfg, True,
                  kv_dtype="float32").generate(prompts, params)
    pal = _engine(fp32_cfg, True, attn_impl="pallas",
                  kv_dtype="float32").generate(prompts, params)
    assert _ids(ref) == _ids(pal)


def test_mixed_preemption_recovers(fp32_cfg):
    """Decode-OOM preemption inside a mixed step re-prefills the victim
    through the mixed path itself; every stream still completes."""
    eng = _engine(fp32_cfg, True, num_blocks=12, max_seqs=3, budget=8)
    params = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    outs = eng.generate([[1, 2, 3, 4, 5, 6, 7, 8], [9, 8, 7, 6, 5],
                         [4, 4, 4]], params)
    for r in outs:
        assert len(r.output_token_ids) == 10
    assert eng.block_manager.num_seqs() == 0


def test_mixed_abort_mid_chunk_frees_blocks(fp32_cfg):
    """Aborting a request mid-way through its budget-chunked mixed
    prefill releases its blocks without poisoning the prefix cache."""
    eng = _engine(fp32_cfg, True, budget=8, prefix=True)
    free0 = eng.block_manager.num_free_blocks
    prompt = list(range(1, 25))
    rid = eng.add_request(prompt_token_ids=prompt,
                          params=SamplingParams(max_tokens=2,
                                                ignore_eos=True))
    eng.step()                        # first mixed step: partial prefill
    assert eng.block_manager.num_free_blocks < free0
    assert eng.abort_request(rid)
    assert eng.block_manager.num_free_blocks == free0
    shared, cached = eng.block_manager.lookup_prefix(prompt)
    assert cached == 0


def test_padding_waste_stats_tracked(fp32_cfg):
    """The per-step padded/actual token counters behind the
    tpuserve_step_padded/actual_tokens gauges: populated on every path,
    and mixed mode's flat bucket wastes no more than the phase-split
    (batch x length) grid on the same workload."""
    prompts = _prompts(seed=19)
    params = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    ref = _engine(fp32_cfg, False)
    ref.generate(prompts, params)
    mix = _engine(fp32_cfg, True)
    mix.generate(prompts, params)
    for e in (ref, mix):
        assert e.stats.actual_tokens_total > 0
        assert e.stats.padded_tokens_total >= e.stats.actual_tokens_total
        assert e.stats.step_padded_tokens >= e.stats.step_actual_tokens
    assert mix.stats.padded_tokens_total <= ref.stats.padded_tokens_total


def test_metrics_expose_padding_gauges():
    from tpuserve.server.metrics import ServerMetrics
    m = ServerMetrics("test-model")
    m.step_padded_tokens.set(64)
    m.step_actual_tokens.set(41)
    m.padded_tokens_total.inc(64)
    m.actual_tokens_total.inc(41)
    m.mixed_steps.inc()
    text = m.render().decode()
    assert "tpuserve_step_padded_tokens" in text
    assert "tpuserve_step_actual_tokens" in text
    assert "tpuserve_padded_tokens_total" in text
    assert "tpuserve_mixed_steps" in text


def test_mixed_warmup_compiles_flat_buckets(fp32_cfg):
    """warmup(mixed_buckets=...) pre-compiles the ragged trunk without
    disturbing the cache, and serving works immediately after."""
    eng = _engine(fp32_cfg, True)
    eng.warmup(mixed_buckets=[16, 32], sample_modes=("greedy",))
    params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    outs = eng.generate(_prompts(seed=21, lens=(10, 6)), params)
    assert all(len(r.output_token_ids) == 4 for r in outs)


def test_mixed_multilora_token_identical(tmp_path_factory):
    """Mixed steps carry per-ROW one-hot adapter weights over the flat
    stream — adapter/base streams must match the phase-split multi-LoRA
    engine exactly."""
    import dataclasses as _dc

    from tests.test_lora import _qproj_tensors, _write_adapter
    from tpuserve.models.config import get_model_config
    root = tmp_path_factory.mktemp("mixed_adapters")
    rng = np.random.default_rng(7)
    _write_adapter(root / "alpha", _qproj_tensors(rng, li=0, r=4))
    mc32 = _dc.replace(get_model_config("tiny-qwen3"), dtype="float32")

    def run(mixed):
        eng = Engine(
            EngineConfig(model="tiny-qwen3",
                         lora_modules={"alpha": str(root / "alpha")},
                         cache=CacheConfig(block_size=4, num_blocks=128,
                                           max_blocks_per_seq=16),
                         scheduler=SchedulerConfig(
                             max_num_seqs=4, mixed_batching=mixed,
                             mixed_token_budget=16)),
            model_cfg=mc32)
        prompts = _prompts(seed=23, lens=(14, 6, 9))
        params = SamplingParams(max_tokens=6, temperature=0.0,
                                ignore_eos=True)
        rids = [eng.add_request(prompt_token_ids=p, params=params,
                                adapter=a)
                for p, a in zip(prompts, ["alpha", None, "alpha"])]
        outs = {}
        while eng.has_work():
            for o in eng.step():
                outs.setdefault(o.request_id, []).extend(o.new_token_ids)
        return [outs[r] for r in rids], eng

    ref, _ = run(False)
    mix, eng = run(True)
    assert ref == mix
    assert eng.stats.num_mixed_steps > 0
