"""Packed batched prefill: one flat token axis through the ragged trunk
gives what the (batch x length) route gives, is bucketed on a ladder the
warm-up covers, and is taken only where the engine observes that it can."""

import dataclasses
import itertools

import numpy as np
import pytest

from tests.test_lora import _qproj_tensors, _write_adapter
from tpuserve.models.config import get_model_config
from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SchedulerConfig
from tpuserve.runtime.request import SamplingParams
from tpuserve.runtime.scheduler import packed_prefill_bucket

# float32 weights AND pages: the two routes then differ by summation
# order alone, so logits compare tightly and greedy tokens exactly
MC32 = dataclasses.replace(get_model_config("tiny-qwen3"), dtype="float32")
GREEDY = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)


def _engine(packed, *, attn_impl="reference", **kw):
    eng = Engine(EngineConfig(
        model="tiny-qwen3", attn_impl=attn_impl,
        cache=CacheConfig(block_size=4, num_blocks=256,
                          max_blocks_per_seq=32, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=16, max_prefill_seqs=8,
                                  min_prefill_bucket=8, min_decode_bucket=2,
                                  max_prefill_tokens=512),
        **kw), model_cfg=MC32)
    assert eng._packed_prefill            # observed: no mesh, f32 on f32
    eng._packed_prefill = packed          # the test steers the (B, L) side
    return eng


def _record_prefills(eng):
    """[(logits of the real rows, sampled tokens, dispatched shape)] of
    every batched prefill the engine runs from now on."""
    seen = []
    defer = eng._defer_first

    def spy(logits, reqs, B):
        # an engine that does not pipeline (the CPU's default) reads the
        # first tokens in the same call
        outs = defer(logits, reqs, B)
        if eng._step_kind == "prefill":
            seen.append((np.asarray(logits, np.float32)[:len(reqs)],
                         np.asarray([r.output_token_ids[-1] for r in reqs]),
                         eng.stats.step_padded_tokens))
        return outs
    eng._defer_first = spy
    return seen


def _drain(eng):
    outs = {}
    while eng.has_work():
        for o in eng.step():
            outs.setdefault(o.request_id, []).extend(o.new_token_ids)
    return outs


def _prompts(rng, lens):
    return [rng.integers(1, 250, n).tolist() for n in lens]


def _single(eng, rng):
    eng.add_request(prompt_token_ids=_prompts(rng, [21])[0], params=GREEDY)
    return 1


def _pair_with_prefix_hit(eng, rng):
    """The second prompt of the pair shares 12 cached tokens (three full
    blocks) with a request served before: the packed route starts it at
    the cached offset."""
    first, other, tail = _prompts(rng, [30, 9, 17])
    eng.add_request(prompt_token_ids=first, params=GREEDY)
    _drain(eng)
    eng.add_request(prompt_token_ids=other, params=GREEDY)
    eng.add_request(prompt_token_ids=first[:12] + tail, params=GREEDY)
    return 2


def _triple_with_lora_row(eng, rng):
    for p, ad in zip(_prompts(rng, [40, 5, 18]), (None, "alpha", None)):
        eng.add_request(prompt_token_ids=p, params=GREEDY, adapter=ad)
    return 3


def _eight_with_reprefill(eng, rng):
    """Two streams decode, the later one is preempted and re-prefills its
    prompt plus what it generated at the head of a batch of eight."""
    lens = [11, 26, 3, 33, 8, 47, 15, 29, 6]
    prompts = _prompts(rng, lens)
    long = dataclasses.replace(GREEDY, max_tokens=12)
    for p in prompts[:2]:
        eng.add_request(prompt_token_ids=p, params=long)
    for _ in range(4):
        eng.step()
    victim = eng.scheduler.preempt_last()
    assert victim is not None and victim.output_token_ids
    for p in prompts[2:]:
        eng.add_request(prompt_token_ids=p, params=GREEDY)
    return 8


@pytest.fixture(scope="module")
def lora_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("adapters") / "alpha"
    _write_adapter(root, _qproj_tensors(np.random.default_rng(7), li=0, r=4))
    return str(root)


@pytest.mark.parametrize("scenario,attn_impl", [
    (_single, "reference"), (_pair_with_prefix_hit, "reference"),
    (_triple_with_lora_row, "reference"), (_eight_with_reprefill, "reference"),
    # the Pallas kernels in interpret mode: flash (B, L) against ragged
    (_pair_with_prefix_hit, "pallas"), (_eight_with_reprefill, "pallas"),
], ids=lambda v: v if isinstance(v, str) else v.__name__.strip("_"))
def test_packed_prefill_matches_the_batch_by_length_route(
        scenario, attn_impl, lora_dir):
    runs = {}
    for packed in (False, True):
        # without the prefix cache a preempted request re-prefills in the
        # batch (with it, it finds its own blocks and takes the chunk route)
        kw = {"enable_prefix_caching": scenario is _pair_with_prefix_hit}
        if scenario is _triple_with_lora_row:
            kw["lora_modules"] = {"alpha": lora_dir}
        eng = _engine(packed, attn_impl=attn_impl, **kw)
        seen = _record_prefills(eng)
        want_batch = scenario(eng, np.random.default_rng(3))
        outs = _drain(eng)
        runs[packed] = (seen, outs, eng.stats, want_batch)
    (bl, bl_outs, bl_stats, n), (pk, pk_outs, pk_stats, _) = \
        runs[False], runs[True]
    assert len(bl) == len(pk) and len(pk[-1][0]) == n
    for (l0, t0, _), (l1, t1, _) in zip(bl, pk):
        np.testing.assert_allclose(l1, l0, atol=2e-4, rtol=2e-4)
        np.testing.assert_array_equal(t1, t0)
    assert list(pk_outs.values()) == list(bl_outs.values())
    # the counters: every batched prefill went out packed, on fewer slots
    assert bl_stats.prefill_packed_steps == 0
    assert pk_stats.prefill_packed_steps == pk_stats.num_prefill_steps \
        == len(pk)
    assert pk_stats.prefill_tokens_total <= bl_stats.prefill_tokens_total
    if n > 1:
        assert pk_stats.prefill_padded_tokens_total \
            < bl_stats.prefill_padded_tokens_total
    blk = 8                                   # ragged_block() off the TPU
    assert all(shape == packed_prefill_bucket(shape, blk)
               for _, _, shape in pk)


def test_a_prefix_hit_starts_at_the_cached_offset():
    eng = _engine(True, enable_prefix_caching=True)
    _pair_with_prefix_hit(eng, np.random.default_rng(3))
    before = eng.stats.prefill_tokens_total
    eng.step()
    # 9 + (12 cached + 17): the cached twelve are not computed again
    assert eng.stats.prefill_tokens_total - before == 9 + 17
    assert eng.stats.step_ctx_tokens == 9 + 29
    assert eng.stats.step_padded_tokens == 48      # 16 + 24 rows -> 48


# ---- every dispatch lands on a warmed program ---------------------------

def _pool_lengths():
    """The benchmark pool's prompt lengths (batch-closed.json), and what a
    preempted request re-prefills on top."""
    import os

    from benchmark.harness import traffic
    mix = traffic.load_mix(os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "traffic",
        "batch-closed.json"))
    return mix, sorted({p for p, _ in traffic.size_pool(mix, mix["pool"])})


def test_every_admissible_batch_lands_on_a_warmed_rung(monkeypatch):
    """The admission rule (block_manager.admit_prefill: one power-of-two
    bucket of the longest, bucket x picked <= max_prefill_tokens) over the
    benchmark pool's lengths: whatever batch it can form, the packed
    dispatch's T is a rung that ``Engine.warmup`` warmed from the list
    ``benchmark/harness/shapes.py`` hands it, at the one descriptor width."""
    from benchmark.harness import traffic
    from benchmark.harness.shapes import warm_shapes
    from tpuserve.utils import next_power_of_2
    mix, lengths = _pool_lengths()
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=32, num_blocks=64,
                          max_blocks_per_seq=128)))
    assert eng._packed_prefill
    blk = 128                                   # the chip's ragged block
    monkeypatch.setattr(eng, "_ragged_blk", blk)
    warmed = []
    monkeypatch.setattr(
        eng, "_exec_forward_ragged",
        lambda tokens, *a, kind="mixed", **kw: warmed.append(
            (kind, tokens.shape[0], a[4].shape[0]))
        or (np.zeros((a[4].shape[0], 8), np.float32), eng.kv_cache))
    monkeypatch.setattr(eng, "_warm_sampling", lambda *a, **k: None)
    shapes = warm_shapes(eng.scheduler,
                         traffic.bounds(mix, mix["pool"]))
    eng.warmup(sample_modes=("greedy",), **shapes)
    assert {k for k, _, _ in warmed} == {"prefill"}
    assert {w for _, _, w in warmed} == {8}
    rungs = {t for _, t, _ in warmed}
    assert len(rungs) == 13 and max(rungs) == 8192
    cfg = eng.scheduler.cfg
    batched = [n for n in lengths if n <= cfg.prefill_chunk_size]
    # a re-prefill carries up to output_max generated tokens too
    batched += [min(n + mix["output"]["max"], cfg.prefill_chunk_size)
                for n in batched[::8]]
    align = lambda n: -(-n // blk) * blk
    # admission is greedy over the queue's head: every prefix of every
    # ordering of up to max_prefill_seqs prompts is a batch it can form.
    # Sizes enter only through their aligned rows and their bucket, so
    # one length per (rows, bucket) class covers the pool exhaustively.
    classes = sorted({(align(n), max(next_power_of_2(n),
                                     cfg.min_prefill_bucket))
                      for n in batched})
    seen_t = set()
    for k in range(1, cfg.max_prefill_seqs + 1):
        for combo in itertools.combinations_with_replacement(classes, k):
            bucket = max(b for _, b in combo)
            if k > 1 and bucket * k > cfg.max_prefill_tokens:
                continue                        # admission stops earlier
            seen_t.add(packed_prefill_bucket(sum(r for r, _ in combo), blk))
    assert seen_t <= rungs
    assert max(seen_t) == 8192 and min(seen_t) == 128


def test_warmup_without_batched_prefill_warms_no_packed_rung(monkeypatch):
    eng = _engine(True)
    kinds = []
    real = eng._exec_forward_ragged
    monkeypatch.setattr(
        eng, "_exec_forward_ragged",
        lambda *a, kind="mixed", **kw: kinds.append(kind)
        or real(*a, kind=kind, **kw))
    eng.warmup(prefill_buckets=[], decode_buckets=[2],
               sample_modes=("greedy",))
    assert kinds == []
    eng.warmup(prefill_buckets=[(2, 16)], decode_buckets=[2],
               sample_modes=("greedy",))
    # 2 prompts of up to 16 tokens: rungs 8, 16, 32 (blocks of 8), twice
    assert kinds == ["prefill"] * 6
    assert sorted(k[1][0][0] for k in eng.devprof.ladder
                  if k[0] == "prefill") == [8, 16, 32]


# ---- the route is chosen by what the engine observes ---------------------

def _small_engine(model="tiny-qwen3", cache_dtype="bfloat16", **mesh):
    from tpuserve.parallel.mesh import MeshConfig, make_mesh
    return Engine(EngineConfig(
        model=model,
        cache=CacheConfig(block_size=4, num_blocks=128,
                          max_blocks_per_seq=16, dtype=cache_dtype),
        scheduler=SchedulerConfig(max_num_seqs=8, min_prefill_bucket=8,
                                  min_decode_bucket=2)),
        mesh=make_mesh(MeshConfig(**mesh)) if mesh else None)


@pytest.mark.parametrize("build,packed", [
    ({}, True),
    ({"tp": 2}, False),
    ({"pp": 2}, False),
    # latent attention packs too since PR 50: the packed route attends
    # the latent pages it just wrote, in the absorbed form
    ({"model": "tiny-deepseek"}, True),
    ({"cache_dtype": "int8"}, False),
    # float32 weights on bf16 pages: the (B, L) route attends the fresh
    # f32 K/V, the pages hold them rounded
    ({"model": "tiny-mistral"}, False),
], ids=["single-chip", "tp-mesh", "pp", "mla", "int8-kv", "narrower-pages"])
def test_route_is_observed_not_configured(build, packed):
    eng = _small_engine(**build)
    assert eng._packed_prefill is packed
    kinds = []
    for hook in ("_exec_prefill", "_exec_forward_ragged"):
        real = getattr(eng, hook)
        setattr(eng, hook, lambda *a, _r=real, _h=hook, **kw:
                kinds.append((_h, a[0].shape)) or _r(*a, **kw))
    rng = np.random.default_rng(0)
    for p in _prompts(rng, [13, 5, 9]):
        eng.add_request(prompt_token_ids=p,
                        params=dataclasses.replace(GREEDY, max_tokens=2))
    _drain(eng)
    if packed:
        assert kinds == [("_exec_forward_ragged", (48,))]   # 16 + 8 + 16
    else:
        assert kinds == [("_exec_prefill", (4, 16))]
    assert eng.stats.prefill_packed_steps == int(packed)
    assert eng.stats.prefill_tokens_total == 27
    assert eng.stats.prefill_padded_tokens_total == (48 if packed else 64)


def test_bucket_ladder():
    got = sorted({packed_prefill_bucket(r, 128) for r in range(1, 8193)})
    assert got == [128, 256, 512, 768, 1024, 1280, 1536, 1792, 2048, 3072,
                   4096, 6144, 8192]
    for r in range(1, 8193):
        t = packed_prefill_bucket(-(-r // 128) * 128, 128)
        assert t >= r and t % 128 == 0
        # the ladder's own padding is under a third of a dispatch
        assert (t - -(-r // 128) * 128) * 3 < t or r <= 128


# ---- the K and V of a packed prefill go out a page at a time ------------

def test_the_page_write_serves_the_row_scatters_tokens(row_scatter_only):
    """A packed batch of three prompts (one ends mid-page, one fills its
    pages exactly) and eight decode steps over the written pages: greedy
    tokens with the page write and with the row scatter are the same."""
    params = dataclasses.replace(GREEDY, max_tokens=9)
    runs = []
    for by_page in (True, False):
        if not by_page:
            row_scatter_only()
        eng = _engine(True, attn_impl="pallas")
        for p in _prompts(np.random.default_rng(11), [21, 8, 34]):
            eng.add_request(prompt_token_ids=p, params=params)
        outs = _drain(eng)
        assert eng.stats.prefill_packed_steps == 1
        assert eng.stats.prefill_tokens_total == 21 + 8 + 34
        # the counter: every prompt token by page, or none
        assert eng.stats.prefill_kv_tokens_paged_total == 63 * by_page
        runs.append(list(outs.values()))
    assert all(len(toks) == 9 for toks in runs[0])
    assert runs[0] == runs[1]
