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


# ---- the route is observed: where a decode step is bound by its weights,
# ---- the decode rows ride the prompt dispatches (ISSUE 53) ---------------

# the pool each benchmark cell's engine sized on the chip (blocks of 32
# tokens; its runs' "server built" lines), the seats it serves, and the
# route its engine observes there, with the reason's first words
CELL_ROUTES = [
    ("qwen3-0.6b.batch", 3821, "phase_split", "a decode step is bound by its K/V"),
    ("mistral-7b-l16.batch", 3678, "mixed", "a decode step is bound by its weights"),
    ("falcon-h1-34b-l6.reason", 7785, "phase_split", "recurrent state"),
    ("mellum2-12b-l12.batch", 5450, "mixed", "a decode step is bound by its weights"),
    ("k-exaone-236b-ep8-l8.reason", 3108, "mixed", "a decode step is bound by its weights"),
    ("olmo-hybrid-7b-l16.reason", 2471, "phase_split", "recurrent state"),
    ("openpangu-ultra-718b-ep16-l7.reason", 10096, "mixed", "a decode step is bound by its weights"),
    # two latent layers, and they do not decide it: the state is asked first
    ("ling-3.0-flash-vl-ep8-l12.reason", 16512, "phase_split", "recurrent state"),
]


@pytest.mark.parametrize("cell,num_blocks,route,why", CELL_ROUTES,
                         ids=[c[0] for c in CELL_ROUTES])
def test_route_is_observed_not_configured(cell, num_blocks, route, why):
    """Which of the benchmark's eight engines ride and which do not, and
    why: the engine's own functions on each configuration AS IT RUNS
    (every layer, the share of experts and vocabulary it holds; shapes
    alone, nothing is allocated) beside the pool the chip left it."""
    import jax

    from benchmark.harness import plan, session
    from tpuserve.models.weights import init_params
    from tpuserve.runtime import engine as engine_mod
    from tpuserve.runtime.kv_cache import create_kv_cache

    loaded = plan.load_cell(cell, plan.load_benchmark())
    cfg = get_model_config(session.register_configuration(loaded))
    args = loaded.config["server_args"]
    seats = (int(args[args.index("--max-num-seqs") + 1])
             if "--max-num-seqs" in args else SchedulerConfig().max_num_seqs)
    cache_cfg = CacheConfig(block_size=32, num_blocks=num_blocks,
                            max_blocks_per_seq=128, dtype="bfloat16")
    params = jax.eval_shape(lambda: init_params(cfg, 0))
    pages = jax.eval_shape(lambda: create_kv_cache(cfg, cache_cfg))
    weights, kv = engine_mod.decode_step_bytes(params, pages, cfg, cache_cfg,
                                               seats)
    got = engine_mod.decode_route(
        weights, kv, excluded=engine_mod.route_excluded(
            cfg, staged=False, mesh=False, packed=True))
    assert (got["route"], got["rides"]) == (route, route == "mixed"), got
    assert got["why"].startswith(why), got
    assert (got["weight_bytes"], got["kv_bytes"]) == (weights, kv)
    # no verdict stands on an edge: the two numbers are a factor of 1.5
    # apart or more wherever they decide
    if why.startswith("a decode step"):
        assert max(weights, kv) > 1.5 * min(weights, kv), got
        assert weights > engine_mod.HOST_BOUND_WEIGHT_BYTES


def _bf16_engine(monkeypatch, *, floor=0, model="tiny-qwen3", num_blocks=64,
                 max_blocks_per_seq=16, max_seqs=4, **kw):
    """A tiny engine whose pages are in its model's dtype (what
    ``Engine._packed_prefill`` asks).  ``floor``: the weights under which
    a decode step counts as bound by the host; a tiny model's are, so the
    tests of the observation steer that here, in the test."""
    from tpuserve.runtime import engine as engine_mod
    monkeypatch.setattr(engine_mod, "HOST_BOUND_WEIGHT_BYTES", floor)
    sched = kw.pop("scheduler", {})
    mesh = kw.pop("mesh", None)
    if mesh:
        from tpuserve.parallel.mesh import MeshConfig, make_mesh
        mesh = make_mesh(MeshConfig(**mesh))
    return Engine(EngineConfig(
        model=model,
        cache=CacheConfig(block_size=4, num_blocks=num_blocks,
                          max_blocks_per_seq=max_blocks_per_seq,
                          dtype=kw.pop("cache_dtype", "bfloat16")),
        scheduler=SchedulerConfig(max_num_seqs=max_seqs,
                                  mixed_token_budget=16, **sched), **kw),
                  mesh=mesh)


@pytest.mark.parametrize("build,rides,why", [
    ({}, True, "a decode step is bound by its weights"),
    # a pool whose seats can make a step read more K/V than weights
    ({"num_blocks": 8192, "max_blocks_per_seq": 1024, "max_seqs": 8}, False,
     "a decode step is bound by its K/V read"),
    # weights read faster than a dispatch is launched (no steering: every
    # tiny engine of this suite stays on the phase split for this reason)
    ({"floor": 1 << 30}, False, "a decode step's weights are read in less"),
    ({"model": "tiny-falcon-h1", "cache_dtype": "float32"}, False,
     "recurrent state"),
    # latent attention is observed like the rest (PR 58): one latent vector
    # a token a layer is a small K/V read beside any weights
    ({"model": "tiny-pangu", "cache_dtype": "float32"}, True,
     "a decode step is bound by its weights"),
    ({"model": "tiny-pangu", "cache_dtype": "float32", "floor": 1 << 30},
     False, "a decode step's weights are read in less"),
    # latent layers beside recurrent ones: the state is asked first
    ({"model": "tiny-ling-hybrid", "cache_dtype": "float32"}, False,
     "recurrent state"),
    ({"cache_dtype": "int8"}, False, "pages narrower"),
    ({"mesh": {"tp": 2}}, False, "the ragged kernel has no tp"),
    ({"mesh": {"pp": 2}}, False, "the ragged trunk is neither"),
    # the option still forces it, whatever was observed
    ({"floor": 1 << 30, "scheduler": {"mixed_batching": True}}, True,
     "forced by mixed_batching"),
], ids=["weights-bound", "kv-bound", "host-bound", "recurrent", "mla",
        "mla-host-bound", "mla-beside-state", "int8-kv", "tp-mesh", "pp",
        "forced"])
def test_the_engine_builds_the_scheduler_it_observed(monkeypatch, build,
                                                     rides, why):
    eng = _bf16_engine(monkeypatch, **build)
    route = eng._route
    assert route["rides"] is rides and route["why"].startswith(why), route
    assert eng.scheduler.cfg.mixed_batching is rides
    # shown on /debug/engine, with its two numbers
    shown = eng.flight.engine_snapshot()["engine"]
    assert shown["decode_route"] == route
    assert shown["mixed_batching"] is rides
    if route["weight_bytes"] is not None:
        assert route["weight_bytes"] > 0 and route["kv_bytes"] > 0


def _drive(eng, prompts, max_tokens, temperature=0.0):
    """Every request at once; ``(tokens by request, scheduled batches)``."""
    batches = []
    real = eng.scheduler.schedule

    def schedule():
        running = list(eng.scheduler.running)
        batch = real()
        if batch is not None:
            batches.append((batch.kind, list(batch.requests), running,
                            [(r.request_id, n)
                             for r, n in batch.prefill_chunks]))
        return batch
    eng.scheduler.schedule = schedule
    for i, (p, n) in enumerate(zip(prompts, max_tokens)):
        eng.add_request(prompt_token_ids=p, request_id=f"r{i}",
                        params=SamplingParams(
                            max_tokens=n, temperature=temperature,
                            seed=100 + i if temperature else None,
                            ignore_eos=True))
    got, steps = {}, 0
    while eng.has_work():
        for o in eng.step():
            got.setdefault(o.request_id, []).extend(o.new_token_ids)
        steps += 1
        assert steps < 5000
    assert eng.block_manager.num_seqs() == 0
    return got, batches


RIDE_LENS = [20, 33, 7, 5, 41, 12, 9, 27, 3, 18]
RIDE_OUTS = [5, 9, 17, 3, 11, 8, 14, 6, 10, 7]


def test_every_cycle_with_prompt_work_carries_every_running_row(monkeypatch):
    """With the route on, a cycle that has admissible prompt work is ONE
    mixed step that carries all running rows (never a prefill or a chunk
    of the phase split), and the ridden tokens are counted."""
    eng = _bf16_engine(monkeypatch)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in RIDE_LENS]
    got, batches = _drive(eng, prompts, RIDE_OUTS)
    assert {k: len(v) for k, v in got.items()} == {
        f"r{i}": n for i, n in enumerate(RIDE_OUTS)}
    assert {kind for kind, *_ in batches} == {"mixed", "decode"}
    carried = 0
    for kind, reqs, running, chunks in batches:
        assert reqs == running           # all running rows, whichever kind
        if kind == "mixed":
            assert chunks
            carried += sum(n for _, n in chunks)
    assert carried == sum(RIDE_LENS)     # every prompt token rode a mixed step
    st = eng.stats
    assert 0 < st.decode_tokens_ridden < st.generated_tokens
    mixed = [s for s in eng.flight.steps_snapshot(limit=1 << 20)
             if s["kind"] == "mixed"]
    assert len(mixed) == st.num_mixed_steps
    assert sum(s["ridden_tokens"] for s in mixed) == st.decode_tokens_ridden
    assert all("ridden_tokens" not in s
               for s in eng.flight.steps_snapshot(limit=1 << 20)
               if s["kind"] != "mixed")


def test_a_recurrent_engine_never_schedules_a_mixed_step(monkeypatch):
    eng = _bf16_engine(monkeypatch, model="tiny-falcon-h1",
                       cache_dtype="float32")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in RIDE_LENS[:6]]
    _, batches = _drive(eng, prompts, RIDE_OUTS[:6])
    assert "mixed" not in {kind for kind, *_ in batches}
    assert eng.stats.num_mixed_steps == eng.stats.decode_tokens_ridden == 0


def _family_engine(model, mixed, *, multi_step, pipeline, share=None,
                   num_blocks=160):
    """A float32 engine on float32 pages (the routes compared attend the
    same K/V to the last bit), four seats, a budget of 16 rows."""
    cfg = dataclasses.replace(get_model_config(model), dtype="float32")
    if share:
        cfg = dataclasses.replace(cfg, moe_experts_held=share)
    return Engine(EngineConfig(
        model=model,
        cache=CacheConfig(block_size=4, num_blocks=num_blocks,
                          max_blocks_per_seq=24, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=4, mixed_batching=mixed,
                                  mixed_token_budget=16),
        multi_step=multi_step, pipeline_decode=pipeline), model_cfg=cfg)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "seeded"])
@pytest.mark.parametrize("multi_step,pipeline", [(1, False), (1, True),
                                                 (4, True)],
                         ids=["sync", "pipelined", "pipelined-windows"])
@pytest.mark.parametrize("model,share", [
    ("tiny-qwen3", None),
    # windowed and full layers mixed, sparse experts in every layer
    ("tiny-mellum2", None),
    # a dense layer, windowed layers that rotate, a share of the experts
    # held beside a shared one
    ("tiny-k-exaone", 8)], ids=["dense", "mellum2-shaped",
                                "k-exaone-share-shaped"])
def test_riding_rows_get_the_phase_splits_tokens(model, share, multi_step,
                                                 pipeline, temperature):
    """Ten requests on four seats: prompts cut at the budget, decode rows
    that ride them, rows that take their token on the device from a window
    in flight, from an earlier mixed step and from a prompt just completed
    (``pipelined``): the same tokens as the phase split, request by
    request."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in RIDE_LENS]

    def run(mixed):
        eng = _family_engine(model, mixed, multi_step=multi_step,
                             pipeline=pipeline, share=share)
        return _drive(eng, prompts, RIDE_OUTS, temperature)[0], eng

    ref, _ = run(False)
    mix, eng = run(True)
    assert mix == ref
    assert eng.stats.num_mixed_steps > 0
    assert eng.stats.decode_tokens_ridden > 0
    if multi_step > 1:
        # nothing was read before the dispatch that followed it (the
        # single-step decode path reads a first token before it runs)
        assert eng.stats.prefill_first_token_flushed_early == 0
        assert eng.stats.prefill_first_token_deferred > 0


def test_a_short_pool_reads_first_and_evicts_as_decode_does():
    """Where the pool cannot hold a slot past what is in flight for every
    row, the mixed step reads its records first and pre-empts: the same
    tokens, later."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in RIDE_LENS]

    def run(mixed, num_blocks):
        eng = _family_engine("tiny-qwen3", mixed, multi_step=4,
                             pipeline=True, num_blocks=num_blocks)
        return _drive(eng, prompts, RIDE_OUTS)[0], eng

    ref, _ = run(False, 160)
    mix, eng = run(True, 20)
    assert mix == ref
    assert eng.stats.preemptions > 0


def test_a_warm_riding_engine_compiles_nothing_in_service(monkeypatch):
    """``compiles_in_window`` has a limit of 0: what a riding engine
    dispatches (mixed rungs, the decode ladder, the samplers at the mixed
    step's width, the selects that take a row's token on the device from
    a window's tail, from a mixed step's tokens and from a completed
    prompt's) is warmed by ``Engine.warmup`` itself."""
    from benchmark.harness.meter import CompileMeter
    meter = CompileMeter()
    eng = _bf16_engine(monkeypatch, num_blocks=160, max_blocks_per_seq=24,
                       multi_step=4, pipeline_decode=True)
    assert eng._route["rides"]
    # as session.build calls it: the phase split's shapes are handed over
    # and a riding engine warms its own ladder in their place
    eng.warmup(sample_modes=("greedy",), prefill_buckets=[(1, 32), (4, 64)],
               chunk_buckets=[16], decode_buckets=[4])
    before = meter.snapshot()["requests"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, size=n).tolist() for n in RIDE_LENS]
    _, batches = _drive(eng, prompts, RIDE_OUTS)
    assert {kind for kind, *_ in batches} == {"mixed", "decode"}
    assert eng.stats.prefill_first_token_flushed_early == 0
    assert meter.snapshot()["requests"] == before
