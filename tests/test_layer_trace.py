"""A trunk traces a layer once a KIND, not once a layer.

Each trunk of ``tpuserve/models/transformer.py`` hands its per-layer body
to a function under its own ``jax.jit`` with ``cfg.layer_like(li)`` as the
static layer index (see "One layer of each trunk" there).  Held here, on
the CPU at tiny sizes: what ``layer_like`` groups, that a traced trunk runs
the body once a kind and calls it once a layer (the two host counts the
engine logs and ``/metrics`` exports), that the lowered module holds one
private function a kind, and that a trunk built this way returns, bit for
bit, what the same body gives when called unwrapped, layer by layer.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_scopes import trunk_programs
from tpuserve.models import transformer
from tpuserve.models.config import get_model_config
from tpuserve.models.weights import init_params
from tpuserve.ops.attention import PAD_SLOT
from tpuserve.runtime.kv_cache import (CacheConfig, create_kv_cache,
                                       create_ssm_state)

# model -> (layers, kinds): the six families of the benchmark, then latent
# attention behind a dense first layer (MLA), rope by layer (Gemma 3) and
# a bank of adapters (LoRA: the kinds are the base model's)
MODELS = {
    "tiny-qwen3": (2, 1),
    "tiny-mistral": (2, 1),
    "tiny-falcon-h1": (2, 1),
    "tiny-mellum2": (8, 2),
    "tiny-olmo-hybrid": (8, 2),
    "tiny-k-exaone": (8, 3),
    "tiny-deepseek": (3, 2),
    "tiny-gemma3": (6, 2),
    "tiny-qwen3+lora": (2, 1),
}
# program -> (the body it counts under, its jitted layer function)
PROGRAMS = {
    "decode_multi": ("decode", "_decode_layer"),
    "forward_ragged": ("ragged", "_ragged_layer"),
    "prefill_chunk": ("chunk", "_chunk_layer"),
}
LAYER_FNS = ("_prefill_layer", "_chunk_layer", "_decode_layer",
             "_ragged_layer", "_nocache_layer")


def config_of(model: str):
    return get_model_config(model.split("+")[0])


def with_lora(params, adapters: int = 2, rank: int = 4):
    """The tree with a bank of ``adapters`` on every layer's q and v
    projections (``weights.load_lora_stack``'s layout)."""
    key = jax.random.PRNGKey(7)
    layers = []
    for lp in params["layers"]:
        lp = dict(lp)
        for name in ("q_proj", "v_proj"):
            k_in, k_out = lp[name]["kernel"].shape
            key, a, b = jax.random.split(key, 3)
            lp[name] = dict(lp[name], lora={
                "A": 0.1 * jax.random.normal(a, (adapters, k_in, rank)),
                "B": 0.1 * jax.random.normal(b, (adapters, rank, k_out))})
        layers.append(lp)
    return dict(params, layers=layers)


def counts():
    return dict(transformer.LAYER_TRACES), dict(transformer.LAYER_CALLS)


def moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


# ---- what layer_like groups ----------------------------------------------

@pytest.mark.parametrize("model", sorted(MODELS))
def test_layer_like_is_the_first_layer_of_the_same_kind(model):
    cfg = config_of(model)
    layers, kinds = MODELS[model]
    assert cfg.num_layers == layers

    def answers(i):
        return (cfg.layer_mixer(i), cfg.layer_window(i),
                cfg.layer_rotates(i), cfg.layer_rope(i), cfg.layer_yarn(i),
                cfg.moe_layer_is_dense(i))

    like = [cfg.layer_like(i) for i in range(layers)]
    assert len(set(like)) == kinds, like
    for i in range(layers):
        assert like[i] <= i
        assert cfg.layer_like(like[i]) == like[i]            # idempotent
        for j in range(layers):
            # equal exactly where every per-layer answer is
            assert (like[i] == like[j]) == (answers(i) == answers(j)), (i, j)
    # hashable and equal by value, as a static argument has to be
    assert hash(dataclasses.replace(cfg)) == hash(cfg)


def test_every_per_layer_question_is_part_of_the_kind():
    """A ``layer_*`` method added to ModelConfig has to join
    ``_layer_kind``, or two layers that differ in it would share a trace."""
    import inspect

    from tpuserve.models.config import ModelConfig
    asked = {name for name, fn in inspect.getmembers(
        ModelConfig, inspect.isfunction)
        if list(inspect.signature(fn).parameters) == ["self", "layer_idx"]}
    src = inspect.getsource(ModelConfig._layer_kind)
    assert asked - {"layer_like", "_layer_kind"} == set(
        re.findall(r"self\.(\w+)\(layer_idx\)", src))


# ---- a traced trunk: bodies traced, calls made, functions lowered --------

@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("model", sorted(MODELS))
def test_a_trunk_traces_its_layer_once_a_kind(model, program):
    cfg = config_of(model)
    layers, kinds = MODELS[model]
    body, layer_fn = PROGRAMS[program]
    fn, args, kwargs = trunk_programs(cfg)[program]
    if "+lora" in model:
        args = (jax.eval_shape(lambda: with_lora(init_params(cfg, 0))),
                *args[1:])
        rows = args[2].shape[0] if program != "prefill_chunk" else 1
        kwargs = dict(kwargs, ad=jax.ShapeDtypeStruct((rows, 2), jnp.float32))
    # a trace of its own: nothing of an earlier test's in the jit caches
    jax.clear_caches()
    traces0, calls0 = counts()
    lowered = fn.lower(*args, **kwargs)
    traces1, calls1 = counts()
    assert moved(traces0, traces1) == {body: kinds}
    assert moved(calls0, calls1) == {body: layers}
    # one private function a kind in the module, called once a layer
    text = lowered.as_text()
    assert len(re.findall(rf"func\.func private @{layer_fn}(_\d+)?\(",
                          text)) == kinds
    assert len(re.findall(rf"call @{layer_fn}(_\d+)?\(", text)) == layers
    if program == "decode_multi":
        # another program over the same layer shapes traces no body again
        fn.lower(*args, **dict(kwargs, mode="temperature"))
        assert moved(traces1, counts()[0]) == {}
        assert moved(calls1, counts()[1]) == {body: layers}


@pytest.mark.parametrize("trunk", ["embed_forward", "score_prompt",
                                   "draft_propose", "forward", "prefill"])
def test_the_other_trunks_trace_once_a_kind_too(trunk):
    cfg = get_model_config("tiny-mellum2")
    params = jax.eval_shape(lambda: init_params(cfg, 0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    lens = jax.ShapeDtypeStruct((2,), jnp.int32)
    jax.clear_caches()
    traces0, calls0 = counts()
    if trunk == "forward":
        jax.jit(transformer.forward, static_argnums=1).lower(
            params, cfg, tokens, lens)
    elif trunk == "draft_propose":
        transformer.draft_propose.lower(params, cfg, tokens, lens, k=2)
    elif trunk == "prefill":
        kv = jax.eval_shape(lambda: create_kv_cache(cfg, CacheConfig(
            block_size=4, num_blocks=16, max_blocks_per_seq=8,
            dtype=cfg.dtype)))
        transformer.prefill.lower(params, cfg, tokens, lens, tokens, kv)
    else:
        getattr(transformer, trunk).lower(params, cfg, tokens, lens)
    body = "prefill" if trunk == "prefill" else "nocache"
    assert moved(traces0, counts()[0]) == {body: 2}
    assert moved(calls0, counts()[1]) == {body: 8}


# ---- wrapped against unwrapped, bit for bit ------------------------------

def unwrapped(monkeypatch):
    """The trunks with every layer body called as the plain function it
    wraps (``fn.__wrapped__``): the loop a trunk held before."""
    for name in LAYER_FNS:
        monkeypatch.setattr(transformer, name,
                            getattr(transformer, name).__wrapped__)


def run_trunks(cfg, params, ad):
    """A prefill of two prompts, a chunk after it, a packed prefill of the
    same prompts and two decode steps through ``decode_multi``: everything
    the trunks return, as numpy."""
    rng = np.random.default_rng(3)
    B, T, bs, mb = 2, 8, 4, 8
    ccfg = CacheConfig(block_size=bs, num_blocks=32, max_blocks_per_seq=mb,
                       dtype=cfg.dtype)
    kv = create_kv_cache(cfg, ccfg)
    kw = {} if ad is None else {"ad": ad}
    if cfg.has_state:
        kw.update(ssm=create_ssm_state(cfg, B + 1),
                  seats=jnp.arange(B, dtype=jnp.int32))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    lens = jnp.asarray([T, T - 3], jnp.int32)
    bt = jnp.asarray(np.arange(B * mb).reshape(B, mb), jnp.int32)
    pos = np.arange(T)[None, :].repeat(B, 0)
    slots = bt[:, :1] * bs + pos                    # the first pages of each
    slots = jnp.asarray(np.where(pos < np.asarray(lens)[:, None], slots,
                                 PAD_SLOT), jnp.int32)
    out = []

    def keep(res):
        out.append(jax.tree.map(np.asarray, res))
        return res

    res = keep(transformer.prefill(params, cfg, tokens, lens, slots, kv,
                                   **kw))
    kv = res[1]
    if cfg.has_state:
        kw["ssm"] = res[2]
    if not cfg.has_state and not cfg.is_mla:
        # a chunk of four more tokens a prompt, against what prefill cached
        more = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 4)), jnp.int32)
        cpos = np.asarray(lens)[:, None] + np.arange(4)[None, :]
        cslots = jnp.asarray(np.asarray(bt)[np.arange(B)[:, None], cpos // bs]
                             * bs + cpos % bs, jnp.int32)
        res = keep(transformer.prefill_chunk(
            params, cfg, more, lens, jnp.full((B,), 4, jnp.int32), cslots, bt,
            kv, **kw))
        kv = res[1]
        lens = lens + 4
    # two fused decode steps
    res = keep(transformer.decode_multi(
        params, cfg, tokens[:, 0], lens, bt, lens + 1,
        jnp.ones((B,), bool), jnp.zeros((B, 2), jnp.uint32),
        jnp.zeros((B,), jnp.float32), kv, steps=2, mode="greedy", **kw))
    return out


def both_ways(cfg, params, ad, monkeypatch):
    """``run_trunks`` with the layer bodies wrapped, then unwrapped."""
    jax.clear_caches()
    wrapped = run_trunks(cfg, params, ad)
    unwrapped(monkeypatch)
    jax.clear_caches()
    traces0, _ = counts()
    plain = run_trunks(cfg, params, ad)
    # (the plain bodies ran once a layer: no jit stood between)
    assert sum(moved(traces0, counts()[0]).values()) \
        >= len(plain) * cfg.num_layers
    flat_w, tree_w = jax.tree.flatten(wrapped)
    flat_p, tree_p = jax.tree.flatten(plain)
    assert tree_w == tree_p
    return flat_w, flat_p


@pytest.mark.parametrize("model", sorted(MODELS))
def test_a_wrapped_trunk_returns_what_the_unwrapped_body_gives(
        model, monkeypatch):
    """Bit for bit, in float32.  (A bfloat16 model is run in float32 here:
    the PROGRAMS are the same, which ``--xla_allow_excess_precision=false``
    shows bit for bit in bfloat16 too, but by default the compiler keeps
    float32 where the program rounds to bfloat16 and widens again, and
    where it finds such pairs depends on what it inlined when; the test
    below holds the two to that rounding.)"""
    cfg = dataclasses.replace(config_of(model), dtype="float32")
    params = init_params(cfg, 0)
    ad = None
    if "+lora" in model:
        params = with_lora(params)
        ad = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
    for a, b in zip(*both_ways(cfg, params, ad, monkeypatch)):
        np.testing.assert_array_equal(a, b)


def test_in_bfloat16_the_two_agree_to_its_rounding(monkeypatch):
    cfg = get_model_config("tiny-qwen3")
    assert cfg.dtype == "bfloat16"
    for a, b in zip(*both_ways(cfg, init_params(cfg, 0), None, monkeypatch)):
        if np.issubdtype(a.dtype, np.integer):
            continue        # greedy tokens of random weights: near ties
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), atol=0.05, rtol=0.05)


# ---- the benchmark's reader files a path with jit(...) components --------

@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/decode/while/body/closed_call/jit(g)/mlp/dot_general",
     ("decode", "mlp")),
    # as the trunks write them: the body opens its trunk's phase once more
    ("jit(decode_multi)/decode/while/body/closed_call/jit(_decode_layer)/"
     "decode/attn.qkv/dot_general", ("decode", "attn.qkv")),
    # the K/V row scatter, which the chip's compiler names without the
    # call's prefix: the phase the body opened is what is left
    ("decode/attn.kv_write/scatter", ("decode", "attn.kv_write")),
    ("jit(forward_ragged)/prefill/jit(_ragged_layer)/prefill/mlp/moe.route/"
     "top_k", ("prefill", "moe.route")),
    # what the compiler made inside the called function: named after the
    # call alone, a phase and no part
    ("jit(decode_multi)/decode/while/body/closed_call/jit(_decode_layer)",
     ("decode", "")),
])
def test_the_readers_filing_rule_reads_through_an_inner_jit(op_name, scope):
    """``_scope_trace.scope_of`` (the benchmark's, imported and not
    edited): the first component that names a phase, the last that names
    a part; a ``jit(...)`` component names neither."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.layer_metrics import _scope_trace
    assert _scope_trace.scope_of(op_name) == scope
    assert not _scope_trace.compiler_made("fusion.1", op_name)


# ---- the two counts, where an operator reads them ------------------------

def test_a_warmed_engine_reports_fewer_traces_than_calls(caplog):
    """``Engine.warmup``'s closing log line and ``/metrics``: layer bodies
    traced against layer calls made, by body."""
    from tpuserve.runtime import CacheConfig, Engine, EngineConfig
    from tpuserve.server.metrics import ServerMetrics
    from tpuserve.server.runner import AsyncEngineRunner
    jax.clear_caches()
    traces0, calls0 = counts()
    eng = Engine(EngineConfig(model="tiny-mellum2", cache=CacheConfig(
        block_size=4, num_blocks=64, max_blocks_per_seq=16)))
    with caplog.at_level("INFO", "tpuserve.engine"):
        eng.warmup(prefill_buckets=[8], decode_buckets=[2, 4])
    traces, calls = (moved(a, b) for a, b in zip((traces0, calls0), counts()))
    assert calls and set(traces) == set(calls)
    for body, n in calls.items():
        # eight layers of two kinds
        assert n % 8 == 0 and 2 <= traces[body] < n, (body, traces, calls)
    line = next(r.getMessage() for r in caplog.records
                if "warmup complete" in r.getMessage())
    total = (sum(transformer.LAYER_TRACES.values()),
             sum(transformer.LAYER_CALLS.values()))
    assert "layer bodies traced %d for %d layer calls" % total in line
    runner = AsyncEngineRunner(eng, ServerMetrics("tiny-mellum2"))
    runner._update_gauges()
    text = runner.metrics.render().decode()
    for body in calls:
        for name, count in (("traces", transformer.LAYER_TRACES),
                            ("calls", transformer.LAYER_CALLS)):
            sample = (f'tpuserve_trunk_layer_{name}_total{{body="{body}",'
                      'model_name="tiny-mellum2"}')
            line = next(ln for ln in text.splitlines()
                        if ln.startswith(sample))
            assert float(line.rsplit(" ", 1)[1]) == count[body]
