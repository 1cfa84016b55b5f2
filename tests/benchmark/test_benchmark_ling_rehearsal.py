"""CPU rehearsal of the Ling-3.0-flash cell: control flow, and what the
probes can tell apart.

What ``test_benchmark_openpangu_rehearsal.py`` does for its family, for a
configuration whose file names ``"reference": "ling_hybrid"``, HOLDS A
SHARE and asks for the Pallas kernels: ``tiny-ling-hybrid`` (two periods of
two Kimi-delta layers and a latent-attention layer, a dense layer and then
8 experts in 2 groups of which 1 survives) told through
``session.register_configuration`` that it holds routing group 0 (4 of its
8 experts) and half of its vocabulary; the real server with ``--attn-impl
pallas`` (the state update's, the convolution memory's and the paged latent
kernels in interpret mode), the warm-up of the traffic's shapes, the probes
against the family's plain reference, the child load generator, the window.
Then the faults, each of which must read over a limit: the decay averaged
to one scalar a head, the output gate dropped, the attention gate dropped,
the group limit dropped (plain top-k of all experts), the state kept in
bfloat16 -- on the SERVED side, scored by the sound reference -- and the
selection bias used as a weight, the share shifted by a group, the scaling
ignored, on the reference's.  Nothing here is a chip run, and nothing it
prints is a device number."""

import dataclasses
import json
import types

import numpy as np
import pytest

from benchmark.harness import plan, session, stats
from benchmark.harness.meter import CompileMeter

CONFIG = {"model": "tiny-ling-hybrid", "chips": 1,
          "reduced": ["num_experts", "vocab_size"],
          "num_experts": 4, "vocab_size": 128,
          "published": {"num_experts": 8, "vocab_size": 256},
          "deployment": "one of 2 chips that share each expert layer, "
                        "routing group 0 (experts 0-3 of 8); one of 2 "
                        "that share the vocabulary",
          "model_type": "bailing_hybrid", "layer_group_size": 3,
          "num_experts_per_tok": 2, "moe_intermediate_size": 32,
          "moe_shared_expert_intermediate_size": 32, "n_group": 2,
          "topk_group": 1, "routed_scaling_factor": 2.5,
          "norm_topk_prob": True, "first_k_dense_replace": 1,
          "moe_router_enable_expert_bias": True,
          "score_function": "sigmoid", "use_qk_norm": True,
          "kv_lora_rank": 136, "q_lora_rank": None,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 12, "v_head_dim": 16,
          "head_dim": 16, "partial_rotary_factor": 0.75, "rotary_dim": 12,
          "rope_theta": 6000000, "kda_lower_bound": -5,
          "kda_safe_gate": True, "short_conv_kernel_size": 4,
          "gated_attention_proj_granularity_type": "head_wise",
          "expert_swiglu_limit_list": [0] * 6,
          "share_expert_swiglu_limit_list": [0] * 6,
          "expect": {"attn_impl": "pallas",
                     "block_manager": "NativeBlockManager"},
          "reference": "ling_hybrid",
          "server_args": ["--num-blocks", "256", "--block-size", "8",
                          "--max-blocks-per-seq", "24",
                          "--max-num-seqs", "4", "--multi-step", "4",
                          "--kv-cache-dtype", "float32",
                          "--attn-impl", "pallas"]}
SEED = 2**31 + 55
HELD, EXPERT_LAYERS, LINEAR = 4, 5, 4


def tiny_cell(tmp_path):
    mix = {"loop": "closed", "pool": 32, "pool_seed": 1, "preroll_s": 1.0,
           "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
           "output": {"median": 10, "sigma": 0.5, "min": 6, "max": 16},
           "end_to_end": ["out_tok_s"]}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    return plan.Cell(name="tiny-ling", chips=1, config_name="tiny-ling-ep2",
                     reference=plan.load_reference(CONFIG),
                     config=CONFIG, traffic_name="mix", traffic=mix,
                     traffic_path=str(path), params={"clients": 3},
                     end_to_end=("out_tok_s", "setup_s"), per_layer=(),
                     units={"out_tok_s": "tokens/s", "setup_s": "s"})


def biased(params, experts=8, seed=3):
    """A random selection bias in every expert layer (the engine draws
    zeros: a trained bias is the checkpoint's)."""
    import jax.numpy as jnp
    rs = np.random.RandomState(seed)
    return dict(params, layers=[
        dict(lp, router_bias={"bias": jnp.asarray(
            0.1 * rs.randn(experts), jnp.float32)})
        if "router_bias" in lp else lp for lp in params["layers"]])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server for the module: the window's run, the page of /metrics
    after it, and the probes as served (scored again by each test)."""
    tmp = tmp_path_factory.mktemp("ling")
    cell, meter = tiny_cell(tmp), CompileMeter()
    # (a routing group of the tiny model is 4 experts: under the floor a
    # real cell keeps to, and nothing else is wrong with the share)
    assert plan.share_faults(cell.config) == [
        "num_experts: 4 held, fewer than 8"]
    assert plan.unchecked_keys(cell.config, cell.reference) == []
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        engine.params = biased(engine.params)
        assert engine._packed_prefill and engine.model_cfg.is_mla
        assert engine.model_cfg.has_state
        verdict = session.probe(url, model, engine, SEED, cell.reference)
        run = session.run_window(cell, server, url, model, SEED, 2.0,
                                 False, str(tmp / "out"), meter)
        page = session.scrape(url)

        def rescore(reference=None, params=None, **fields):
            other = types.SimpleNamespace(
                model_cfg=dataclasses.replace(engine.model_cfg, **fields),
                params=params or engine.params)
            return session.probe(url, model, other, SEED,
                                 reference or plan.load_reference(CONFIG))

        yield types.SimpleNamespace(cell=cell, engine=engine, run=run,
                                    verdict=verdict, page=page,
                                    rescore=rescore, url=url, model=model)
    finally:
        server.shutdown()


def test_the_share_reaches_the_program_as_data(served):
    """What the file lists under ``reduced`` is what the registered model
    gained: the experts held (the router keeps its 8 and its 2 groups), the
    vocabulary slice, and nothing else; latent pages for 2 layers and a
    pool for 4 in ONE engine."""
    from tpuserve.models.config import get_model_config
    cfg = served.engine.model_cfg
    assert cfg == dataclasses.replace(
        get_model_config("tiny-ling-hybrid"), name="bench/tiny-ling-ep2",
        moe_experts_held=HELD, vocab_size=128)
    assert (cfg.num_experts, cfg.moe_first_expert, cfg.moe_n_group) == (
        8, 0, 2)
    lp = served.engine.params["layers"][1]
    assert lp["experts"]["gate_proj"]["kernel"].shape == (HELD, 64, 32)
    assert lp["router"]["kernel"].shape == (64, 8)
    assert lp["router_bias"]["bias"].shape == (8,)
    assert served.engine.params["lm_head"]["kernel"].shape == (64, 128)
    assert served.engine.attn_impl == "pallas"
    assert len(served.engine.kv_cache) == 2
    assert all(set(entry) == {"k"} and entry["k"].shape[2:] == (1, 256)
               for entry in served.engine.kv_cache)
    assert len(served.engine.ssm_state) == LINEAR


def test_a_tiny_ling_cell_runs_end_to_end(served):
    verdict, run, page = served.verdict, served.run, served.page
    assert verdict["ok"], verdict
    assert verdict["positions"] == 48
    assert verdict["logprob_diff_max"] < 1e-3       # float32 on both sides
    assert verdict["tie_gap_max"] < 1e-3
    assert run["compiles_in_window"] == 0, run["new_executables"]
    s = stats.summarize(run["records"], "closed", run["t_window"],
                        run["t_end"])
    assert s["attempted"] > 0 and s["failed"] == 0, s["errors"]
    assert stats.end_to_end("out_tok_s", s) > 0
    assert {"prefill", "window"} <= {step["kind"] for step in run["steps"]}
    cfg = served.engine.model_cfg
    routed = [step for step in run["steps"] if step.get("moe_rows")]
    assert routed and all(
        step["moe_rows"] == step["padded_tokens"] * EXPERT_LAYERS
        * cfg.num_experts_per_tok for step in routed)
    # the two counters this family adds, in the step records and on the
    # page (which counts from the server's start)
    decodes = [s for s in run["steps"] if s["kind"] in ("window", "decode")]
    assert decodes and all(s["kda_row_layers"] == LINEAR * s["actual_tokens"]
                           for s in decodes)
    assert 0 < sum(s["kda_row_layers"] for s in decodes) \
        <= page["tpuserve_kda_state_row_layers_total"]
    here = sum(s.get("moe_group_rows", 0) for s in routed)
    assert 0 < here <= page["tpuserve_moe_group_rows_total"]
    assert all(s["moe_held_rows"] <= cfg.num_experts_per_tok
               * s["moe_group_rows"] for s in routed)
    assert page["tpuserve_moe_experts_held"] == HELD
    reader = plan.discover_layer_metrics()["moe.group_rows_share"]
    start = {k: 0 for k in page}
    share = reader.compute({"metrics_start": start, "metrics_end": page,
                            "config": CONFIG})
    assert 20 < share < 80              # one group of two: near a half
    # no trace, so the readers of the device's time find nothing to read
    # and say so, without raising
    run["trace"] = None
    readers = plan.discover_layer_metrics()
    names = [n for n in readers if n.startswith("kda.")]
    assert len(names) == 5
    for name in names:
        assert readers[name].compute(dict(run, config=dict(
            CONFIG, num_attention_heads=4, num_hidden_layers=6))) is None


def _bias_as_a_weight(served):
    """The reference with the router's weights taken from the BIASED
    scores of its picks."""
    import jax.numpy as jnp
    mod = plan.load_reference(CONFIG)
    sound = mod.route

    def route(lp, h, cfg, named):
        w = sound(lp, h, cfg, named)
        c = jnp.where(w > 0, jax_sigmoid(mod._linear(h, lp["router"]))
                      + lp["router_bias"]["bias"][None, :], 0.0)
        return c / (jnp.sum(c, axis=-1, keepdims=True) + 1e-20) \
            * cfg.moe_routed_scaling

    import jax
    jax_sigmoid = jax.nn.sigmoid
    mod.route = route
    return {"reference": mod}


REFERENCE_FAULTS = {
    "the selection bias used as a weight": _bias_as_a_weight,
    "the share shifted by a group":
        lambda served: {"moe_first_expert": 4},
    "the scaling 2.5 ignored":
        lambda served: {"moe_routed_scaling": 1.0},
    "a lower bound of -1":
        lambda served: {"lin_gate_lower_bound": -1.0},
}


@pytest.mark.parametrize("fault", sorted(REFERENCE_FAULTS))
def test_each_fault_of_the_reference_reads_over_a_limit(served, fault):
    bad = served.rescore(**REFERENCE_FAULTS[fault](served))
    assert not bad["ok"] and "disagree" in bad["why"], (fault, bad)
    assert max(bad["logprob_diff_max"], bad["tie_gap_max"]) \
        > 1.2 * session.LOGPROB_ATOL, (fault, bad)
    good = served.rescore()
    assert good["ok"] and good["logprob_diff_max"] < 1e-3


def _served_off(served, model_cfg, params):
    """The largest distance between the chosen tokens' log-probabilities
    as ANOTHER program serves them on these weights and the sound
    reference's rows for the same tokens."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SamplingParams)
    sound = served.engine.model_cfg
    eng = Engine(EngineConfig(model="tiny-ling-hybrid", multi_step=4,
                              cache=CacheConfig(block_size=8, num_blocks=64,
                                                max_blocks_per_seq=24,
                                                dtype="float32")),
                 params=params, model_cfg=model_cfg)
    ids = session.traffic_mod.prompt_ids(SEED, "probe", 0, 64,
                                         sound.vocab_size)
    (out,) = eng.generate([ids], SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True, logprobs=1))
    toks = out.output_token_ids
    rows = np.asarray(served.cell.reference.score_probes(
        served.engine.params, sound, [(ids, toks, {})]))
    assert [e["token_id"] for e in out.logprobs] == toks
    return max(abs(float(rows[j, e["token_id"]]) - e["logprob"])
               for j, e in enumerate(out.logprobs))


@pytest.fixture(scope="module")
def sound_off(served):
    """The sound program through the same path: float32 noise."""
    off = _served_off(served, *_named(served, "sound"))
    assert off < 1e-4, off
    return off


def _named(served, what, **fields):
    return (dataclasses.replace(served.engine.model_cfg, name=what,
                                **fields), served.engine.params)


def _decay_averaged(served, monkeypatch):
    """One scalar a head: the mean of the head's channels on every one."""
    import jax.numpy as jnp

    from tpuserve.models import transformer
    sound = transformer._lin_inputs

    def averaged(*args):
        x, g, beta = sound(*args)
        return x, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True),
                                   g.shape), beta

    monkeypatch.setattr(transformer, "_lin_inputs", averaged)
    return _named(served, "decay-averaged")


def _output_gate_dropped(served, monkeypatch):
    from tpuserve.models import transformer
    sound = transformer._lin_output

    def no_gate(o, gate, h, lp, cfg):
        return sound(o, gate * 0 + 30.0, h, lp, cfg)    # sigmoid = 1

    monkeypatch.setattr(transformer, "_lin_output", no_gate)
    return _named(served, "output-gate-dropped")


def _state_in_bfloat16(served, monkeypatch):
    """The pool's state stored in bfloat16: every step rounds what the
    next one reads."""
    import jax
    import jax.numpy as jnp

    from tpuserve.runtime import kv_cache
    sound = kv_cache._ssm_layer

    def rounded(c, seats):
        layer = sound(c, seats)
        return dict(layer, state=jax.ShapeDtypeStruct(
            layer["state"].shape, jnp.bfloat16))

    monkeypatch.setattr(kv_cache, "_ssm_layer", rounded)
    return _named(served, "state-in-bfloat16")


def _attention_gate_dropped(served, monkeypatch):
    return _named(served, "attention-gate-dropped", attn_head_gate=False)


def _group_limit_dropped(served, monkeypatch):
    """Plain top-k of all experts."""
    return _named(served, "group-limit-dropped", moe_n_group=1,
                  moe_topk_group=1)


SERVED_FAULTS = {
    "the decay averaged to one scalar a head": _decay_averaged,
    "the output gate dropped": _output_gate_dropped,
    "the attention gate dropped": _attention_gate_dropped,
    "the group limit dropped": _group_limit_dropped,
}


def _faulted_off(served, fault, monkeypatch):
    """``_served_off`` of a faulted program.  A fault that patches a helper
    of the trunk is traced under a name of its own (``_named``) with the
    caches cleared around it: no program of a faulted trunk stays, and
    none traced before it is reused."""
    import jax
    patched = fault not in (_attention_gate_dropped, _group_limit_dropped)
    if patched:
        jax.clear_caches()
    try:
        return _served_off(served, *fault(served, monkeypatch))
    finally:
        monkeypatch.undo()
        if patched:
            jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(SERVED_FAULTS))
def test_each_fault_of_the_program_reads_over_a_limit(served, sound_off,
                                                      fault, monkeypatch):
    off = _faulted_off(served, SERVED_FAULTS[fault], monkeypatch)
    assert off > 1.2 * session.LOGPROB_ATOL, (fault, off)


def test_a_state_kept_in_bfloat16_reads_over_the_limit_it_can_reach(
        served, sound_off, monkeypatch):
    """The state in bfloat16 rounds 3 decimal digits off what every later
    row reads.  At the tiny size (4 heads of 16 x 16, 72 rows) that is
    two orders over the sound path's float32 noise and is held to THAT:
    the probe's 0.1 is reached at the published sizes on the chip, where
    the configuration's nearest lower precision reads (PERF.md §6)."""
    off = _faulted_off(served, _state_in_bfloat16, monkeypatch)
    assert off > 30 * sound_off, (off, sound_off)


def test_the_parent_would_have_refused_this_file(monkeypatch):
    """A program without the registered model (the parent commit) answers
    the cell before any server is built: ``get_model_config`` raises on
    the name, at once."""
    import tpuserve.models.config as models
    monkeypatch.setattr(models, "_REGISTRY", {
        k: v for k, v in models._REGISTRY.items() if "ling" not in k})
    cell = plan.load_cell("ling-3.0-flash-vl-ep8-l12.reason",
                          plan.load_benchmark())
    with pytest.raises(KeyError, match="Unknown model"):
        session.register_configuration(cell)
