"""CPU rehearsal of the Olmo-Hybrid cell: control flow, and what the probes
can tell apart.

What ``test_benchmark_falcon_h1_rehearsal.py`` does for its family, for a
configuration whose file names ``"reference": "olmo_hybrid"``:
``tiny-olmo-hybrid`` (two periods of three gated delta-rule linear layers
to one unrotated full-attention layer, norms on each branch's output)
behind the real server, the warm-up of the traffic's shapes, the probes
against the family's plain reference, the child load generator, the
window.  Then the faults, each of which must read over a limit of the
harness: on the reference's side (handed other weights or another field
than what runs) a linear layer skipped, the step size not doubled, the
decay dropped; on the served side (the same weights through a program
that departs from the equations, scored by the sound reference) the
output gate dropped, the full layers rotated, the norms on the branches'
inputs.  Nothing here is a chip run, and nothing it prints is a device
number."""

import dataclasses
import json
import time
import types

import numpy as np
import pytest

from benchmark.harness import plan, session, stats
from benchmark.harness.meter import CompileMeter

CONFIG = {"model": "tiny-olmo-hybrid", "reduced": [], "chips": 1,
          "expect": {"block_manager": "NativeBlockManager"},
          "reference": "olmo_hybrid",
          "linear_num_key_heads": 6, "linear_num_value_heads": 6,
          "linear_key_head_dim": 24, "linear_value_head_dim": 48,
          "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
          "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
          "rope_parameters": {"rope_theta": None},
          "server_args": ["--num-blocks", "256", "--block-size", "8",
                          "--max-blocks-per-seq", "24",
                          "--max-num-seqs", "8", "--multi-step", "4",
                          "--kv-cache-dtype", "float32"]}
SEED = 2**31 + 43


def tiny_cell(tmp_path):
    mix = {"loop": "closed", "pool": 32, "pool_seed": 1, "preroll_s": 1.0,
           "prompt": {"median": 16, "sigma": 0.6, "min": 8, "max": 60},
           "output": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
           "end_to_end": ["out_tok_s"]}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    return plan.Cell(name="tiny-olmo-hybrid", chips=1,
                     config_name="tiny-olmo-hybrid",
                     reference=plan.load_reference(CONFIG),
                     config=CONFIG, traffic_name="mix", traffic=mix,
                     traffic_path=str(path), params={"clients": 5},
                     end_to_end=("out_tok_s", "setup_s"), per_layer=(),
                     units={"out_tok_s": "tokens/s", "setup_s": "s"})


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server for the module: the window's run, the page of /metrics
    after it, and the probes as served (scored again by each test)."""
    tmp = tmp_path_factory.mktemp("olmo_hybrid")
    cell, meter = tiny_cell(tmp), CompileMeter()
    assert plan.unchecked_keys(cell.config, cell.reference) == []
    server, url, model = session.build(cell, meter, 2.0)
    try:
        engine = server.engine
        assert engine.ssm_state is not None and engine._packed_prefill
        verdict = session.probe(url, model, engine, SEED, cell.reference)
        run = session.run_window(cell, server, url, model, SEED, 2.0,
                                 False, str(tmp / "out"), meter)
        # the runner refreshes the gauge once a cycle and cut requests are
        # still leaving: read the two until they agree
        for _ in range(100):
            page = session.scrape(url)
            seats_left = engine.block_manager.seats.in_use
            if page["tpuserve_ssm_state_slots"] == seats_left:
                break
            time.sleep(0.1)

        def rescore(params=None, **fields):
            """The same served path scored on other weights, or for
            another architecture than the one that runs."""
            other = types.SimpleNamespace(
                model_cfg=dataclasses.replace(engine.model_cfg, **fields),
                params=params or engine.params)
            return session.probe(url, model, other, SEED, cell.reference)

        yield types.SimpleNamespace(cell=cell, engine=engine, run=run,
                                    verdict=verdict, page=page,
                                    seats_left=seats_left, rescore=rescore)
    finally:
        server.shutdown()


def test_a_tiny_olmo_hybrid_cell_runs_end_to_end(served):
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
    # bytes a token are the pages of the 2 attention layers of 8, with the
    # 10 heads stored as 16; the pool is not a leaf of the KV cache
    cfg = served.engine.model_cfg
    assert len(cfg.kv_layers) == 2 and cfg.cache_kv_heads == 16
    assert run["kv_bytes_per_token"] == 2 * 2 * 16 * cfg.head_dim * 4
    assert page["tpuserve_ssm_state_resets_total"] >= s["attempted"]
    assert page["tpuserve_ssm_state_slots"] == served.seats_left
    assert page["tpuserve_kv_page_layers"] == 2
    assert page["tpuserve_state_layers"] == 6
    # no trace, so the lin.* readers find nothing to read and say so
    run["trace"] = None
    readers = plan.discover_layer_metrics()
    for name in ("lin.state_update_roofline", "lin.state_update_ns_per_row",
                 "lin.state_update_device_share",
                 "lin.prefill_scan_device_share"):
        assert readers[name].compute(run) is None


def test_the_reference_refuses_another_family(served, capsys):
    """A cell that named this reference for a dense model, or for the
    other model with a seat pool, is refused before a server is built."""
    for model in ("tiny-qwen3", "tiny-falcon-h1"):
        config = {k: v for k, v in CONFIG.items()
                  if not k.startswith("linear_")
                  and k not in ("layer_types", "rope_parameters")}
        cell = types.SimpleNamespace(
            config=dict(config, model=model), config_name="x",
            reference=served.cell.reference)
        with pytest.raises(session.Refused):
            session.register_configuration(cell)
        assert "not the Olmo-Hybrid family" in capsys.readouterr().out


def _layer0(served, **leaves):
    """The served weights with leaves of layer 0's linear mixer replaced."""
    layers = list(served.engine.params["layers"])
    lin = dict(layers[0]["lin"])
    for path, value in leaves.items():
        lin[path] = value(lin[path])
    layers[0] = dict(layers[0], lin=lin)
    return {"params": dict(served.engine.params, layers=layers)}


REFERENCE_FAULTS = {
    # the mixer's output projection zeroed: x + norm(0) = x
    "a linear layer skipped": lambda s: _layer0(
        s, o_proj=lambda p: {"kernel": p["kernel"] * 0}),
    "beta not doubled": lambda s: {"lin_allow_neg_eigval": False},
    # exp(A_log) = 0: alpha = exp(-0 x softplus) = 1 on every row
    "the decay dropped": lambda s: _layer0(
        s, A_log=lambda a: a * 0 - 1e9),
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
    eng = Engine(EngineConfig(model="tiny-olmo-hybrid", multi_step=4,
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


def _gate_dropped(served, monkeypatch):
    import jax

    from tpuserve.models import transformer
    sound = transformer._lin_output

    def no_gate(o, gate, h, lp, cfg):
        return sound(o, gate * 0 + 1.2784645, h, lp, cfg)   # silu = 1

    monkeypatch.setattr(transformer, "_lin_output", no_gate)
    jax.clear_caches()
    cfg = dataclasses.replace(served.engine.model_cfg, name="gate-dropped")
    return cfg, served.engine.params


def _rotated(served, monkeypatch):
    cfg = dataclasses.replace(served.engine.model_cfg, name="rotated",
                              pos="rope")
    return cfg, served.engine.params


def _pre_norm(served, monkeypatch):
    """The same norm weights on the branches' INPUTS."""
    cfg = dataclasses.replace(served.engine.model_cfg, name="pre-norm",
                              norm_placement="pre")
    layers = [dict(lp, attn_norm=lp["post_attn_norm"],
                   mlp_norm=lp["post_mlp_norm"])
              for lp in served.engine.params["layers"]]
    return cfg, dict(served.engine.params, layers=layers)


SERVED_FAULTS = {"the output gate dropped": _gate_dropped,
                 "the full layers rotated": _rotated,
                 "pre-norm for post-norm": _pre_norm}


@pytest.mark.parametrize("fault", sorted(SERVED_FAULTS))
def test_each_fault_of_the_program_reads_over_a_limit(served, fault,
                                                      monkeypatch):
    import jax
    try:
        off = _served_off(served, *SERVED_FAULTS[fault](served, monkeypatch))
    finally:
        monkeypatch.undo()
        jax.clear_caches()              # no program of a faulted trunk stays
    assert off > 1.2 * session.LOGPROB_ATOL, (fault, off)
    sound = _served_off(served, dataclasses.replace(
        served.engine.model_cfg, name="sound"), served.engine.params)
    assert sound < 1e-3


def test_the_parent_would_have_failed_this_cell_at_once():
    """The program before this model knows no such name: the cell ends
    with one sentence before any server is built."""
    import tpuserve.models.config as models
    known = dict(models._REGISTRY)
    try:
        for key in [k for k, c in models._REGISTRY.items()
                    if c.linear_layers is not None]:
            del models._REGISTRY[key]
        cell = plan.load_cell("olmo-hybrid-7b-l16.reason",
                              plan.load_benchmark())
        with pytest.raises(KeyError, match="Unknown model"):
            session.register_configuration(cell)
    finally:
        models._REGISTRY.clear()
        models._REGISTRY.update(known)
