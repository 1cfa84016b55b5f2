"""Olmo-Hybrid on the normal path: gated delta-rule linear-attention layers
three to one with full attention layers, a matrix state a SEAT in the
linear layers alone and paged K/V in the attention layers alone.

Everything is compared with the plain reference the benchmark scores this
family by (``benchmark/reference/olmo_hybrid.py``: float32, the recurrence
token by token, no code shared with ``tpuserve``), on the registered
``tiny-olmo-hybrid`` (float32; two periods of L L L F; 6 linear heads with
keys of 24 and values of 48, no whole lane tile anywhere; a scan chunk of
8; 10 attention heads of 16, cached as 16) under seeded random weights.

Tolerances: both sides are float32 on the CPU, so what separates them is
the ORDER of the same sums (the chunked WY form's triangular solve and
matrix products against the reference's token loop, blocked attention
against a dense softmax), carried through eight layers that each norm
their branch's OUTPUT (a unit-size term whatever the branch's size): up to
3e-5 on logits of size ~1-4, and 4e-4 where a test scales a norm's weight
by half again (the next layers carry the difference with it).  ``ATOL``
5e-4 leaves an order of magnitude over the first and sits two orders under
what a left-out term moves (``test_every_term_of_the_layer_is_live``: over
3e-2 each).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_routes import (BLOCK, FAMILIES, ROOT, SEATS, engine_for, plan,
                           prompts_of, ref_logits, run_route, serve)
from tpuserve.models import transformer
from tpuserve.models.config import (MIXER_ATTENTION, MIXER_BOTH, MIXER_LINEAR,
                                    config_from_hf_json, get_model_config)
from tpuserve.models.weights import init_params
from tpuserve.ops import gated_delta as gdn_ops
from tpuserve.ops import pallas_gdn_update as upd
from tpuserve.runtime import CacheConfig
from tpuserve.runtime.kv_cache import (bytes_per_block, create_kv_cache,
                                       create_ssm_state, ssm_state_bytes)

FAMILY = FAMILIES["olmo_hybrid"]
ATOL = FAMILY.atol
MODEL = FAMILY.model
PUBLISHED = "allenai/Olmo-Hybrid-7B"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b-l16.json")

ref = FAMILY.ref

@pytest.fixture(scope="module")
def cfg():
    return get_model_config(MODEL)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, seed=7)


# --------------------------------------------------------------------------
# the trunks, driven by hand: logits against the reference at every position
# --------------------------------------------------------------------------

# (the hand-driven cache builds its pools from the ModelConfig it is handed,
# so here the cache has 2 entries and the pool 6)

def test_the_plain_forward_is_the_reference(cfg, params):
    """``transformer.forward`` (no cache) against the reference at every
    position of two sequences."""
    tokens = np.asarray(prompts_of(27, 27, seed=8), np.int32)
    got = np.asarray(transformer.forward(params, cfg, jnp.asarray(tokens)))
    for i in range(2):
        np.testing.assert_allclose(
            got[i],
            ref_logits(FAMILY, params, cfg, list(tokens[i]), range(27)),
            atol=ATOL)


@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
@pytest.mark.parametrize("route", ["prefill", "packed", "chunks"])
def test_every_route_matches_the_reference_at_every_position(
        cfg, params, route, attn_impl):
    """(B, L) prefill, a packed prefill of three uneven prompts, a prompt
    over several chunks (``prefill_chunk`` continuing a state); then
    ``decode_step`` and a fused ``decode_multi`` window.  ``pallas``: the
    paged kernels and the state-update kernel in interpret mode."""
    run_route(FAMILY, cfg, params, route, attn_impl)


# --------------------------------------------------------------------------
# the recurrence's two forms
# --------------------------------------------------------------------------

def _rows(length, T, H, dk, dv, seed):
    """Random rows of one sequence: unit keys and queries, the decay's log
    in (-0.5, 0), the step size up to 2 and exactly 2 on every third row
    (the state's transition is then a reflection); zero past ``length``."""
    rs = np.random.RandomState(seed)
    unit = lambda y: y / np.linalg.norm(y, axis=-1, keepdims=True)
    q, k = unit(rs.randn(T, H, dk)), unit(rs.randn(T, H, dk))
    v = rs.randn(T, H, dv)
    valid = (np.arange(T) < length)[:, None]
    g = np.where(valid, -rs.uniform(0.001, 0.5, (T, H)), 0.0)
    beta = rs.uniform(0.0, 2.0, (T, H))
    beta[::3] = 2.0
    return q, k, v, g, np.where(valid, beta, 0.0)


def _token_loop(s0, q, k, v, g, beta, length):
    """The recurrence row by row, in float64."""
    state, out = s0.copy(), []
    for t in range(length):
        state = state * np.exp(g[t])[:, None, None]
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", state, k[t]))
        state = state + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", state, q[t]))
    return np.asarray(out), state


def _scan(q, k, v, g, beta, s0, chunk_seq, Q):
    """The scan over rows given as one array of channels [q | k | v], as
    a linear layer hands them, split back a group of rows at a time."""
    T, H, dk = q.shape
    f32 = lambda y: jnp.asarray(y, jnp.float32)
    x = np.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                        v.reshape(T, -1)], axis=1)

    def split(rows):
        n = rows.shape[0]
        return (rows[:, :H * dk].reshape(n, H, dk),
                rows[:, H * dk:2 * H * dk].reshape(n, H, dk),
                rows[:, 2 * H * dk:].reshape(n, H, -1))

    return gdn_ops.gated_delta_chunk_scan(
        f32(x), f32(g), f32(beta), f32(s0), chunk_seq, chunk=Q, split=split)


@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_the_chunked_scan_is_the_plain_recurrence(length):
    """``gated_delta_chunk_scan`` (chunk 64, the published size) against
    the token-by-token loop, from a non-zero state, with the step size up
    to 2, at lengths around the chunk: the rows past the end carry g = 0
    and beta = 0 and must change nothing.  Float32 against float64: 1e-5
    on values of size ~1."""
    H, dk, dv, Q = 3, 24, 48, 64
    T = -(-length // Q) * Q
    q, k, v, g, beta = _rows(length, T, H, dk, dv, length)
    s0 = np.random.RandomState(1).randn(1, H, dk, dv)
    want, state = _token_loop(s0[0], q, k, v, g, beta, length)
    o, finals = _scan(q, k, v, g, beta, s0,
                      jnp.zeros((T // Q,), jnp.int32), Q)
    np.testing.assert_allclose(np.asarray(o)[:length], want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(finals)[0], state, atol=1e-5)


def test_the_chunked_scan_keeps_packed_sequences_apart():
    """Two sequences and a padding chunk on one flat axis, as a packed
    prefill lays them: each starts from its own ``s0`` and ends in its own
    row of ``finals``; the padding chunk touches neither."""
    H, dk, dv, Q = 2, 8, 12, 8
    lens, chunks = (11, 5), (2, 1)
    parts = [_rows(n, c * Q, H, dk, dv, n) for n, c in zip(lens, chunks)]
    pad = [np.zeros_like(x[:Q]) for x in parts[0]]
    flat = [np.concatenate(xs) for xs in zip(parts[0], pad, parts[1])]
    s0 = np.random.RandomState(2).randn(2, H, dk, dv)
    o, finals = _scan(*flat, s0, jnp.asarray([0, 0, -1, 1], jnp.int32), Q)
    for i, (start, part) in enumerate(zip((0, 3 * Q), parts)):
        want, state = _token_loop(s0[i], *part, lens[i])
        np.testing.assert_allclose(
            np.asarray(o)[start:start + lens[i]], want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(finals)[i], state, atol=1e-5)


@pytest.mark.parametrize("shape", [(4, 6, 24, 48), (3, 5, 24, 48),
                                   (2, 4, 96, 192)],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_state_update_kernel_is_one_step_of_the_recurrence(shape):
    """``_gdn_state_update`` in interpret mode against one row of the
    token loop (float64) on a pool with more seats than rows, the last row
    a padding row on the trash seat: the rows' seats are updated in place,
    every other seat is left as it was.  Heads in pairs (6 heads of 48,
    and the published 96 x 192), and one a slab (5 heads)."""
    B, H, dk, dv = shape
    S = B + 3
    hp = upd.heads_per_slab(H, dv)
    assert hp == (1 if H % 2 else 2)
    rs = np.random.RandomState(B)
    pool = rs.randn(S + 1, H // hp, dk, hp * dv).astype(np.float32)
    seats = np.append(rs.permutation(S)[:B - 1], S).astype(np.int32)
    q, k, v, g, beta = (x.astype(np.float32)
                        for x in _rows(B, B, H, dk, dv, B + 1))
    q[-1] = k[-1] = v[-1] = g[-1] = beta[-1] = 0      # as _lin_inputs pads
    o, got = upd.gdn_state_update(
        jnp.asarray(pool), *map(jnp.asarray, (seats, q, k, v, g, beta)),
        interpret=True)
    o_ref, got_ref = upd.gdn_state_update_reference(
        jnp.asarray(pool), *map(jnp.asarray, (seats, q, k, v, g, beta)))
    states = np.asarray(upd.from_slabs(jnp.asarray(pool), hp), np.float64)
    for b in range(B):
        want_o, want_s = _token_loop(states[seats[b]], q[b:b + 1], k[b:b + 1],
                                     v[b:b + 1], g[b:b + 1], beta[b:b + 1], 1)
        for mine, pool_after in ((o, got), (o_ref, got_ref)):
            np.testing.assert_allclose(np.asarray(mine)[b], want_o[0],
                                       atol=2e-5)
            np.testing.assert_allclose(
                np.asarray(upd.from_slabs(pool_after, hp))[seats[b]], want_s,
                atol=2e-5)
    untouched = np.setdiff1d(np.arange(S + 1), seats)
    np.testing.assert_array_equal(np.asarray(got)[untouched], pool[untouched])
    # the trash seat's row neither decayed nor wrote
    np.testing.assert_array_equal(np.asarray(got)[S], pool[S])


def test_the_pool_stores_whole_lane_tiles_at_the_published_sizes():
    """30 heads of 96 x 192: two heads a slab, 384 lanes = 3 tiles, 96
    sublanes = 12 tiles, so a seat's state is 2,211,840 B with no padding;
    stored a head at a time its 192 lanes would be padded to 256."""
    big = get_model_config(PUBLISHED)
    assert upd.heads_per_slab(30, 192) == 2
    pool = jax.eval_shape(lambda: create_ssm_state(
        dataclasses.replace(big, num_layers=4), 64))
    assert [tuple(x["state"].shape) for x in pool] == [(65, 15, 96, 384)] * 3
    assert pool[0]["state"].shape[-1] % 128 == 0
    assert pool[0]["state"].shape[-2] % 8 == 0
    # the convolution's memory beside it: a row of 11,520 channels as 90
    # sublanes of whole lane tiles (ops/pallas_conv_tail.py tail_slab)
    assert tuple(pool[0]["conv"].shape) == (65, 3, 90, 128)
    x = jnp.arange(2 * 6 * 4 * 5, dtype=jnp.float32).reshape(2, 6, 4, 5)
    np.testing.assert_array_equal(upd.from_slabs(upd.to_slabs(x, 2), 2), x)
    assert upd.to_slabs(x, 2).shape == (2, 3, 4, 10)
    np.testing.assert_array_equal(upd.to_slabs(x, 2)[0, 1, :, 5:], x[0, 3])


# --------------------------------------------------------------------------
# each layer holds its own kind of memory
# --------------------------------------------------------------------------

def test_pages_for_the_attention_layers_and_state_for_the_linear(cfg):
    """``layer_mixer`` by layer, and what follows from it: the cache
    trees' lengths, ``bytes_per_block``, ``ssm_state_bytes`` -- at the
    tiny size and at the published one (4 of 16 layers hold pages, 12 a
    state).  Falcon-H1's every layer holds both, a dense model's pages."""
    assert [cfg.layer_mixer(i) for i in range(8)] == (
        [MIXER_LINEAR] * 3 + [MIXER_ATTENTION]) * 2
    assert cfg.kv_layers == (3, 7) and cfg.state_layers == (0, 1, 2, 4, 5, 6)
    assert cfg.layer_types == (["linear_attention"] * 3
                               + ["full_attention"]) * 2
    cc = CacheConfig(block_size=BLOCK, num_blocks=8, max_blocks_per_seq=8,
                     dtype="float32")
    kv, pool = create_kv_cache(cfg, cc), create_ssm_state(cfg, SEATS)
    assert len(kv) == 2 and len(pool) == 6
    assert kv[0]["k"].shape == (8, BLOCK, 16, 16)       # 10 heads, as 16
    assert bytes_per_block(cfg, cc) == sum(
        x.nbytes for x in jax.tree.leaves(kv)) // 8
    assert ssm_state_bytes(cfg, SEATS) == sum(
        x.nbytes for x in jax.tree.leaves(pool))
    big = dataclasses.replace(get_model_config(PUBLISHED), num_layers=16)
    assert len(big.kv_layers) == 4 and len(big.state_layers) == 12
    served = CacheConfig(block_size=32, num_blocks=16, max_blocks_per_seq=16)
    # 4 layers x K and V x 32 head rows (30 heads in whole tiles) x 128 x 2 B
    assert bytes_per_block(big, served) // 32 == 4 * 2 * 32 * 128 * 2 == 65536
    assert ssm_state_bytes(big, 64) == 12 * 65 * (
        30 * 96 * 192 * 4 + 3 * 11520 * 4)
    falcon = get_model_config("tiny-falcon-h1")
    assert {falcon.layer_mixer(i) for i in range(2)} == {MIXER_BOTH}
    assert falcon.kv_layers == falcon.state_layers == (0, 1)
    dense = get_model_config("tiny-qwen3")
    assert dense.state_layers == () and not dense.has_state
    assert dense.kv_layers == (0, 1)
    assert dense.cache_kv_heads == 2 and dense.cache_q_heads == 4


def test_a_cut_of_the_depth_keeps_the_first_layers_kinds():
    """The harness overrides ``num_layers`` (32 -> 16): the kinds follow,
    four whole periods, and so do the parameters the cache budget reads."""
    whole = get_model_config(PUBLISHED)
    cut = dataclasses.replace(whole, num_layers=16)
    assert cut.layer_types == whole.layer_types[:16]
    assert cut.layer_types[:4] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert whole.num_params == pytest.approx(7.43e9, rel=2e-3)
    assert cut.num_params == pytest.approx(4.10e9, rel=2e-3)
    with pytest.raises(ValueError, match="states 32 layers of 40"):
        dataclasses.replace(whole, num_layers=40)


# --------------------------------------------------------------------------
# no equation dropped: every term moves the logits
# --------------------------------------------------------------------------

def _scaled(tree, layer, path, factor):
    """``tree`` with the leaf at ``path`` of ``layer`` times ``factor``."""
    out = jax.tree.map(lambda x: x, tree)
    node = out["layers"][layer]
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = node[path[-1]] * factor
    return out


TERMS = {
    "A_log": (0, ("lin", "A_log"), 0.5),
    "dt_bias": (0, ("lin", "dt_bias"), 8.0),
    # (drawn for the stream's size, models/weights.py _init_lin: small)
    "decay": (0, ("lin", "a_proj", "kernel"), 6.0),
    "step size": (0, ("lin", "b_proj", "kernel"), 6.0),
    "conv": (0, ("lin", "conv", "kernel")),
    "gate": (0, ("lin", "g_proj", "kernel")),
    "head norm": (0, ("lin", "norm", "scale")),
    "mixer norm": (0, ("post_attn_norm", "scale")),
    "mlp norm": (3, ("post_mlp_norm", "scale")),
    "q norm": (3, ("q_norm", "scale")),
    "k norm": (3, ("k_norm", "scale")),
}


@pytest.mark.parametrize("what", sorted(TERMS))
def test_every_term_of_the_layer_is_live(cfg, params, what):
    """Each parameter of the two mixers that a plain dense trunk lacks:
    changed on BOTH sides, the served trunk and the reference still agree
    (the term is implemented, and in the same place); the logits part from
    the unchanged ones by far more than the tolerance (it is not a no-op
    under these weights, so leaving it out could not pass)."""
    layer, path, *factor = TERMS[what]
    tokens = np.asarray(prompts_of(21, seed=3), np.int32)
    rows = [(0, t) for t in range(21)]
    base = np.asarray(transformer.forward(params, cfg, jnp.asarray(tokens)))[0]
    params2 = _scaled(params, layer, path, factor[0] if factor else 1.5)
    moved = np.asarray(transformer.forward(params2, cfg,
                                           jnp.asarray(tokens)))[0]
    want = np.asarray(ref.logits_at(params2, cfg, tokens, rows))
    np.testing.assert_allclose(moved, want, atol=ATOL)
    assert np.abs(moved - base).max() > 3e-2, what


# --------------------------------------------------------------------------
# through the engine (what it shares word for word with Falcon-H1, the
# other family with a seat pool: tests/test_seat_pool.py)
# --------------------------------------------------------------------------

def test_a_preempted_sequence_reprefills_to_the_same_logits():
    """A cache too small for four growing sequences pre-empts; the victim
    re-prefills prompt plus generated tokens from a zeroed seat (nothing
    snapshots its state): every token it serves is the roomy engine's and
    the argmax of the reference's logits after the same prefix."""
    prompts = prompts_of(10, 12, 9, 11, seed=4)

    roomy = serve(engine_for(FAMILY, multi_step=1), prompts, max_tokens=24)
    tight = engine_for(FAMILY, multi_step=1, cache={"num_blocks": 14})
    got = serve(tight, prompts, max_tokens=24)
    assert got == roomy
    assert tight.stats.preemptions > 0
    assert tight.stats.ssm_rebuilt_tokens > 0
    assert tight.stats.ssm_state_resets == 4 + tight.stats.preemptions
    assert tight.block_manager.seats.in_use == 0
    # the same logits: the re-prefilled sequences' tokens are the
    # reference's greedy ones, which a state that was not rebuilt from
    # zeros, or rebuilt from other tokens, would leave within a few steps
    for p, toks in zip(prompts, got):
        full = p + toks
        rows = ref_logits(FAMILY, tight.params, tight.model_cfg, full,
                          range(len(p) - 1, len(full) - 1))
        assert list(np.argmax(rows, axis=-1)) == toks


def test_what_the_engine_observes_of_recurrent_state(caplog):
    """No option: with a state in ANY layer the prefix cache, the KV tier
    and mixed batching are off, each with its logged sentence, and the
    pool is accounted beside the KV cache, not inside it: 6 layers of
    state, 2 of pages."""
    import logging
    with caplog.at_level(logging.INFO, logger="tpuserve.engine"):
        engine = engine_for(FAMILY, enable_prefix_caching=True,
                            kv_tiers=True,
                            scheduler={"mixed_batching": True})
    assert not engine.block_manager.enable_prefix_caching
    assert engine._kv_tiers is None
    assert not engine.scheduler.cfg.mixed_batching
    said = caplog.text
    assert "prefix caching and the KV tier are off" in said
    assert "mixed ragged batching is off" in said
    cfg = engine.model_cfg
    want = ssm_state_bytes(cfg, 4)
    assert want == 6 * 5 * (6 * 24 * 48 * 4 + 3 * 576 * 4)
    assert len(engine.ssm_state) == 6 and len(engine.kv_cache) == 2
    assert sum(x.nbytes for x in jax.tree.leaves(engine.ssm_state)) == want
    hbm = engine.devprof.hbm_snapshot()
    assert hbm["state_bytes"] == want
    assert hbm["kv_reserved_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(engine.kv_cache))
    assert engine.flight.dump_bundle("test")["engine"][
        "ssm_state_seats"] == 4


def test_the_auto_sizer_counts_each_kind_of_memory_over_its_layers(
        monkeypatch):
    from tpuserve.models.weights import param_nbytes
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(40 << 20))
    engine = engine_for(FAMILY, cache={"num_blocks": 0},
                        scheduler={"max_num_seqs": 64})
    cfg, cc = engine.model_cfg, engine.cache_cfg
    budget = int((40 << 20) * 0.9) - param_nbytes(engine.params) \
        - ssm_state_bytes(cfg, 64)
    assert cc.num_blocks == budget // bytes_per_block(cfg, cc)
    # a block is 2 layers' pages, not 8's
    assert bytes_per_block(cfg, cc) == 2 * 2 * BLOCK * 16 * 16 * 4


def test_the_gauges_say_what_each_memory_was_counted_over():
    """``tpuserve_kv_page_layers`` and ``tpuserve_state_layers``, set once
    beside the seat pool's gauges: 2 and 6 here, 2 and 2 for Falcon-H1
    (both in every layer), 2 and 0 for a dense model."""
    from tpuserve.server.metrics import ServerMetrics
    for model, pages, state in ((MODEL, 2, 6), ("tiny-falcon-h1", 2, 2),
                                ("tiny-qwen3", 2, 0)):
        m = ServerMetrics(model)
        m.set_layer_kinds(get_model_config(model))
        page = m.render().decode()
        label = f'{{model_name="{model}"}}'
        assert f"tpuserve_kv_page_layers{label} {pages}.0" in page, model
        assert f"tpuserve_state_layers{label} {state}.0" in page, model


# --------------------------------------------------------------------------
# the configuration and the reference's family check
# --------------------------------------------------------------------------

def test_config_json_maps_onto_the_registered_model():
    """The published config.json (the benchmark's configuration file holds
    every key of it, cut to 16 layers) through ``config_from_hf_json`` is
    the registered model, depth aside; a ``layer_types`` the parser does
    not describe, a rotary base or a window is refused."""
    with open(CONFIG_FILE) as f:
        hf = json.load(f)
    got = config_from_hf_json("x", hf)
    want = get_model_config(PUBLISHED)
    skip = {"name", "num_layers", "bos_token_id", "eos_token_id",
            "linear_layers"}
    for field in dataclasses.fields(want):
        if field.name not in skip:
            assert getattr(got, field.name) == getattr(want, field.name), \
                field.name
    assert got.num_layers == 16 and want.num_layers == 32
    assert got.linear_layers == want.linear_layers[:16]
    for bad in ({"layer_types": hf["layer_types"][:-1]},
                {"layer_types": ["sliding_attention"] * 16},
                {"rope_parameters": {"rope_theta": 500000.0}},
                {"sliding_window": 4096},
                {"linear_num_key_heads": 15}):
        with pytest.raises(ValueError):
            config_from_hf_json("x", {**hf, **bad})


def test_an_hf_checkpoint_loads_into_the_same_forward(cfg, params):
    """The ASSUMED tensor names through the loader give the tree
    ``init_params`` builds: same logits."""
    from tpuserve.models.weights import _load_olmo_hybrid
    raw = {"model.embed_tokens.weight": params["embed"]["weight"],
           "model.norm.weight": params["final_norm"]["scale"],
           "lm_head.weight": params["lm_head"]["kernel"].T}
    H, dk = cfg.lin_num_key_heads, cfg.lin_key_head_dim
    for i, lp in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        raw[pre + "post_attention_layernorm.weight"] = \
            lp["post_attn_norm"]["scale"]
        raw[pre + "post_feedforward_layernorm.weight"] = \
            lp["post_mlp_norm"]["scale"]
        for p in ("gate", "up", "down"):
            raw[pre + f"mlp.{p}_proj.weight"] = lp[f"{p}_proj"]["kernel"].T
        if "lin" in lp:
            sp, la = lp["lin"], pre + "linear_attn."
            for p in ("g", "a", "b", "o"):
                raw[la + f"{p}_proj.weight"] = sp[f"{p}_proj"]["kernel"].T
            qkv = sp["qkv_proj"]["kernel"].T                # (C, hidden)
            raw[la + "q_proj.weight"] = qkv[:H * dk]
            raw[la + "k_proj.weight"] = qkv[H * dk:2 * H * dk]
            raw[la + "v_proj.weight"] = qkv[2 * H * dk:]
            conv = sp["conv"]["kernel"].T[:, None, :]       # (C, 1, W)
            raw[la + "q_conv1d.weight"] = conv[:H * dk]
            raw[la + "k_conv1d.weight"] = conv[H * dk:2 * H * dk]
            raw[la + "v_conv1d.weight"] = conv[2 * H * dk:]
            raw[la + "A_log"], raw[la + "dt_bias"] = sp["A_log"], \
                sp["dt_bias"]
            raw[la + "o_norm.weight"] = sp["norm"]["scale"]
        else:
            for p in ("q", "k", "v", "o"):
                raw[pre + f"self_attn.{p}_proj.weight"] = \
                    lp[f"{p}_proj"]["kernel"].T
            for p in ("q", "k"):
                raw[pre + f"self_attn.{p}_norm.weight"] = \
                    lp[f"{p}_norm"]["scale"]
    loaded = _load_olmo_hybrid(cfg, raw, jnp.float32)
    tokens = jnp.asarray(prompts_of(17, seed=6), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(transformer.forward(loaded, cfg, tokens)),
        np.asarray(transformer.forward(params, cfg, tokens)))


def test_each_family_is_kept_from_the_other_reference(cfg):
    """``olmo_hybrid.check_family`` refuses every other family, Falcon-H1
    (the other model with a seat pool) included, and the Falcon-H1
    reference refuses this one; the configuration file describes what
    runs, lists and dicts compared as such."""
    ref.check_family(cfg)
    ref.check_family(get_model_config(PUBLISHED))
    for name in ("tiny-qwen3", "tiny-mistral", "tiny-llama",
                 "tiny-falcon-h1", "tiny-k-exaone"):
        with pytest.raises(ValueError, match="not the Olmo-Hybrid family"):
            ref.check_family(get_model_config(name))
    with pytest.raises(ValueError, match="not the Falcon-H1 family"):
        plan.load_reference({"reference": "falcon_h1"}).check_family(cfg)
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    assert plan.unchecked_keys(config, ref) == []
    loose = plan.unchecked_keys(
        config, plan.load_reference({"reference": "dense_gqa"}))
    assert {"linear_key_head_dim", "layer_types", "rope_parameters"} \
        <= set(loose)
    model_cfg = dataclasses.replace(
        get_model_config(config["model"]),
        **plan.architecture_overrides(config))
    assert plan.published_mismatches(
        config, get_model_config(config["model"])) == []
    assert plan.architecture_mismatches(config, model_cfg, ref) == []
    wrong = {**config, "linear_allow_neg_eigval": False,
             "layer_types": ["full_attention"] * 16}
    assert len(plan.architecture_mismatches(wrong, model_cfg, ref)) == 2
