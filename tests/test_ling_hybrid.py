"""Ling-3.0-flash's language model on the normal path: Kimi-delta
linear-attention layers (the gated delta rule with a decay for every key
channel) five to one with latent-attention layers (a q/k norm, a sigmoid
gate a head), a seat pool AND latent pages in one engine, behind a
group-limited sigmoid router with a selection bias, as one chip's share of
a deployment whose chips share each expert layer.

Everything is compared with the plain reference the benchmark scores this
family by (``benchmark/reference/ling_hybrid.py``: float32, the recurrence
ROW BY ROW, latent attention in its naive form, every HELD expert on every
token weighted by the router's choice over ALL experts in ALL groups; no
code shared with ``tpuserve``), on the registered ``tiny-ling-hybrid``
(float32; two periods of K K A, 4 heads of 16, a scan chunk of 32 in
sub-blocks of 16, a cached vector of 136 + 12 stored as 256, one dense
layer, then 8 experts in 2 groups of which 1 survives, 2 a token) under
seeded random weights with a random selection bias.  Logits, not tokens.

Tolerances: both sides are float32 on the CPU, so what separates them is
the ORDER of the same sums; the chunked scan's (a triangular solve and
products of decays split about a sub-block's first row, against a row at a
time) is the widest, Olmo-Hybrid's 5e-4 on logits of size ~1-3 (the
worst route reads a tenth of it); the scan and the kernel alone are held
to 2e-5.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_routes import (BLOCK, FAMILIES, ROOT, SEATS, engine_for, plan,
                           prompts_of, ref_greedy, run_route, serve)
from tpuserve.models import transformer
from tpuserve.models.config import (MIXER_ATTENTION, MIXER_LINEAR,
                                    ModelConfig, config_from_hf_json,
                                    get_model_config)
from tpuserve.models.weights import init_params
from tpuserve.ops import gated_delta as gd
from tpuserve.ops import pallas_kda_update as upd
from tpuserve.ops.pallas_gdn_update import heads_per_slab, to_slabs
from tpuserve.runtime import CacheConfig
from tpuserve.runtime.kv_cache import (bytes_per_block, create_kv_cache,
                                       create_ssm_state, ssm_state_bytes)

FAMILY = FAMILIES["ling_hybrid"]
ATOL = FAMILY.atol
MODEL = FAMILY.model
PUBLISHED = "inclusionAI/Ling-3.0-flash-VL"
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-vl-ep8-l12.json")
EXPERT_LAYER = 1        # the first layer after the dense one (a KDA layer)
BOUND = -5.0

ref = FAMILY.ref


def with_bias(params, cfg, seed=3):
    """``params`` with a random selection bias in every expert layer (the
    engine draws zeros: a trained bias is the checkpoint's)."""
    rs = np.random.RandomState(seed)
    return dict(params, layers=[
        dict(lp, router_bias={"bias": jnp.asarray(
            0.1 * rs.randn(cfg.num_experts), jnp.float32)})
        if "router_bias" in lp else lp for lp in params["layers"]])


@pytest.fixture(scope="module")
def cfg():
    return get_model_config(MODEL)


@pytest.fixture(scope="module")
def params(cfg):
    return with_bias(init_params(cfg, seed=7), cfg)


def share_of(cfg: ModelConfig, params, first: int, held: int):
    """``(cfg, params)`` of the share that holds experts ``first`` to
    ``first + held - 1``: the ModelConfig told so and the tree with those
    experts' kernels alone (the router's every column and the bias's every
    entry as they were)."""
    layers = []
    for lp in params["layers"]:
        if "experts" in lp:
            lp = dict(lp, experts={
                name: {"kernel": p["kernel"][first:first + held]}
                for name, p in lp["experts"].items()})
        layers.append(lp)
    return (dataclasses.replace(cfg, name=f"{cfg.name}-from{first}",
                                moe_experts_held=held,
                                moe_first_expert=first),
            dict(params, layers=layers))


# --------------------------------------------------------------------------
# the trunks, driven by hand: logits against the reference at every position
# --------------------------------------------------------------------------

def test_the_plain_forward_is_the_reference(cfg, params):
    seq = prompts_of(37, seed=5)[0]
    got = np.asarray(transformer.forward(params, cfg, jnp.asarray([seq])))[0]
    want = np.asarray(ref.logits_at(params, cfg, np.asarray([seq], np.int32),
                                    [(0, t) for t in range(37)]))
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("route,attn_impl", [
    ("prefill", "reference"), ("packed", "reference"), ("packed", "pallas"),
    ("chunks", "reference"), ("chunks", "pallas")])
def test_every_route_matches_the_reference_under_a_share(cfg, params, route,
                                                         attn_impl):
    """(B, L) prefill, a packed prefill of three uneven prompts (5, 19 and
    37 rows: none a multiple of the chunk of 32 or its sub-block of 16) or
    one prompt over three chunks; then decode steps and a fused window
    through the pool AND the latent pages, every logit against the
    reference's full forward pass -- under the share the cell holds, group
    0 of the two.  ``pallas``: the state update's, the convolution
    memory's and the paged latent kernels in interpret mode."""
    scfg, sparams = share_of(cfg, params, 0, 4)
    served = run_route(FAMILY, scfg, sparams, route, attn_impl)
    assert len(served.kv) == 2 and set(served.kv[0]) == {"k"}
    assert len(served.pool) == 4
    E = cfg.num_experts
    counts = served.counts
    assert counts[:E].sum() > 0
    # under the share: what landed on group 0 (held rows <= routed rows)
    # and, fifth, the rows whose group 0 survived
    held_rows, group_rows = counts[E + 1], counts[E + 5]
    assert 0 < held_rows <= counts[:4].sum() + 1
    assert held_rows == counts[:4].sum()
    # every held pick is of a row whose group survived, k a row at most
    assert held_rows <= cfg.num_experts_per_tok * group_rows
    assert 0 < group_rows < counts[:E].sum() // cfg.num_experts_per_tok


def test_the_grid_prefill_has_no_pallas_form(cfg, params):
    with pytest.raises(NotImplementedError, match="go out packed"):
        run_route(FAMILY, cfg, params, "prefill", "pallas")


# --------------------------------------------------------------------------
# the recurrence: chunked scan and decode kernel against kda_step
# --------------------------------------------------------------------------

def _unit(y):
    return y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)


def _rows(length, T, H, dk, dv, seed, g_all=None):
    rs = np.random.RandomState(seed)
    q = (_unit(rs.randn(T, H, dk)) * dk ** -0.5).astype(np.float32)
    k = _unit(rs.randn(T, H, dk)).astype(np.float32)
    v = rs.randn(T, H, dv).astype(np.float32)
    g = (BOUND / (1 + np.exp(-2 * rs.randn(T, H, dk)))).astype(np.float32)
    if g_all is not None:
        g[:] = g_all
    beta = (1 / (1 + np.exp(-rs.randn(T, H)))).astype(np.float32)
    g[length:], beta[length:] = 0, 0                # as _lin_inputs pads
    return q, k, v, g, beta


def _token_loop(s0, q, k, v, g, beta, length, step=gd.kda_step):
    s, outs = jnp.asarray(s0), []
    for t in range(length):
        o, s = step(s, q[t][None], k[t][None], v[t][None], g[t][None],
                    beta[t][None])
        outs.append(o[0])
    return np.stack(outs), np.asarray(s[0])


def _scan(q, k, v, g, beta, s0, chunk_seq, Q, sub=gd.SUB_BLOCK,
          scan=gd.kda_chunk_scan, **kw):
    T, H, dk = q.shape
    dv = v.shape[-1]
    x = np.concatenate([a.reshape(T, -1) for a in (q, k, v)], -1)

    def split(xg):
        n = xg.shape[0]
        return (xg[:, :H * dk].reshape(n, H, dk),
                xg[:, H * dk:2 * H * dk].reshape(n, H, dk),
                xg[:, 2 * H * dk:].reshape(n, H, dv))

    if scan is gd.kda_chunk_scan:
        kw["sub_block"] = sub
    o, finals = jax.jit(lambda *a: scan(*a, chunk=Q, split=split, **kw))(
        x, g, beta, s0, np.asarray(chunk_seq, np.int32))
    return np.asarray(o), np.asarray(finals)


@pytest.mark.parametrize("length", [1, 15, 16, 17, 63, 64, 65, 200])
def test_the_chunked_scan_is_the_plain_recurrence(length):
    """Chunks of 64 in sub-blocks of 16 against one row at a time, from a
    state that is not zero, at lengths that are no multiple of the chunk
    or of the sub-block."""
    Q, H, dk, dv = 64, 3, 16, 24
    T = -(-length // Q) * Q
    q, k, v, g, beta = _rows(length, T, H, dk, dv, seed=length)
    s0 = np.random.RandomState(9).randn(1, H, dk, dv).astype(np.float32)
    want, final = _token_loop(s0, q, k, v, g, beta, length)
    seq = np.where(np.arange(T // Q) * Q < length, 0, -1)
    got, finals = _scan(q, k, v, g, beta, s0, seq, Q)
    np.testing.assert_allclose(got[:length], want, atol=2e-5)
    np.testing.assert_allclose(finals[0], final, atol=2e-5)


@pytest.mark.parametrize("g_all", [BOUND, 0.0])
def test_every_gate_at_the_bound_for_a_whole_chunk_stays_finite(g_all):
    """Every channel of every row AT ``kda_lower_bound`` for two whole
    chunks: inside a sub-block of 16 the split exponent reaches 75, under
    ln(float32 max); the result is finite and the row-by-row one.  With ONE
    sub-block a chunk (the scalar form's way) the same rows overflow.  And
    at 0 (no decay at all) the delta rule alone."""
    Q, H, dk, dv = 64, 2, 16, 16
    q, k, v, g, beta = _rows(128, 128, H, dk, dv, seed=2, g_all=g_all)
    s0 = np.random.RandomState(4).randn(1, H, dk, dv).astype(np.float32)
    want, final = _token_loop(s0, q, k, v, g, beta, 128)
    got, finals = _scan(q, k, v, g, beta, s0, [0, 0], Q)
    assert np.isfinite(got).all() and np.isfinite(finals).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(finals[0], final, atol=2e-5)
    if g_all == BOUND:
        whole, _ = _scan(q, k, v, g, beta, s0, [0, 0], Q, sub=64)
        assert not np.isfinite(whole).all()


def test_the_chunked_scan_keeps_packed_sequences_apart():
    """Three sequences on one flat axis, each from its own state, a
    padding chunk between them: each is its own token loop."""
    Q, H, dk, dv = 8, 2, 16, 16
    lens, starts = (13, 8, 3), (0, 16, 32)
    T = 48
    q, k, v, g, beta = _rows(T, T, H, dk, dv, seed=11)
    live = np.zeros(T, bool)
    for n, s in zip(lens, starts):
        live[s:s + n] = True
    g[~live], beta[~live] = 0, 0
    s0 = np.random.RandomState(5).randn(3, H, dk, dv).astype(np.float32)
    seq = [0, 0, 1, -1, 2, -1]
    got, finals = _scan(q, k, v, g, beta, s0, seq, Q, sub=4)
    for i, (n, s) in enumerate(zip(lens, starts)):
        want, final = _token_loop(s0[i:i + 1], q[s:], k[s:], v[s:], g[s:],
                                  beta[s:], n)
        np.testing.assert_allclose(got[s:s + n], want, atol=2e-5)
        np.testing.assert_allclose(finals[i], final, atol=2e-5)


def test_equal_channels_are_the_scalar_gate():
    """A vector decay whose channels are all equal IS Olmo-Hybrid's scalar
    one: the step, and the chunked scan against the scalar form's."""
    Q, H, dk, dv = 64, 3, 16, 24
    q, k, v, g, beta = _rows(100, 128, H, dk, dv, seed=6)
    g[:] = g[..., :1]
    s0 = np.random.RandomState(1).randn(1, H, dk, dv).astype(np.float32)
    want, final = _token_loop(
        s0, q, k, v, g[..., 0], beta, 100, step=gd.gated_delta_step)
    got, got_final = _token_loop(s0, q, k, v, g, beta, 100)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got_final, final, atol=1e-6)
    scalar, _ = _scan(q, k, v, g[..., 0], beta, s0, [0, 0], Q,
                      scan=gd.gated_delta_chunk_scan)
    vector, _ = _scan(q, k, v, g, beta, s0, [0, 0], Q)
    np.testing.assert_allclose(vector[:100], scalar[:100], atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 4, 16, 16), (3, 6, 24, 48),
                                   (2, 2, 128, 128), (5, 3, 16, 16)])
def test_the_state_update_kernel_is_one_step_of_the_recurrence(shape):
    """``_kda_state_update`` in interpret mode against ``kda_step`` on
    gathered rows: slabs of two heads, of one (an odd count; the published
    128-lane head), scattered seats, a padding row on the trash seat."""
    B, H, dk, dv = shape
    rs = np.random.RandomState(sum(shape))
    hp, S = heads_per_slab(H, dv), B + 3
    states = rs.randn(S, H, dk, dv).astype(np.float32)
    pool = to_slabs(jnp.asarray(states), hp)
    seats = np.concatenate([rs.permutation(S - 1)[:B - 1], [S - 1]])
    q, k, v, g, beta = _rows(B, B, H, dk, dv, seed=B)
    q[-1] = k[-1] = v[-1] = g[-1] = beta[-1] = 0    # as _lin_inputs pads
    want_o, want_s = gd.kda_step(jnp.asarray(states[seats]), q, k, v, g,
                                 beta)
    ref_o, ref_pool = upd.kda_state_update_reference(
        pool, jnp.asarray(seats), q, k, v, g, beta)
    got_o, got_pool = upd.kda_state_update(
        jnp.array(pool), jnp.asarray(seats), q, k, v, g, beta,
        interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(ref_o, want_o, atol=1e-6)
    np.testing.assert_allclose(got_pool, ref_pool, atol=2e-5)
    np.testing.assert_allclose(got_pool[seats], to_slabs(want_s, hp),
                               atol=2e-5)
    untouched = np.setdiff1d(np.arange(S), seats)
    assert np.array_equal(np.asarray(got_pool)[untouched],
                          np.asarray(pool)[untouched])


# --------------------------------------------------------------------------
# the share and the groups
# --------------------------------------------------------------------------

def rows_of(n, seed=1, hidden=64):
    return jnp.asarray(np.random.RandomState(seed).randn(n, hidden),
                       jnp.float32)


def ref_layer(lp, h, cfg):
    none = jnp.full((h.shape[0], cfg.num_experts_per_tok), -1, jnp.int32)
    return (np.asarray(ref._held_experts(lp, h, cfg, none)),
            np.asarray(ref._gated_mlp(h, lp["shared"])))


@pytest.mark.parametrize("held", [1, 4])
def test_the_shares_add_up_to_the_uncut_layer(cfg, params, held):
    """What ties the share to the model: at the tiny size the held parts of
    all eight chips that hold an expert each (and of the two that hold a
    routing group each, the cell's way), with the shared expert (which
    every chip computes alike) counted once, are the uncut reference layer
    and the uncut served layer; each share is the reference handed that
    share; and no share is the whole."""
    lp, h = params["layers"][EXPERT_LAYER], rows_of(37)
    uncut_routed, always = ref_layer(lp, h, cfg)
    parts = []
    for first in range(0, cfg.num_experts, held):
        scfg, sparams = share_of(cfg, params, first, held)
        slp = sparams["layers"][EXPERT_LAYER]
        got = np.asarray(transformer._moe_mlp(h, slp, scfg))
        routed, _ = ref_layer(slp, h, scfg)
        np.testing.assert_allclose(got, routed + always, atol=1e-5)
        parts.append(got - always)
    assert len(parts) == cfg.num_experts // held
    np.testing.assert_allclose(sum(parts) + always, uncut_routed + always,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(transformer._moe_mlp(h, lp, cfg)),
                               uncut_routed + always, atol=1e-5)
    assert all(np.max(np.abs(p - uncut_routed)) > 1e-2 for p in parts)


def test_the_router_limits_the_groups_and_weighs_by_unbiased_scores(cfg,
                                                                    params):
    """The picks lie in ONE of the two groups (the one whose two best
    BIASED scores sum highest), are the 2 best biased scores there, and
    weigh 2.5 p / (sum of the 2 + 1e-20) by the UNBIASED scores; the
    fifth count of a share is the rows whose group it holds survived."""
    lp, h = params["layers"][EXPERT_LAYER], rows_of(41, seed=4)
    scfg, sparams = share_of(cfg, params, 0, 4)
    tally = []
    transformer._moe_mlp(h, sparams["layers"][EXPERT_LAYER], scfg, tally)
    (_, picks, landed), = tally
    picks = np.asarray(picks)
    p = 1 / (1 + np.exp(-np.asarray(h, np.float64)
                        @ np.asarray(lp["router"]["kernel"], np.float64)))
    c = p + np.asarray(lp["router_bias"]["bias"], np.float64)
    group = np.sort(c.reshape(41, 2, 4), -1)[..., -2:].sum(-1).argmax(-1)
    assert np.array_equal(picks // 4, np.stack([group, group], 1))
    inside = np.where(np.arange(8)[None, :] // 4 == group[:, None], c, 0.0)
    want = np.argsort(-inside, axis=1)[:, :2]
    assert np.array_equal(np.sort(picks, 1), np.sort(want, 1))
    assert 0 < (group == 0).sum() < 41
    assert int(landed[4]) == (group == 0).sum()
    assert int(landed[0]) == 2 * (group == 0).sum()
    none = jnp.full((41, 2), -1, jnp.int32)
    w = np.asarray(ref.route(lp, h, cfg, none))
    top = np.take_along_axis(p, want, 1)
    np.testing.assert_allclose(
        np.take_along_axis(w, want, 1),
        2.5 * top / (top.sum(1, keepdims=True) + 1e-20), atol=1e-6)
    # without the limit a fifth of these rows would pick across the groups
    plain = np.argsort(-c, axis=1)[:, :2]
    assert (plain // 4 != group[:, None]).any()


def test_the_reference_replays_a_near_tied_group(cfg, params):
    """A row whose two groups score within ``GROUP_TIE``: handed the
    server's picks from the OTHER group the reference evaluates with them;
    handed picks from a group far behind it keeps its own."""
    lp = params["layers"][EXPERT_LAYER]
    h = rows_of(400, seed=8)
    none = jnp.full((400, 2), -1, jnp.int32)
    own = np.asarray(ref.route(lp, h, cfg, none))
    p = 1 / (1 + np.exp(-np.asarray(h, np.float64)
                        @ np.asarray(lp["router"]["kernel"], np.float64)))
    c = p + np.asarray(lp["router_bias"]["bias"], np.float64)
    scores = np.sort(c.reshape(400, 2, 4), -1)[..., -2:].sum(-1)
    gap = np.abs(scores[:, 0] - scores[:, 1])
    near, far = int(np.argmin(gap)), int(np.argmax(gap))
    assert gap[near] < ref.GROUP_TIE / 4 and gap[far] > 4 * ref.GROUP_TIE
    for row, replayed in ((near, True), (far, False)):
        other = 1 - int(scores[row].argmax())
        picks = other * 4 + np.argsort(-c[row, other * 4:other * 4 + 4])[:2]
        served = np.full((400, 2), -1, np.int32)
        served[row] = picks
        got = np.asarray(ref.route(lp, h, cfg, jnp.asarray(served)))
        assert (set(np.nonzero(got[row])[0]) == set(picks)) == replayed
        if not replayed:
            np.testing.assert_array_equal(got[row], own[row])
        np.testing.assert_array_equal(np.delete(got, row, 0),
                                      np.delete(own, row, 0))


# --------------------------------------------------------------------------
# each layer holds its own kind of memory
# --------------------------------------------------------------------------

def test_latent_pages_for_the_attention_layers_and_state_for_the_linear(cfg):
    """``layer_mixer`` by layer, and what follows from it: latent pages
    (ONE array a layer, no V) for 2 layers, a pool for 4, at the tiny size;
    2 and 10 of the 12 the cell runs at the published one, whose state is
    one head a slab (``dv`` is a lane tile)."""
    assert [cfg.layer_mixer(i) for i in range(6)] == (
        [MIXER_LINEAR] * 2 + [MIXER_ATTENTION]) * 2
    assert cfg.kv_layers == (2, 5) and cfg.state_layers == (0, 1, 3, 4)
    assert cfg.is_mla and cfg.has_state and cfg.layer_group_size == 3
    cc = CacheConfig(block_size=BLOCK, num_blocks=8, max_blocks_per_seq=8,
                     dtype="float32")
    kv, pool = create_kv_cache(cfg, cc), create_ssm_state(cfg, SEATS)
    assert len(kv) == 2 and len(pool) == 4
    assert set(kv[0]) == {"k"} and kv[0]["k"].shape == (8, BLOCK, 1, 256)
    assert pool[0]["state"].shape == (SEATS + 1, 2, 16, 32)
    assert bytes_per_block(cfg, cc) == sum(
        x.nbytes for x in jax.tree.leaves(kv)) // 8
    assert ssm_state_bytes(cfg, SEATS) == sum(
        x.nbytes for x in jax.tree.leaves(pool))
    big = dataclasses.replace(get_model_config(PUBLISHED), num_layers=12)
    assert big.kv_layers == (5, 11) and len(big.state_layers) == 10
    assert big.layer_group_size == 6
    served = CacheConfig(block_size=32, num_blocks=16, max_blocks_per_seq=16)
    # 2 latent layers x ONE array x 640 lanes x 2 B
    assert bytes_per_block(big, served) // 32 == 2 * 640 * 2 == 2560
    assert ssm_state_bytes(big, 128) == 10 * 129 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 4) == 2_895_544_320
    shapes = jax.eval_shape(lambda: create_ssm_state(big, 128))
    assert shapes[0]["state"].shape == (129, 32, 128, 128)
    assert (big.qk_head_dim, big.head_dim, big.attn_scale) == (
        192, 128, 192 ** -0.5)
    assert (big.mla_latent_dim, big.cache_head_dim) == (576, 640)
    # a family whose head_dim IS the q/k width keeps it
    pangu = get_model_config("tiny-pangu")
    assert pangu.qk_head_dim == pangu.head_dim == 28


def test_the_share_is_counted_from_the_shapes():
    """The cell's 12 layers under the share, from the parameter tree's
    shapes: 4,736 M parameters, 9.47 GB."""
    big = dataclasses.replace(get_model_config(PUBLISHED), num_layers=12,
                              moe_experts_held=64, vocab_size=19648)
    shapes = jax.eval_shape(lambda: init_params(big, 0))
    layers = [sum(int(np.prod(x.shape)) for x in jax.tree.leaves(lp))
              for lp in shapes["layers"]]
    kda, latent = 63_055_008, 31_971_072
    dense, sparse = 47_185_920, 384_696_832
    assert layers[0] == kda + dense and layers[5] == latent + sparse
    assert layers[2] == kda + sparse and big.lin_layer_params == kda
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == 10 * kda + 2 * latent + 2 * dense + 10 * sparse \
        + 2 * 19648 * 2560 + 2560 == 4_736_432_704
    nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves(shapes))
    assert 9.47e9 < nbytes < 9.48e9


# --------------------------------------------------------------------------
# no equation dropped: every term moves the logits
# --------------------------------------------------------------------------

def _scaled(tree, layer, path, factor):
    out = jax.tree.map(lambda x: x, tree)
    node = out["layers"][layer]
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = node[path[-1]] * factor
    return out


TERMS = {
    "A_log": (0, ("lin", "A_log"), 3.0),
    "dt_bias": (0, ("lin", "dt_bias"), -1.0),
    "decay": (0, ("lin", "f_proj", "kernel"), 4.0),
    "step size": (0, ("lin", "b_proj", "kernel"), 6.0),
    "conv": (0, ("lin", "conv", "kernel")),
    "output gate": (0, ("lin", "g_proj", "kernel"), 4.0),
    "head norm": (0, ("lin", "norm", "scale")),
    "mixer norm": (0, ("attn_norm", "scale")),
    "q norm": (2, ("q_norm", "scale"), 3.0),
    "k norm": (2, ("k_norm", "scale"), 4.0),
    "latent norm": (2, ("kv_a_norm", "scale"), 2.0),
    "head gate": (2, ("attn_gate_proj", "kernel"), 6.0),
    "selection bias": (1, ("router_bias", "bias"), -8.0),
}


@pytest.mark.parametrize("what", sorted(TERMS))
def test_every_term_of_the_layers_is_live(cfg, params, what):
    """Each parameter of the two mixers and of the router that a plain
    dense trunk lacks: changed on BOTH sides, the served trunk and the
    reference still agree (the term is implemented, and in the same
    place); the logits part from the unchanged ones by far more than the
    tolerance (it is not a no-op under these weights)."""
    layer, path, *factor = TERMS[what]
    tokens = np.asarray(prompts_of(21, seed=3), np.int32)
    rows = [(0, t) for t in range(21)]
    base = np.asarray(transformer.forward(params, cfg, jnp.asarray(tokens)))[0]
    params2 = _scaled(params, layer, path, factor[0] if factor else 1.5)
    moved = np.asarray(transformer.forward(params2, cfg,
                                           jnp.asarray(tokens)))[0]
    want = np.asarray(ref.logits_at(params2, cfg, tokens, rows))
    np.testing.assert_allclose(moved, want, atol=ATOL)
    assert np.abs(moved - base).max() > 20 * ATOL, what


# --------------------------------------------------------------------------
# through the engine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("multi_step,attn_impl", [
    (1, "reference"), (4, "pallas")])
def test_served_greedy_tokens_are_the_references(cfg, params, multi_step,
                                                 attn_impl):
    """Through ``Engine.step`` under the share: packed prefill (prompts of
    5 and 11), chunked prefill (23 and 40 against a 16-token chunk), then
    single steps or fused windows -- token for token the float32
    reference's greedy continuation; a seat a sequence, given back; the
    step records and the totals carry the Kimi-delta row-layers (from host
    integers) and the rows whose group survived here (from the device)."""
    scfg, sparams = share_of(cfg, params, 0, 4)
    engine = engine_for(FAMILY, sparams, scfg, multi_step=multi_step,
                        attn_impl=attn_impl)
    assert engine._packed_prefill
    assert len(engine.ssm_state) == 4 and len(engine.kv_cache) == 2
    prompts = prompts_of(5, 11, 23, 40, seed=1)
    got = serve(engine, prompts)
    for p, toks in zip(prompts, got):
        assert toks == ref_greedy(FAMILY, sparams, scfg, p, 10)
    assert engine.stats.ssm_state_resets == 4
    assert engine.block_manager.seats.in_use == 0
    stats = engine.stats
    # every row of every decode step dispatched (a fused window runs its 4
    # steps whatever a sequence still needs: 9 tokens take 3 windows), on 4
    # linear layers
    served = 4 * 4 * (9 if multi_step == 1 else 12)
    assert stats.kda_state_row_layers == served
    steps = engine.flight.steps_snapshot(limit=4096)
    assert sum(s.get("kda_row_layers", 0) for s in steps) == served
    assert all(s.get("kda_row_layers", 0) == 4 * s["actual_tokens"]
               * (s["kind"] in ("decode", "window")) for s in steps)
    assert 0 < stats.moe_group_rows == sum(
        s.get("moe_group_rows", 0) for s in steps)
    assert stats.moe_held_rows <= 2 * stats.moe_group_rows
    assert stats.moe_group_rows < stats.moe_routed_rows // 2
    assert stats.kv_latent_tokens_attended_total > 0


# what the engine served before the group-limited selection went sort-free
# (recorded on the ``lax.top_k`` form): a position a word, its five expert
# layers' two picks each a digit
SERVED_PICKS = {
    19: "5764542357 0176327613 6503672321 7631207575 2032207565 6756032356 "
        "0130742121 6754645765 4665742167 1230457621 1054767621 6556746776 "
        "5431757465 1254757676 0147763056 0113204674 0145757467 4664026756 "
        "4657317502 6465756764 7675203212 0230656556 1064756774 0256323176",
    7: "5431202310 6757207513 0231455445 0131655713 1031200276 0167302156 "
       "0156471257 0357323102 0154216776 0156672376 2057677664 1031767657",
}


def test_the_served_picks_and_group_rows_are_what_they_were(cfg, params):
    """``routed_experts`` of two fixed prompts, layer by layer, and the
    count behind ``tpuserve_moe_group_rows_total`` for them: the selection
    chooses what it chose by ``lax.top_k``."""
    from tpuserve.runtime import SamplingParams
    scfg, sparams = share_of(cfg, params, 0, 4)
    engine = engine_for(FAMILY, sparams, scfg, multi_step=4)
    prompts = prompts_of(*SERVED_PICKS, seed=7)
    outs = engine.generate(prompts, SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True, logprobs=1))
    for prompt, out in zip(prompts, outs):
        got = np.asarray(out.logprobs[0]["prompt_routed_experts"]
                         + [e["routed_experts"] for e in out.logprobs[1:]])
        assert got.shape == (len(prompt) + 5, 5, 2)
        assert " ".join("".join(map(str, row.reshape(-1)))
                        for row in got) == SERVED_PICKS[len(prompt)]
    stats = engine.stats
    assert (stats.moe_group_rows, stats.moe_held_rows,
            stats.moe_routed_rows) == (134, 268, 560)


def test_the_counters_reach_the_metrics_page(cfg, params):
    from tpuserve.server.metrics import ServerMetrics
    m = ServerMetrics(MODEL)
    page = m.render().decode()
    for name in ("tpuserve_moe_group_rows_total",
                 "tpuserve_kda_state_row_layers_total"):
        assert f'{name}{{model_name="{MODEL}"}} 0.0' in page
    # a model of the scalar gate counts none
    olmo = engine_for(FAMILIES["olmo_hybrid"])
    serve(olmo, prompts_of(5, seed=1), max_tokens=3)
    assert olmo.stats.kda_state_row_layers == 0
    assert olmo.stats.moe_group_rows == 0


def test_what_the_engine_observes_of_a_pool_beside_latent_pages(caplog):
    """No option: with a state in ANY layer the prefix cache, the KV tier
    and mixed batching are off, each with its logged sentence; the decode
    route stays on the phase split; the pool is accounted beside the latent
    cache: 4 layers of state, 2 of pages."""
    import logging
    with caplog.at_level(logging.INFO, logger="tpuserve.engine"):
        engine = engine_for(FAMILY, enable_prefix_caching=True,
                            kv_tiers=True,
                            scheduler={"mixed_batching": True})
    assert not engine.block_manager.enable_prefix_caching
    assert engine._kv_tiers is None
    assert not engine.scheduler.cfg.mixed_batching
    assert "prefix caching and the KV tier are off" in caplog.text
    assert "mixed ragged batching is off" in caplog.text
    cfg = engine.model_cfg
    want = ssm_state_bytes(cfg, 4)
    assert want == 4 * 5 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert sum(x.nbytes for x in jax.tree.leaves(engine.ssm_state)) == want
    hbm = engine.devprof.hbm_snapshot()
    assert hbm["state_bytes"] == want
    assert hbm["kv_reserved_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(engine.kv_cache))


# --------------------------------------------------------------------------
# the configuration, the parser and the reference's family check
# --------------------------------------------------------------------------

def catalog_config() -> dict:
    """The catalog's ``config`` of the model (model-configs guide,
    architectures.jsonl), where the catalog is; else the configuration
    file's keys with the three cuts undone."""
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if os.path.isfile(row):
        with open(row) as f:
            hf = next(json.loads(line) for line in f
                      if '"name": "Ling-3.0-flash-VL"' in line)["config"]
        # (the VL row states no model_type; its sibling row, whose language
        # model is the same, does)
        return dict(hf, model_type="bailing_hybrid")
    with open(CONFIG_FILE) as f:
        data = json.load(f)
    hf = {k: v for k, v in data.items() if k not in plan.OWN_KEYS}
    hf.update(data["published"])
    for key, tail in (("expert_swiglu_limit_list", [0] * 23 + [4] * 7),
                      ("share_expert_swiglu_limit_list",
                       [0] * 22 + [5] * 6 + [7] * 2)):
        hf[key] = hf[key] + tail
    return hf


def cut_config(layers=12) -> dict:
    hf = dict(catalog_config(), num_hidden_layers=layers)
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        hf[key] = hf[key][:layers]
    return hf


def test_config_json_maps_onto_the_registered_model():
    """The catalog's ``config``, cut to the layers whose activation is not
    clamped, gives the preset cut alike, field for field, and the
    properties a configuration file's keys are held to spell it back; the
    whole depth is refused for its clamped layers, by name."""
    hf = catalog_config()
    assert hf["layer_group_size"] == 6 and hf["num_hidden_layers"] == 42
    with pytest.raises(ValueError, match="expert_swiglu_limit_list clamps"):
        config_from_hf_json(PUBLISHED, hf)
    whole = get_model_config(PUBLISHED)
    assert get_model_config("ling-3.0-flash-vl") is whole
    with pytest.raises(ValueError, match="clamps the gated activation"):
        init_params(whole)
    assert whole.expert_swiglu_limit_list == hf["expert_swiglu_limit_list"]
    assert whole.share_expert_swiglu_limit_list \
        == hf["share_expert_swiglu_limit_list"]
    for layers in (12, 34):
        cut = cut_config(layers)
        got = config_from_hf_json(PUBLISHED, cut)
        want = dataclasses.replace(whole, num_layers=layers)
        for f in dataclasses.fields(ModelConfig):
            a, b = getattr(got, f.name), getattr(want, f.name)
            if isinstance(a, tuple) and f.name != "mlp_multipliers":
                a, b = a[:layers], b[:layers]
            assert a == b, f.name
        for key, field in {**plan.FIXED, **plan.CUTTABLE,
                           **ref.FIXED}.items():
            if key in cut:
                assert getattr(got, field) == cut[key], key
        assert set(cut) <= set(plan.FIXED) | set(plan.CUTTABLE) \
            | set(ref.FIXED) | set(plan.DESCRIPTIVE) | set(ref.DESCRIPTIVE)
        ref.check_family(got)
        assert got.layer_types == want.layer_types
    assert got.layer_types[:6] == ["linear_attention"] * 5 + [
        "full_attention"]
    assert whole.layer_mixer(41) == MIXER_ATTENTION
    assert sum(whole.linear_layers) == 35
    assert whole.lin_gate == "channel" and whole.lin_gate_lower_bound == -5
    ref.check_family(get_model_config(MODEL))


def test_the_configuration_file_is_the_catalogs_row_cut():
    """Every key of the catalog's ``config`` is in the file under the same
    key with the same value, but the three under ``reduced`` and the two
    lists cut with the depth; and the file parses, through the family's
    parser, to what the harness registers."""
    with open(CONFIG_FILE) as f:
        data = json.load(f)
    hf = catalog_config()
    cut = ("num_hidden_layers", "num_experts", "vocab_size")
    assert data["reduced"] == list(cut)
    assert data["published"] == {k: hf[k] for k in cut}
    lists = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")
    assert data["model_type"] == hf.pop("model_type")
    for key, value in hf.items():
        if key in lists:
            assert data[key] == value[:12] == [0] * 12
        elif key not in cut:
            assert data[key] == value, key
    assert (data["num_hidden_layers"], data["num_experts"],
            data["vocab_size"]) == (12, 64, 19648)
    assert data["vocab_size"] * 8 == hf["vocab_size"]
    assert data["vocab_size"] % 128            # no whole number of tiles
    assert not plan.unchecked_keys(data, ref)
    assert not plan.share_faults(data)
    base = get_model_config(data["model"])
    assert not plan.published_mismatches(data, base)
    runs = dataclasses.replace(base, **plan.architecture_overrides(data))
    assert not plan.architecture_mismatches(data, runs, ref)
    parsed = config_from_hf_json(
        "x", {k: v for k, v in {**data, **data["published"],
                                "num_hidden_layers": 12}.items()
              if k not in plan.OWN_KEYS or k == "model_type"})
    for f in dataclasses.fields(ModelConfig):
        if f.name in ("name", "moe_experts_held", "vocab_size"):
            continue
        a, b = getattr(parsed, f.name), getattr(runs, f.name)
        if isinstance(a, tuple) and f.name != "mlp_multipliers":
            a, b = a[:12], b[:12]
        assert a == b, f.name


@pytest.mark.parametrize("bad,why", [
    ({"layer_group_size": None}, "must carry layer_group_size"),
    ({"expert_swiglu_limit_list": [0] * 11 + [4]},
     "expert_swiglu_limit_list clamps"),
    ({"share_expert_swiglu_limit_list": [5] + [0] * 11},
     "share_expert_swiglu_limit_list clamps"),
    ({"kda_safe_gate": False}, "kda_safe_gate"),
    ({"kda_lower_bound": 0}, "negative bound"),
    ({"use_mla_nope": True}, "use_mla_nope"),
    ({"group_norm_size": 4}, "group_norm_size"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"num_kv_heads_for_linear_attn": 8}, "fewer key heads"),
    ({"gated_attention_proj_granularity_type": "element_wise"},
     "granularity"),
    ({"partial_rotary_factor": 1.0}, "partial_rotary_factor"),
    ({"score_function": "softmax"}, "router"),
])
def test_what_the_family_does_not_implement_raises(bad, why):
    hf = cut_config()
    if bad.get("layer_group_size", 0) is None:
        hf.pop("layer_group_size")
        bad = {}
    with pytest.raises(ValueError, match=why):
        config_from_hf_json("x", {**hf, **bad})


def test_the_other_families_parse_as_before():
    """An Olmo-Hybrid config is the scalar gate's, as it was; grouped
    routing under a held share is no longer refused where the family has
    groups, and still is where it has none."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b-l16.json")) as f:
        data = json.load(f)
    hf = {k: v for k, v in data.items() if k not in plan.OWN_KEYS}
    olmo = config_from_hf_json("allenai/Olmo-Hybrid-7B", {
        **hf, "model_type": "olmo_hybrid", "num_hidden_layers": 32,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8})
    assert olmo == get_model_config("allenai/Olmo-Hybrid-7B")
    assert (olmo.lin_gate, olmo.lin_gate_lower_bound) == ("scalar", 0.0)
    assert not olmo.attn_head_gate and olmo.mla_qk_head_dim is None
    assert olmo.layer_group_size == 4
    dataclasses.replace(get_model_config(MODEL), moe_experts_held=4)
    for name in ("tiny-k-exaone", "tiny-pangu"):
        assert get_model_config(name).moe_n_group == 1


def test_an_hf_checkpoint_loads_into_the_same_forward(cfg, params):
    """The ASSUMED tensor names through the loader give the tree
    ``init_params`` builds: same logits (a checkpoint whose rope features
    are already split-half: the de-interleave is DeepSeek's, tested
    there)."""
    from tpuserve.models.weights import _load_llama_family
    raw = {"model.embed_tokens.weight": params["embed"]["weight"],
           "model.norm.weight": params["final_norm"]["scale"],
           "lm_head.weight": params["lm_head"]["kernel"].T}
    H, d = cfg.num_heads, cfg.head_dim
    for i, lp in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        sa = pre + "self_attn."
        raw[pre + "input_layernorm.weight"] = lp["attn_norm"]["scale"]
        raw[pre + "post_attention_layernorm.weight"] = lp["mlp_norm"]["scale"]
        if "lin" in lp:
            sp = lp["lin"]
            for p in ("g", "f", "b", "o"):
                raw[sa + f"{p}_proj.weight"] = sp[f"{p}_proj"]["kernel"].T
            qkv = sp["qkv_proj"]["kernel"].T                # (C, hidden)
            conv = sp["conv"]["kernel"].T[:, None, :]       # (C, 1, W)
            for j, c in enumerate("qkv"):
                raw[sa + f"{c}_proj.weight"] = qkv[j * H * d:(j + 1) * H * d]
                raw[sa + f"{c}_conv1d.weight"] = conv[j * H * d:
                                                      (j + 1) * H * d]
            raw[sa + "A_log"], raw[sa + "dt_bias"] = sp["A_log"], \
                sp["dt_bias"]
            raw[sa + "o_norm.weight"] = sp["norm"]["scale"]
        else:
            for name, key in (("q_proj", "q_proj"), ("kv_b_proj", "kv_b_proj"),
                              ("o_proj", "o_proj"), ("g_proj",
                                                     "attn_gate_proj"),
                              ("kv_a_proj_with_mqa", "kv_a_proj")):
                raw[sa + name + ".weight"] = lp[key]["kernel"].T
            raw[sa + "kv_a_layernorm.weight"] = lp["kv_a_norm"]["scale"]
            for p in ("q", "k"):
                raw[sa + f"{p}_norm.weight"] = lp[f"{p}_norm"]["scale"]
        if "experts" in lp:
            raw[pre + "mlp.gate.weight"] = lp["router"]["kernel"].T
            raw[pre + "mlp.gate.e_score_correction_bias"] = \
                lp["router_bias"]["bias"]
            for p in ("gate_proj", "up_proj", "down_proj"):
                for e in range(cfg.num_experts):
                    raw[pre + f"mlp.experts.{e}.{p}.weight"] = \
                        lp["experts"][p]["kernel"][e].T
                raw[pre + f"mlp.shared_experts.{p}.weight"] = \
                    lp["shared"][p]["kernel"].T
        else:
            for p in ("gate_proj", "up_proj", "down_proj"):
                raw[pre + f"mlp.{p}.weight"] = lp[p]["kernel"].T
    loaded = _load_llama_family(
        dataclasses.replace(cfg, mla_rope_interleave=False), raw,
        jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    tokens = jnp.asarray(prompts_of(17, seed=6), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(transformer.forward(loaded, cfg, tokens)),
        np.asarray(transformer.forward(params, cfg, tokens)))


def test_each_family_is_kept_from_the_other_reference(cfg):
    for other in ("olmo_hybrid", "openpangu", "k_exaone"):
        with pytest.raises(ValueError, match="not the"):
            FAMILIES[other].ref.check_family(cfg)
        with pytest.raises(ValueError, match="not the Ling-3.0-flash"):
            ref.check_family(get_model_config(FAMILIES[other].model))
