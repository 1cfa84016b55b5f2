"""Falcon-H1 on the normal path: Mamba-2 state-space heads beside attention
heads in every layer, a recurrent state a SEAT beside the paged KV cache.

Everything is compared with the plain reference the benchmark scores this
family by (``benchmark/reference/falcon_h1.py``: float32, the recurrence
token by token, no code shared with ``tpuserve``), on the registered
``tiny-falcon-h1`` (float32; 10 query heads on 2 KV heads, 2 B/C groups, a
scan chunk of 8, every multiplier off 1) under seeded random weights.

Tolerances: both sides are float32 on the CPU, so what separates them is
the ORDER of the same sums (the chunked scan's matrix products against the
reference's token loop, blocked attention against a dense softmax): a few
1e-6 on logits of size ~1-4.  ``ATOL`` 2e-4 leaves two orders of
magnitude over that and sits three orders under what a left-out term
moves (``test_every_term_of_the_layer_is_live``: over 1e-2 each).
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserve.models import transformer
from tpuserve.models.config import (config_from_hf_json, get_model_config)
from tpuserve.models.weights import init_params
from tpuserve.ops import ssm as ssm_ops
from tpuserve.ops.attention import PAD_SLOT
from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SamplingParams
from tpuserve.runtime.kv_cache import (create_kv_cache, create_ssm_state,
                                       ssm_state_bytes)
from tpuserve.runtime.scheduler import SchedulerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:            # ``benchmark`` is a package of the root
    sys.path.insert(0, ROOT)
from benchmark.harness import plan  # noqa: E402

ATOL = 2e-4
MODEL = "tiny-falcon-h1"
BLOCK = 4               # KV block size of the hand-driven caches
SEATS = 6


def _reference(name):
    """A family's plain reference, loaded as the harness loads it."""
    return plan.load_reference({"reference": name})


ref = _reference("falcon_h1")


@pytest.fixture(scope="module")
def cfg():
    return get_model_config(MODEL)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, seed=7)


def prompts_of(*lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(2, 256, n)] for n in lengths]


def ref_logits(params, cfg, seq, positions):
    """Reference logits after each of ``positions`` of one sequence."""
    return np.asarray(ref.logits_at(
        params, cfg, np.asarray([seq], np.int32),
        [(0, p) for p in positions]))


def ref_greedy(params, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(ref_logits(params, cfg, seq,
                                            [len(seq) - 1])[0])))
    return seq[len(prompt):]


# --------------------------------------------------------------------------
# the trunks, driven by hand: logits against the reference at every position
# --------------------------------------------------------------------------

class Served:
    """A paged cache and a seat pool driven by hand: sequence ``i`` owns
    seat ``i`` and the blocks ``[i * mb, (i + 1) * mb)``."""

    mb = 16                                     # blocks a sequence

    def __init__(self, cfg, params, n_seqs, attn_impl="reference"):
        self.cfg, self.params, self.attn_impl = cfg, params, attn_impl
        cc = CacheConfig(block_size=BLOCK, num_blocks=n_seqs * self.mb,
                         max_blocks_per_seq=self.mb, dtype="float32")
        self.kv = create_kv_cache(cfg, cc)
        self.ssm = create_ssm_state(cfg, SEATS)
        # what a seat held before must not matter: fill the pool with junk
        self.ssm = jax.tree.map(lambda x: jnp.full_like(x, 3.0), self.ssm)
        self.tables = np.arange(n_seqs * self.mb, dtype=np.int32).reshape(
            n_seqs, self.mb)

    def slots(self, i, start, n):
        t = np.arange(start, start + n)
        return (self.tables[i, t // BLOCK] * BLOCK + t % BLOCK).astype(
            np.int32)

    def prefill(self, prompts):
        B, L = len(prompts), 32
        tokens = np.zeros((B, L), np.int32)
        slot_ids = np.full((B, L), PAD_SLOT, np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            slot_ids[i, :len(p)] = self.slots(i, 0, len(p))
        logits, self.kv, self.ssm = transformer.prefill(
            self.params, self.cfg, jnp.asarray(tokens),
            jnp.asarray([len(p) for p in prompts], jnp.int32),
            jnp.asarray(slot_ids), self.kv, None, self.ssm,
            jnp.arange(B, dtype=jnp.int32), attn_impl=self.attn_impl)
        return np.asarray(logits)

    def packed(self, prompts, blk=8):
        """Several prompts on one flat token axis, each starting on a
        ``blk``-row boundary, as Engine._pack_ragged lays them out."""
        starts, cursor = [], 0
        for p in prompts:
            starts.append(cursor)
            cursor += -(-len(p) // blk) * blk
        T, B = cursor + blk, 4                  # a padding block, a spare row
        tokens = np.zeros((T,), np.int32)
        positions = np.zeros((T,), np.int32)
        slot_ids = np.full((T,), PAD_SLOT, np.int32)
        row_seq = np.zeros((T,), np.int32)
        kv_lens, q_lens = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        q_starts = np.full((B,), T, np.int32)
        last_rows = np.zeros((B,), np.int32)
        tables = np.zeros((B, self.mb), np.int32)
        blk_seq = np.full((T // blk,), -1, np.int32)
        for i, (p, s) in enumerate(zip(prompts, starts)):
            n = len(p)
            tokens[s:s + n], positions[s:s + n] = p, np.arange(n)
            slot_ids[s:s + n], row_seq[s:s + n] = self.slots(i, 0, n), i
            kv_lens[i] = q_lens[i] = n
            q_starts[i], last_rows[i] = s, s + n - 1
            tables[i] = self.tables[i]
            blk_seq[s // blk:(s + -(-n // blk) * blk) // blk] = i
        seats = np.full((B,), SEATS, np.int32)          # spare row: trash
        seats[:len(prompts)] = np.arange(len(prompts))
        logits, self.kv, self.ssm = transformer.forward_ragged(
            self.params, self.cfg, *map(jnp.asarray, (
                tokens, positions, slot_ids, row_seq, tables, kv_lens,
                q_starts, q_lens, np.zeros((2,), np.int32), blk_seq,
                last_rows)), self.kv, None, self.ssm, jnp.asarray(seats),
            ragged_blk=blk, attn_impl=self.attn_impl, decode_rows=False)
        return np.asarray(logits)[:len(prompts)]

    def chunks(self, prompt, C=16):
        """One prompt, ``C`` rows a dispatch, state and convolution memory
        carried from chunk to chunk; the logits after each chunk."""
        out = []
        for done in range(0, len(prompt), C):
            part = prompt[done:done + C]
            tokens = np.zeros((1, C), np.int32)
            tokens[0, :len(part)] = part
            slot_ids = np.full((1, C), PAD_SLOT, np.int32)
            slot_ids[0, :len(part)] = self.slots(0, done, len(part))
            logits, self.kv, self.ssm = transformer.prefill_chunk(
                self.params, self.cfg, jnp.asarray(tokens),
                jnp.asarray([done], jnp.int32),
                jnp.asarray([len(part)], jnp.int32), jnp.asarray(slot_ids),
                jnp.asarray(self.tables[:1]), self.kv, None, self.ssm,
                jnp.zeros((1,), jnp.int32), attn_impl=self.attn_impl)
            out.append(np.asarray(logits)[0])
        return out

    def decode(self, seqs):
        """One token a row: ``seqs[i]`` ends in the token to decode."""
        B = len(seqs)
        n = np.asarray([len(s) for s in seqs], np.int32)
        logits, self.kv, self.ssm = transformer.decode_step(
            self.params, self.cfg,
            jnp.asarray([s[-1] for s in seqs], jnp.int32),
            jnp.asarray(n - 1),
            jnp.asarray([self.slots(i, n[i] - 1, 1)[0] for i in range(B)]),
            jnp.asarray(self.tables[:B]), jnp.asarray(n), self.kv, None,
            self.ssm, jnp.arange(B, dtype=jnp.int32),
            attn_impl=self.attn_impl)
        return np.asarray(logits)

    def window(self, seqs, steps):
        """A fused greedy window with one padding row: tokens and the
        chosen tokens' log-probabilities, (B, steps) each."""
        B = len(seqs) + 1
        n = np.ones((B,), np.int32)
        n[:len(seqs)] = [len(s) for s in seqs]
        tokens = np.zeros((B,), np.int32)
        tokens[:len(seqs)] = [s[-1] for s in seqs]
        tables = np.zeros((B, self.mb), np.int32)
        tables[:len(seqs)] = self.tables[:len(seqs)]
        active = np.arange(B) < len(seqs)
        seats = np.where(active, np.arange(B), SEATS).astype(np.int32)
        toks, self.kv, lp, self.ssm = transformer.decode_multi(
            self.params, self.cfg, jnp.asarray(tokens), jnp.asarray(n - 1),
            jnp.asarray(tables), jnp.asarray(n), jnp.asarray(active),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros((B,), jnp.float32),
            self.kv, None, self.ssm, jnp.asarray(seats), steps=steps,
            mode="greedy", logprobs_n=1, attn_impl=self.attn_impl)
        return np.asarray(toks)[:len(seqs)], np.asarray(lp[0])[:len(seqs)]


def then_decode(served, params, cfg, seqs, first_logits):
    """After any prefill route: its logits, three decode steps and a fused
    window of four, each against the reference's full forward."""
    seqs = [list(s) for s in seqs]
    for i, s in enumerate(seqs):
        np.testing.assert_allclose(
            first_logits[i], ref_logits(params, cfg, s, [len(s) - 1])[0],
            atol=ATOL)
        s.append(int(np.argmax(first_logits[i])))
    for _ in range(3):
        logits = served.decode(seqs)
        for i, s in enumerate(seqs):
            np.testing.assert_allclose(
                logits[i], ref_logits(params, cfg, s, [len(s) - 1])[0],
                atol=ATOL)
            s.append(int(np.argmax(logits[i])))
    toks, lps = served.window(seqs, 4)
    for i, s in enumerate(seqs):
        assert list(toks[i]) == ref_greedy(params, cfg, s, 4)
        full = s + list(toks[i])
        rows = np.asarray(jax.nn.log_softmax(ref_logits(
            params, cfg, full, range(len(s) - 1, len(full) - 1))))
        np.testing.assert_allclose(
            lps[i], rows[np.arange(4), toks[i]], atol=ATOL)


@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
@pytest.mark.parametrize("route", ["prefill", "packed", "chunks"])
def test_every_route_matches_the_reference_at_every_position(
        cfg, params, route, attn_impl):
    """(B, L) prefill, a packed prefill of three uneven prompts, a prompt
    over several chunks; then ``decode_step`` and a fused ``decode_multi``
    window.  ``pallas``: the paged kernels and the state-update kernel in
    interpret mode."""
    if route == "chunks":
        seqs = prompts_of(43)                   # 16 + 16 + 11 rows
        served = Served(cfg, params, 1, attn_impl)
        per_chunk = served.chunks(seqs[0])
        for logits, upto in zip(per_chunk, (16, 32, 43)):
            np.testing.assert_allclose(
                logits, ref_logits(params, cfg, seqs[0], [upto - 1])[0],
                atol=ATOL)
        first = [per_chunk[-1]]
    else:
        seqs = prompts_of(5, 19, 12)            # none a multiple of the chunk
        served = Served(cfg, params, 3, attn_impl)
        first = served.prefill(seqs) if route == "prefill" \
            else served.packed(seqs)
    then_decode(served, params, cfg, seqs, first)


@pytest.mark.parametrize("length", [1, 5, 8, 13, 27])
def test_the_chunked_scan_is_the_plain_recurrence(length):
    """``ssd_chunk_scan`` (chunk 8) against the token-by-token loop, from a
    non-zero state, at lengths that are not multiples of the chunk: the
    rows past the end carry dt = 0 and must change nothing.  Float32
    against float64: 1e-5 on values of size ~1."""
    H, P, N, G, Q = 4, 6, 5, 2, 8
    T = -(-length // Q) * Q
    rs = np.random.RandomState(length)
    x, bm, cm = rs.randn(T, H, P), rs.randn(T, G, N), rs.randn(T, G, N)
    dt = np.where(np.arange(T)[:, None] < length,
                  rs.uniform(0.01, 0.5, (T, H)), 0.0)
    a = -rs.uniform(0.5, 8.0, H)
    s0 = rs.randn(1, H, P, N)
    state, want = s0[0].copy(), []
    for t in range(length):
        bh, ch = np.repeat(bm[t], H // G, 0), np.repeat(cm[t], H // G, 0)
        state = (np.exp(dt[t] * a)[:, None, None] * state
                 + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :])
        want.append(np.einsum("hpn,hn->hp", state, ch))
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    y, finals = ssm_ops.ssd_chunk_scan(
        f32(x), f32(dt), f32(a), f32(bm), f32(cm), f32(s0),
        jnp.zeros((T // Q,), jnp.int32), chunk=Q)
    np.testing.assert_allclose(np.asarray(y)[:length], want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(finals)[0], state, atol=1e-5)


@pytest.mark.parametrize("shape", [(4, 4, 8, 16, 2), (5, 32, 16, 128, 2),
                                   (3, 6, 8, 128, 1)],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_state_update_kernel_is_the_formula(shape):
    """``_ssm_state_update`` in interpret mode against the formula on
    gathered rows, on a pool with more seats than rows: the rows' seats are
    updated in place, every other seat is left as it was."""
    from tpuserve.ops.pallas_ssm_update import (ssm_state_update,
                                                ssm_state_update_reference)
    B, H, P, N, G = shape
    k = jax.random.split(jax.random.key(B), 5)
    state = jax.random.normal(k[0], (B + 4, H, P, N), jnp.float32)
    seats = jnp.asarray(np.random.RandomState(B).permutation(B + 4)[:B],
                        jnp.int32)
    decay = jax.random.uniform(k[1], (B, H))
    dtx = jax.random.normal(k[2], (B, H, P))
    bm, cm = (jax.random.normal(kk, (B, G, N)) for kk in k[3:])
    want_y, want_s = ssm_state_update_reference(state, seats, decay, dtx,
                                                bm, cm)
    got_y, got_s = ssm_state_update(state + 0.0, seats, decay, dtx, bm, cm,
                                    interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    untouched = np.setdiff1d(np.arange(B + 4), np.asarray(seats))
    np.testing.assert_array_equal(np.asarray(got_s)[untouched],
                                  np.asarray(state)[untouched])


def check_conv_tail_step(dtype, width, channels, biased):
    """``_conv_tail_step`` in interpret mode, bit for bit: against its
    reference (jitted, as the trunks run it: the CPU contracts a product
    and a sum to one rounding inside a program and not between two) and
    against the lines both mixers' decode steps held before it --
    ``causal_conv`` over the gathered memory and the new row, then
    ``rows[:, 1:]`` scattered back -- on the pool as ``(seats, W - 1,
    C)``.  Seats shuffled, more seats than rows; the last two rows are
    padding rows on the trash seat, which leave every real seat alone;
    seats outside the batch keep their memory."""
    from tpuserve.ops import pallas_conv_tail as tap
    B, S = 6, 11
    rs = np.random.RandomState(width * channels + biased)

    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32).astype(dtype)

    pool = draw(S + 1, width - 1, *tap.tail_slab(channels))
    x, kernel = draw(B, channels), draw(width, channels)
    bias = draw(channels) if biased else None
    seats = np.append(rs.permutation(S)[:B - 2], [S, S]).astype(np.int32)
    real = seats != S

    @jax.jit
    def before(flat, seats, x):
        out, rows = ssm_ops.causal_conv(x[:, None], flat[seats], kernel, bias)
        return out[:, 0], flat.at[seats].set(rows[:, 1:].astype(flat.dtype))

    want_o, want_p = jax.jit(tap.conv_tail_step_reference)(
        pool, seats, x, kernel, bias)
    was_o, was_p = before(pool.reshape(S + 1, width - 1, channels), seats, x)
    got_o, got_p = tap.conv_tail_step(pool + 0, jnp.asarray(seats), x, kernel,
                                      bias, interpret=True)
    assert got_o.dtype == jnp.float32 and got_p.dtype == pool.dtype
    assert got_p.shape == pool.shape

    def bits(a):
        return np.asarray(a.astype(jnp.float32))

    np.testing.assert_array_equal(bits(got_o)[real], bits(want_o)[real])
    np.testing.assert_array_equal(bits(got_o)[real], bits(was_o)[real])
    np.testing.assert_array_equal(bits(got_p)[:S], bits(want_p)[:S])
    np.testing.assert_array_equal(bits(got_p)[:S].reshape(S, width - 1, -1),
                                  bits(was_p)[:S])
    # a real row's seat: the memory shifted by one, the new row last
    flat = bits(got_p).reshape(S + 1, width - 1, channels)
    for b in np.flatnonzero(real):
        np.testing.assert_array_equal(
            flat[seats[b], :-1],
            bits(pool).reshape(S + 1, width - 1, channels)[seats[b], 1:])
        np.testing.assert_array_equal(flat[seats[b], -1], bits(x)[b])
    untouched = np.setdiff1d(np.arange(S), seats)
    assert untouched.size
    np.testing.assert_array_equal(bits(got_p)[untouched],
                                  bits(pool)[untouched])


@pytest.mark.parametrize("biased", [True, False])
@pytest.mark.parametrize("width", [4, 3])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_conv_tail_kernel_is_the_lines_it_replaces(dtype, width, biased):
    """The model's dtype in the pool, as Falcon-H1 keeps it (bfloat16 at
    the published sizes, float32 in ``tiny-falcon-h1``), with the bias its
    convolution has and without; a row of 256 channels is two lane tiles
    down the sublanes."""
    check_conv_tail_step(jnp.dtype(dtype), width, 256, biased)


# --------------------------------------------------------------------------
# no equation dropped: every multiplier and every term moves the logits
# --------------------------------------------------------------------------

def _scaled(tree, path, factor):
    """``tree`` with the leaf at ``path`` times ``factor`` in layer 0."""
    out = jax.tree.map(lambda x: x, tree)
    node = out["layers"][0]
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = node[path[-1]] * factor
    return out


MULTIPLIERS = (
    [(name, None) for name in (
        "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "ssm_in_multiplier", "ssm_out_multiplier")]
    + [("mlp_multipliers", i) for i in range(2)]
    + [("ssm_multipliers", i) for i in range(5)])
TERMS = {
    "D": ("ssm", "D"),
    "dt_bias": ("ssm", "dt_bias"),
    "conv_bias": ("ssm", "conv", "bias"),
    "A_log": ("ssm", "A_log"),
}


@pytest.mark.parametrize("what", [f"{n}[{i}]" if i is not None else n
                                  for n, i in MULTIPLIERS]
                         + sorted(TERMS) + ["gate"])
def test_every_term_of_the_layer_is_live(cfg, params, what):
    """Each of the fixed multipliers, and each of ``D``, ``dt_bias``, the
    convolution's bias, ``A`` and the gate: changed on BOTH sides, the
    served trunk and the reference still agree (the term is implemented,
    and in the same place); changed on one side only, they part by far
    more than the tolerance (it is not a no-op under these weights, so
    leaving it out could not pass)."""
    tokens = np.asarray(prompts_of(21, seed=3), np.int32)
    rows = [(0, t) for t in range(21)]
    base = np.asarray(transformer.forward(params, cfg, jnp.asarray(tokens)))[0]
    cfg2, params2 = cfg, params
    if what == "gate":
        # the gate is silu(z): scale the z columns of the input projection
        d = cfg.mamba_d_ssm
        kern = params["layers"][0]["ssm"]["in_proj"]["kernel"]
        params2 = _scaled(params, ("ssm", "in_proj", "kernel"), 1.0)
        params2["layers"][0]["ssm"]["in_proj"]["kernel"] = \
            kern.at[:, :d].multiply(1.5)
    elif what in TERMS:
        params2 = _scaled(params, TERMS[what], 1.5)
    else:
        name, _, idx = what.partition("[")
        value = getattr(cfg, name)
        if idx:
            i = int(idx[:-1])
            value = value[:i] + (value[i] * 1.5,) + value[i + 1:]
        else:
            value = value * 1.5
        cfg2 = dataclasses.replace(cfg, **{name: value})
    moved = np.asarray(transformer.forward(params2, cfg2,
                                           jnp.asarray(tokens)))[0]
    want = np.asarray(ref.logits_at(params2, cfg2, tokens, rows))
    np.testing.assert_allclose(moved, want, atol=ATOL)
    assert np.abs(moved - base).max() > 1e-2, what


# --------------------------------------------------------------------------
# through the engine
# --------------------------------------------------------------------------

def engine_for(**kw):
    sched = SchedulerConfig(**{"max_num_seqs": 4, "prefill_chunk_size": 16,
                               **kw.pop("scheduler", {})})
    cache = CacheConfig(**{"block_size": BLOCK, "num_blocks": 128,
                           "max_blocks_per_seq": 32, "dtype": "float32",
                           **kw.pop("cache", {})})
    return Engine(EngineConfig(model=MODEL, scheduler=sched, cache=cache,
                               **kw))


def serve(engine, prompts, max_tokens=10):
    rids = [engine.add_request(
        prompt_token_ids=p, params=SamplingParams(
            max_tokens=max_tokens, temperature=0.0, ignore_eos=True))
        for p in prompts]
    out = {r: [] for r in rids}
    while engine.has_work():
        for o in engine.step():
            out[o.request_id] += o.new_token_ids
    return [out[r] for r in rids]


@pytest.mark.parametrize("multi_step,attn_impl", [
    (1, "reference"), (4, "reference"), (4, "pallas")])
def test_served_greedy_tokens_are_the_references(multi_step, attn_impl):
    """Through ``Engine.step``: packed prefill (prompts of 5 and 11),
    chunked prefill (23 and 40 against a 16-token chunk), then single
    steps or fused windows — token for token the float32 reference's
    greedy continuation."""
    engine = engine_for(multi_step=multi_step, attn_impl=attn_impl)
    assert engine._packed_prefill
    prompts = prompts_of(5, 11, 23, 40, seed=1)
    got = serve(engine, prompts)
    assert engine.stats.prefill_packed_steps > 0
    for p, toks in zip(prompts, got):
        assert toks == ref_greedy(engine.params, engine.model_cfg, p, 10)
    # every sequence took a seat with its blocks and gave it back
    assert engine.stats.ssm_state_resets == 4
    assert engine.block_manager.seats.in_use == 0
    assert engine.block_manager.num_seqs() == 0


@pytest.mark.parametrize("multi_step", [1, 4])
def test_the_decode_kernels_serve_what_the_formulas_serve(multi_step):
    """A packed prefill, then eight decode steps, one at a time or in fused
    windows: the state update's and the convolution memory's kernels
    (``attn_impl="pallas"``, interpret mode here) against the formulas in
    ``jax.numpy``, token for token."""
    prompts = prompts_of(7, 12, 19, seed=3)
    got = {impl: serve(engine_for(multi_step=multi_step, attn_impl=impl),
                       prompts, max_tokens=9)
           for impl in ("pallas", "reference")}
    assert got["pallas"] == got["reference"]
    assert all(len(toks) == 9 for toks in got["pallas"])


@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
def test_a_seat_given_to_a_new_sequence_starts_from_zero(attn_impl):
    """One seat: the second sequence runs on the slot the first one left
    its state and its convolution's memory in, and serves what an
    untouched engine serves."""
    prompts = prompts_of(9, 14, seed=2)
    engine = engine_for(scheduler={"max_num_seqs": 1}, multi_step=4,
                        attn_impl=attn_impl)
    first, second = (serve(engine, [p])[0] for p in prompts)
    pool = np.asarray(engine.ssm_state[0]["state"])
    assert np.abs(pool[0]).max() > 0            # the seat was used
    assert np.abs(np.asarray(engine.ssm_state[0]["conv"])[0]).max() > 0
    assert second == serve(engine_for(multi_step=4), [prompts[1]])[0]
    assert second == ref_greedy(engine.params, engine.model_cfg,
                                prompts[1], 10)
    assert first == ref_greedy(engine.params, engine.model_cfg,
                               prompts[0], 10)


def test_a_preempted_sequence_serves_the_same_tokens():
    """A cache too small for four growing sequences pre-empts; the victim
    re-prefills prompt plus generated tokens from a zeroed seat (nothing
    snapshots its state) and the tokens are those of a roomy engine."""
    prompts = prompts_of(10, 12, 9, 11, seed=4)
    roomy = serve(engine_for(multi_step=1), prompts, max_tokens=24)
    tight = engine_for(multi_step=1, cache={"num_blocks": 26})
    assert serve(tight, prompts, max_tokens=24) == roomy
    assert tight.stats.preemptions > 0
    assert tight.stats.ssm_rebuilt_tokens > 0
    assert tight.stats.ssm_state_resets == 4 + tight.stats.preemptions
    assert tight.block_manager.seats.in_use == 0


def test_what_the_engine_observes_of_recurrent_state(caplog):
    """No option: with recurrent state the prefix cache, the KV tier and
    mixed batching are off, each with its logged sentence, and the pool is
    accounted beside the KV cache, not inside it."""
    import logging
    with caplog.at_level(logging.INFO, logger="tpuserve.engine"):
        engine = engine_for(enable_prefix_caching=True, kv_tiers=True,
                            scheduler={"mixed_batching": True})
    assert not engine.block_manager.enable_prefix_caching
    assert engine._kv_tiers is None
    assert not engine.scheduler.cfg.mixed_batching
    said = caplog.text
    assert "prefix caching and the KV tier are off" in said
    assert "mixed ragged batching is off" in said
    cfg = engine.model_cfg
    want = ssm_state_bytes(cfg, 4)
    assert want == cfg.num_layers * 5 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert sum(x.nbytes for x in jax.tree.leaves(engine.ssm_state)) == want
    hbm = engine.devprof.hbm_snapshot()
    assert hbm["state_bytes"] == want
    assert hbm["kv_reserved_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(engine.kv_cache))
    assert all(set(layer) == {"k", "v"} for layer in engine.kv_cache)
    # /debug/engine and the dump bundles show the pool
    assert engine.flight.engine_snapshot()["devprof"]["hbm"][
        "state_bytes"] == want
    assert engine.flight.dump_bundle("test")["engine"][
        "ssm_state_seats"] == 4


def test_the_auto_sizer_subtracts_the_state_pool(monkeypatch):
    from tpuserve.models.weights import param_nbytes
    from tpuserve.runtime.kv_cache import bytes_per_block
    monkeypatch.setenv("TPUSERVE_HBM_BYTES", str(4 << 20))
    engine = engine_for(cache={"num_blocks": 0},
                        scheduler={"max_num_seqs": 64})
    cfg, cc = engine.model_cfg, engine.cache_cfg
    budget = int((4 << 20) * 0.9) - param_nbytes(engine.params) \
        - ssm_state_bytes(cfg, 64)
    assert cc.num_blocks == budget // bytes_per_block(cfg, cc)


@pytest.mark.parametrize("route", ["speculative", "mesh", "lora_modules",
                                   "adopt"])
def test_routes_that_need_a_snapshot_raise(route):
    from tpuserve.runtime.spec import SpecConfig
    if route == "speculative":
        with pytest.raises(ValueError, match="no snapshot to roll back"):
            engine_for(speculative=SpecConfig())
    elif route == "mesh":
        # tp and pp alike: the engine refuses any mesh for this model
        from tpuserve.parallel.mesh import MeshConfig, make_mesh
        mesh = make_mesh(MeshConfig(pp=2))
        with pytest.raises(ValueError, match="has no sharding yet"):
            Engine(EngineConfig(model=MODEL), mesh=mesh)
    elif route == "lora_modules":
        with pytest.raises(ValueError, match="multi-LoRA"):
            engine_for(lora_modules={"a": "/nonexistent"})
    else:
        with pytest.raises(ValueError, match="do not carry it"):
            engine_for().adopt_prefilled("r", [1, 2, 3], 4,
                                         SamplingParams(), [])


def test_swap_model_rebuilds_the_pool():
    """To a model without recurrent state and back: the pool goes and
    comes with the model, seats and all."""
    engine = engine_for(multi_step=1)
    prompt = prompts_of(9, seed=5)
    before = serve(engine, prompt)
    cache = engine.config.cache
    engine.swap_model(dataclasses.replace(engine.config, model="tiny-llama",
                                          cache=cache))
    assert engine.ssm_state is None
    assert engine.block_manager.seats is None
    serve(engine, prompt)
    engine.swap_model(dataclasses.replace(engine.config, model=MODEL))
    assert len(engine.ssm_state) == engine.model_cfg.num_layers
    assert engine.block_manager.seats.num_seats == 4
    assert serve(engine, prompt) == before


# --------------------------------------------------------------------------
# the configuration and the reference's family check
# --------------------------------------------------------------------------

def test_config_json_maps_onto_the_registered_model():
    """The published config.json (the benchmark's configuration file holds
    every key of it) through ``config_from_hf_json`` is the registered
    model, depth aside."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        hf = json.load(f)
    got = config_from_hf_json("x", hf)
    want = get_model_config("tiiuae/Falcon-H1-34B-Instruct")
    skip = {"name", "num_layers", "bos_token_id", "eos_token_id"}
    for field in dataclasses.fields(want):
        if field.name not in skip:
            assert getattr(got, field.name) == getattr(want, field.name), \
                field.name
    assert got.num_layers == 6 and want.num_layers == 72
    assert want.num_params == pytest.approx(33.6e9, rel=0.01)
    for bad in ({"attn_layer_indices": [0, 2]}, {"mamba_use_mlp": False},
                {"rope_scaling": {"type": "linear", "factor": 2}}):
        with pytest.raises(ValueError):
            config_from_hf_json("x", {**hf, **bad})


def test_an_hf_checkpoint_loads_into_the_same_forward(cfg, params):
    """HF ``modeling_falcon_h1`` tensor names through the loader give the
    tree ``init_params`` builds: same logits."""
    from tpuserve.models.weights import _load_llama_family
    raw = {"model.embed_tokens.weight": params["embed"]["weight"],
           "model.final_layernorm.weight": params["final_norm"]["scale"],
           "lm_head.weight": params["lm_head"]["kernel"].T}
    for i, lp in enumerate(params["layers"]):
        pre = f"model.layers.{i}."
        raw[pre + "input_layernorm.weight"] = lp["attn_norm"]["scale"]
        raw[pre + "pre_ff_layernorm.weight"] = lp["mlp_norm"]["scale"]
        for p in ("q", "k", "v", "o"):
            raw[pre + f"self_attn.{p}_proj.weight"] = \
                lp[f"{p}_proj"]["kernel"].T
        for p in ("gate", "up", "down"):
            raw[pre + f"feed_forward.{p}_proj.weight"] = \
                lp[f"{p}_proj"]["kernel"].T
        sp = lp["ssm"]
        raw[pre + "mamba.in_proj.weight"] = sp["in_proj"]["kernel"].T
        raw[pre + "mamba.out_proj.weight"] = sp["out_proj"]["kernel"].T
        raw[pre + "mamba.conv1d.weight"] = sp["conv"]["kernel"].T[:, None, :]
        raw[pre + "mamba.conv1d.bias"] = sp["conv"]["bias"]
        raw[pre + "mamba.norm.weight"] = sp["norm"]["scale"]
        for name in ("A_log", "dt_bias", "D"):
            raw[pre + "mamba." + name] = sp[name]
    loaded = _load_llama_family(cfg, raw, jnp.float32)
    tokens = jnp.asarray(prompts_of(17, seed=6), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(transformer.forward(loaded, cfg, tokens)),
        np.asarray(transformer.forward(params, cfg, tokens)))


def test_each_family_is_kept_from_the_other_reference(cfg):
    """``falcon_h1.check_family`` refuses every dense model.  The other way
    round it is the harness that refuses: ``dense_gqa.check_family`` reads
    none of the fields this family adds (the file is the accepted
    benchmark's and is not this PR's to edit), but a Falcon-H1
    configuration that named it would leave every mixer size and every
    multiplier checked against nothing, which ``plan.lint`` reports."""
    ref.check_family(cfg)
    ref.check_family(get_model_config("tiiuae/Falcon-H1-34B-Instruct"))
    for name in ("tiny-qwen3", "tiny-mistral", "tiny-llama"):
        with pytest.raises(ValueError, match="not the Falcon-H1 family"):
            ref.check_family(get_model_config(name))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        config = json.load(f)
    assert plan.unchecked_keys(config, ref) == []
    loose = plan.unchecked_keys(config, _reference("dense_gqa"))
    assert {"mamba_d_state", "ssm_multipliers", "lm_head_multiplier"} \
        <= set(loose)
    # and the file describes what runs: lists compare equal to lists
    model_cfg = dataclasses.replace(
        get_model_config(config["model"]),
        **plan.architecture_overrides(config))
    assert plan.architecture_mismatches(config, model_cfg, ref) == []
    wrong = {**config, "ssm_multipliers": [1, 1, 1, 1, 1]}
    assert plan.architecture_mismatches(wrong, model_cfg, ref) == [
        f"ssm_multipliers: file [1, 1, 1, 1, 1], runs "
        f"{model_cfg.ssm_multiplier_list!r}"]
