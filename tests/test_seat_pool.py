"""What the families with a seat pool share word for word: Falcon-H1
(state-space heads beside attention in every layer), Olmo-Hybrid
(linear-attention layers in place of attention in three of four) and
Ling-3.0-flash (Kimi-delta layers in place of LATENT attention in two of
three, behind grouped experts), a case a family.  Their own cases, fixtures and tolerances are in
``tests/test_falcon_h1.py`` and ``tests/test_olmo_hybrid.py``; the harness
is ``tests/family_routes.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_routes import (FAMILIES, engine_for, prompts_of, ref_greedy,
                           serve)
from tpuserve.ops import ssm as ssm_ops
from tpuserve.runtime import Engine, EngineConfig, SamplingParams

SEATED = pytest.mark.parametrize(
    "family", [FAMILIES["falcon_h1"], FAMILIES["olmo_hybrid"],
               FAMILIES["ling_hybrid"]],
    ids=["falcon_h1", "olmo_hybrid", "ling_hybrid"])


@SEATED
@pytest.mark.parametrize("multi_step,attn_impl", [
    (1, "reference"), (4, "reference"), (4, "pallas")])
def test_served_greedy_tokens_are_the_references(family, multi_step,
                                                 attn_impl):
    """Through ``Engine.step``: packed prefill (prompts of 5 and 11),
    chunked prefill (23 and 40 against a 16-token chunk), then single
    steps or fused windows — token for token the float32 reference's
    greedy continuation."""
    engine = engine_for(family, multi_step=multi_step, attn_impl=attn_impl)
    assert engine._packed_prefill
    prompts = prompts_of(5, 11, 23, 40, seed=1)
    got = serve(engine, prompts)
    assert engine.stats.prefill_packed_steps > 0
    for p, toks in zip(prompts, got):
        assert toks == ref_greedy(family, engine.params, engine.model_cfg,
                                  p, 10)
    # every sequence took a seat with its blocks and gave it back
    assert engine.stats.ssm_state_resets == 4
    assert engine.block_manager.seats.in_use == 0
    assert engine.block_manager.num_seqs() == 0


@SEATED
@pytest.mark.parametrize("multi_step", [1, 4])
def test_the_decode_kernels_serve_what_the_formulas_serve(family,
                                                          multi_step):
    """A packed prefill, then eight decode steps, one at a time or in fused
    windows: the state update's and the convolution memory's kernels
    (``attn_impl="pallas"``, interpret mode here) against the formulas in
    ``jax.numpy``, token for token."""
    prompts = prompts_of(7, 12, 19, seed=3)
    got = {impl: serve(engine_for(family, multi_step=multi_step,
                                  attn_impl=impl), prompts, max_tokens=9)
           for impl in ("pallas", "reference")}
    assert got["pallas"] == got["reference"]
    assert all(len(toks) == 9 for toks in got["pallas"])


@SEATED
@pytest.mark.parametrize("attn_impl", ["reference", "pallas"])
def test_a_seat_given_to_a_new_sequence_starts_from_zero(family, attn_impl):
    """One seat: the second sequence runs on the slot the first one left
    its state and its convolution's memory in, and serves what an
    untouched engine serves."""
    prompts = prompts_of(9, 14, seed=2)
    engine = engine_for(family, scheduler={"max_num_seqs": 1}, multi_step=4,
                        attn_impl=attn_impl)
    first, second = (serve(engine, [p])[0] for p in prompts)
    pool = np.asarray(engine.ssm_state[0]["state"])
    assert np.abs(pool[0]).max() > 0            # the seat was used
    assert np.abs(np.asarray(engine.ssm_state[0]["conv"])[0]).max() > 0
    assert second == serve(engine_for(family, multi_step=4), [prompts[1]])[0]
    assert second == ref_greedy(family, engine.params, engine.model_cfg,
                                prompts[1], 10)
    assert first == ref_greedy(family, engine.params, engine.model_cfg,
                               prompts[0], 10)


@SEATED
@pytest.mark.parametrize("route", ["speculative", "mesh", "lora_modules",
                                   "adopt"])
def test_routes_that_need_a_snapshot_raise(family, route):
    from tpuserve.runtime.spec import SpecConfig
    if route == "speculative":
        with pytest.raises(ValueError, match="no snapshot to roll back"):
            engine_for(family, speculative=SpecConfig())
    elif route == "mesh":
        # tp and pp alike: the engine refuses any mesh for this model
        from tpuserve.parallel.mesh import MeshConfig, make_mesh
        mesh = make_mesh(MeshConfig(pp=2))
        with pytest.raises(ValueError, match="has no sharding yet"):
            Engine(EngineConfig(model=family.model), mesh=mesh)
    elif route == "lora_modules":
        with pytest.raises(ValueError, match="multi-LoRA"):
            engine_for(family, lora_modules={"a": "/nonexistent"})
    else:
        with pytest.raises(ValueError, match="do not carry it"):
            engine_for(family).adopt_prefilled("r", [1, 2, 3], 4,
                                               SamplingParams(), [])


# --------------------------------------------------------------------------
# the convolution memory's decode step, in both families' pools
# --------------------------------------------------------------------------

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



# Falcon-H1: the model's dtype in the pool (bfloat16 at the published sizes,
# float32 in ``tiny-falcon-h1``), with the bias its convolution has and
# without; a row of 256 channels is two lane tiles down the sublanes.
# Olmo-Hybrid: the float32 pool a linear layer keeps (its products leave in
# float32), at the published width of 4 and at 2; rows of whole lane tiles
# and, as ``tiny-olmo-hybrid``'s 288 channels, one slab of lanes; without
# the bias (the family has none) and with.
CONV_TAILS = [
    pytest.param(dtype, width, 256, biased,
                 id=f"falcon_h1-{dtype}-{width}-{biased}")
    for biased in (True, False) for width in (4, 3)
    for dtype in ("bfloat16", "float32")
] + [
    pytest.param("float32", width, channels, biased,
                 id=f"olmo_hybrid-{width}-{channels}-{biased}")
    for biased in (False, True) for channels in (256, 48)
    for width in (4, 2)]


@pytest.mark.parametrize("dtype,width,channels,biased", CONV_TAILS)
def test_the_conv_tail_kernel_is_the_lines_it_replaces(dtype, width,
                                                       channels, biased):
    check_conv_tail_step(jnp.dtype(dtype), width, channels, biased)
