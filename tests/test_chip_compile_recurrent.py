"""The recurrent and linear-attention layers compile for the chip: the
decode-time state updates, the convolution memory's step, and one whole
linear layer at every rung of a packed prefill (see
``tests/test_chip_compile.py`` and ``tests/chip_v5e.py``)."""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_v5e import (MAX_NUM_SEQS, PREFILL_SEQS, olmo_hybrid, shapes_on)
from chip_v5e import (  # noqa: F401  (fixtures, found by name)
    _no_persistent_cache, one_chip, topo)

def _ssm_update(S, rows=MAX_NUM_SEQS, heads=32, head=128, state=256,
                groups=2):
    """The decode-time state update at Falcon-H1-34B's sizes: a full
    decode batch on a pool of one seat a row and the trash seat."""
    from tpuserve.ops.pallas_ssm_update import ssm_state_update
    f32 = jnp.float32
    return (lambda pool, seats, decay, dtx, b, c: ssm_state_update(
        pool, seats, decay, dtx, b, c, interpret=False),
        [S((MAX_NUM_SEQS + 1, heads, head, state), f32),
         S((rows,), jnp.int32), S((rows, heads), f32),
         S((rows, heads, head), f32), S((rows, groups, state), f32),
         S((rows, groups, state), f32)])


@pytest.mark.parametrize("rows", [4, MAX_NUM_SEQS])
def test_the_state_update_kernel_compiles_for_v5e(rows, one_chip):
    """``_ssm_state_update`` at the smallest and the largest decode bucket:
    compiled, named as the benchmark's ``ssm.*`` readers match it, and in
    place — the pool's bytes are aliased from input to output, not
    copied (65 seats x 4 MiB would be 273 MB a layer a step)."""
    from tpuserve.ops.pallas_ssm_update import KERNEL_NAME

    S, _ = shapes_on(one_chip)
    assert KERNEL_NAME == "_ssm_state_update"
    fn, args = _ssm_update(S, rows)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", compiled.as_text())
    pool_bytes = (MAX_NUM_SEQS + 1) * 32 * 128 * 256 * 4
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


@pytest.mark.parametrize("rows", [4, MAX_NUM_SEQS])
def test_the_gdn_state_update_kernel_compiles_for_v5e(rows, one_chip):
    """``_gdn_state_update`` at Olmo-Hybrid-7B's sizes (30 heads of 96 x
    192, two a slab) at the smallest and the largest decode bucket:
    compiled, named as the benchmark's ``lin.*`` readers match it, and in
    place -- the pool's bytes are aliased from input to output, not copied
    -- and the pool holds no padding: 65 seats x 2,211,840 B."""
    from tpuserve.ops.pallas_gdn_update import (KERNEL_NAME, gdn_state_update,
                                                heads_per_slab)

    S, _ = shapes_on(one_chip)
    assert KERNEL_NAME == "_gdn_state_update"
    H, dk, dv, f32 = 30, 96, 192, jnp.float32
    hp = heads_per_slab(H, dv)
    pool = S((MAX_NUM_SEQS + 1, H // hp, dk, hp * dv), f32)
    compiled = jax.jit(
        lambda pool, seats, q, k, v, g, b: gdn_state_update(
            pool, seats, q, k, v, g, b, interpret=False),
        donate_argnums=(0,)).lower(
            pool, S((rows,), jnp.int32), S((rows, H, dk), f32),
            S((rows, H, dk), f32), S((rows, H, dv), f32), S((rows, H), f32),
            S((rows, H), f32)).compile()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", compiled.as_text())
    pool_bytes = (MAX_NUM_SEQS + 1) * H * dk * dv * 4
    assert pool_bytes == 65 * 2_211_840
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


@pytest.mark.parametrize("rows", [4, MAX_NUM_SEQS, 128])
def test_the_kda_state_update_kernel_compiles_for_v5e(rows, one_chip):
    """``_kda_state_update`` at Ling-3.0-flash's sizes (32 heads of 128 x
    128, one a slab: ``dv`` is one lane tile and nothing is packed) at the
    smallest decode bucket, the other cells' largest and this cell's 128
    rows: compiled, named as the benchmark's ``kda.*`` readers match it
    (NOT as the scalar gate's kernel), and in place -- the pool's bytes are
    aliased from input to output, not copied -- with no padding: 129 seats
    x 2,097,152 B."""
    from tpuserve.ops.pallas_gdn_update import heads_per_slab
    from tpuserve.ops.pallas_kda_update import KERNEL_NAME, kda_state_update

    S, _ = shapes_on(one_chip)
    assert KERNEL_NAME == "_kda_state_update"
    H, d, f32 = 32, 128, jnp.float32
    assert heads_per_slab(H, d) == 1
    pool = S((128 + 1, H, d, d), f32)
    compiled = jax.jit(
        lambda pool, seats, q, k, v, g, b: kda_state_update(
            pool, seats, q, k, v, g, b, interpret=False),
        donate_argnums=(0,)).lower(
            pool, S((rows,), jnp.int32), S((rows, H, d), f32),
            S((rows, H, d), f32), S((rows, H, d), f32), S((rows, H, d), f32),
            S((rows, H), f32)).compile()
    text = compiled.as_text()
    assert re.search(rf"%{KERNEL_NAME}(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r"tpu_custom_call", text)
    assert "_gdn_state_update" not in text
    pool_bytes = 129 * H * d * d * 4
    assert pool_bytes == 129 * 2_097_152
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes


# the convolution memory's decode step at both families' published sizes:
# (channels, the pool's dtype, a bias or none)
CONV_TAILS = {"olmo-hybrid-7b": (11520, jnp.float32, False),
              "falcon-h1-34b": (5120, jnp.bfloat16, True)}


@pytest.mark.parametrize("rows", [4, MAX_NUM_SEQS])
@pytest.mark.parametrize("family", sorted(CONV_TAILS))
def test_the_conv_tail_kernel_compiles_for_v5e(family, rows, one_chip):
    """``_conv_tail_step`` at the smallest and the largest decode bucket:
    compiled, named, in place -- the pool's bytes are aliased from input
    to output, as the chip tiles them (90 sublanes of float32 stored as
    96; 40 of bfloat16, two a word, as 40) -- and the pool operand is left
    in HBM (no ``S(1)`` in its layout: the compiler stages a 9 MB operand
    of a custom call through its faster memory otherwise)."""
    from tpuserve.ops.pallas_conv_tail import (KERNEL_NAME, conv_tail_step,
                                               tail_slab)

    S, _ = shapes_on(one_chip)
    assert KERNEL_NAME == "_conv_tail_step"
    C, dtype, biased = CONV_TAILS[family]
    W = 4
    pool = S((MAX_NUM_SEQS + 1, W - 1, *tail_slab(C)), dtype)
    args = [pool, S((rows,), jnp.int32), S((rows, C), dtype),
            S((W, C), jnp.bfloat16)] + ([S((C,), jnp.bfloat16)] * biased)
    compiled = jax.jit(
        lambda pool, seats, x, k, b=None: conv_tail_step(
            pool, seats, x, k, b, interpret=False),
        donate_argnums=(0,)).lower(*args).compile()
    call = re.search(rf"%{KERNEL_NAME}(\.\d+)? = ([^\n]*)custom-call\([^\n]*"
                     r"tpu_custom_call", compiled.as_text())
    assert call
    pool_out = re.findall(r"[a-z0-9]+\[65,3,\d+,128\]\{[^}]*\}", call.group(2))
    assert pool_out and "S(1)" not in pool_out[0], call.group(2)
    sublanes = {jnp.float32: 96, jnp.bfloat16: 40}[dtype]
    assert compiled.memory_analysis().alias_size_in_bytes == (
        65 * 3 * sublanes * 128 * jnp.dtype(dtype).itemsize)


# the flat-token rungs of a packed prefill at 128-row ragged blocks
# (scheduler.packed_prefill_bucket: every rung to the budget of 8,192)
LIN_TOKENS = [128, 256, 512, 768, 1024, 1280, 1536, 1792, 2048, 3072, 4096,
              6144, 8192]


@pytest.mark.parametrize("tokens", LIN_TOKENS)
def test_a_linear_layer_compiles_for_v5e_at_every_rung(tokens, one_chip):
    """One linear-attention layer of Olmo-Hybrid-7B at the published
    widths over a packed prefill of ``tokens`` flat rows, eight prompts:
    its projections, the convolution, the chunked scan (chunk 64: the
    triangular solve a chunk and the ``lax.scan`` over chunks), the gated
    norm and the write of the seats' state and memory into the pool,
    which stays in place."""
    from tpuserve.models import transformer
    from tpuserve.models.weights import init_params
    from tpuserve.runtime.kv_cache import create_ssm_state
    from tpuserve.runtime.scheduler import packed_prefill_bucket

    S, place = shapes_on(one_chip)
    assert packed_prefill_bucket(tokens, 128) == tokens
    cfg = olmo_hybrid(num_layers=1)
    lp = place(jax.eval_shape(lambda: init_params(cfg, 0))["layers"][0])
    assert "lin" in lp and "q_proj" not in lp and "o_proj" not in lp
    entry = place(jax.eval_shape(
        lambda: create_ssm_state(cfg, MAX_NUM_SEQS))[0])
    i32, seqs = jnp.int32, S((PREFILL_SEQS,), jnp.int32)

    def layer(h, lp, positions, slots, blk_seq, q_starts, q_lens, entry,
              seats):
        h, entry = transformer._lin_packed(h, lp, cfg, positions, slots,
                                           blk_seq, q_starts, q_lens, 128,
                                           entry, seats)
        return transformer._mlp_residual(h, lp, cfg), entry

    compiled = jax.jit(layer, donate_argnums=(7,)).lower(
        S((tokens, cfg.hidden_size), jnp.bfloat16), lp, S((tokens,), i32),
        S((tokens,), i32), S((tokens // 128,), i32), seqs, seqs, entry,
        seqs).compile()
    mem = compiled.memory_analysis()
    # (the convolution's three rows a seat, 90 sublanes of whole lane
    # tiles each, are stored as 96)
    pool_bytes = 65 * (2_211_840 + 3 * 96 * 128 * 4)
    assert mem.alias_size_in_bytes == pool_bytes
    # what the layer holds beside its weights and the pool: activations a
    # few times the stream's q, k, v in float32, never a copy of the pool
    assert mem.temp_size_in_bytes < 40 * tokens * 11520 * 4 + (64 << 20)
