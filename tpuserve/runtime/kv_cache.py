"""Paged KV cache device arrays + sizing.

Layout (per layer): K and V each ``(num_blocks, block_size, num_kv_heads,
head_dim)`` so a physical block is contiguous in HBM — the Pallas decode
kernel DMAs whole blocks, and the kv-head axis is shardable over the 'tp'
mesh axis.  The capacity math plays the role of the reference's PVC sizing
(reference: kubernetes-single-node.yaml:375-401 provisions fixed 100Gi PVCs;
here capacity is derived from the HBM budget).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from tpuserve.models.config import ModelConfig
from tpuserve.ops.attention import SCALE_LANES


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    block_size: int = 32
    num_blocks: int = 1024
    max_blocks_per_seq: int = 64
    # "bfloat16"/"float32" store raw; "int8" stores symmetric-absmax
    # quantized values plus one f32 scale per (token, kv head) in parallel
    # lane-padded ``ks``/``vs`` paged arrays (ops/attention.py
    # pad_scale_lanes) — halves the value bytes a decode step reads; the
    # saving has not been measured on the current code.
    dtype: str = "bfloat16"

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"

    @property
    def max_model_len(self) -> int:
        return self.block_size * self.max_blocks_per_seq


def bytes_per_block(model_cfg: ModelConfig, cache_cfg: CacheConfig,
                    head_shards: int = 1) -> int:
    """Bytes one block takes across all layers that hold K/V pages
    (``ModelConfig.kv_layers``: a linear-attention layer holds none) and
    all ``head_shards`` shards of the kv-head axis — what
    :func:`create_kv_cache` allocates, lane padding of the int8 scale
    pages included."""
    itemsize = jnp.dtype(cache_cfg.dtype).itemsize
    per_token = (model_cfg.cache_kv_heads * model_cfg.cache_head_dim
                 * itemsize)
    if cache_cfg.quantized:
        # MLA carries two f32 scales per token (latent + rope slices);
        # everyone else one 128-lane f32 row per token per head shard
        per_token += (8 if model_cfg.is_mla
                      else head_shards * SCALE_LANES * 4)
    # MLA stores ONE latent array (no V pages) — that asymmetry is the
    # ~10x cache-capacity win (models/transformer.py MLA section)
    kv_arrays = 1 if model_cfg.is_mla else 2
    return (kv_arrays * len(model_cfg.kv_layers) * cache_cfg.block_size
            * per_token)


def _ssm_layer(c: ModelConfig, num_seats: int) -> dict:
    """One layer of the recurrent-state pool, as shapes: Mamba-2 heads'
    (Falcon-H1), or a linear-attention layer's matrix states (either form
    of the gate: ``ModelConfig.lin_gate``) in slabs of heads whose lane
    axis is whole tiles (ops/pallas_gdn_update.py; one head a slab where a
    head's values are a lane tile, as Ling-3.0-flash's 128).
    Beside either, the short convolution's last ``W - 1`` inputs, each row
    of channels as whole 128-lane tiles down the sublanes
    (ops/pallas_conv_tail.py ``tail_slab``: a seat's memory is one
    contiguous piece that the decode step's kernel moves in place)."""
    from tpuserve.ops.pallas_conv_tail import tail_slab
    if c.linear_layers is not None:
        from tpuserve.ops.pallas_gdn_update import heads_per_slab
        hp = heads_per_slab(c.lin_num_value_heads, c.lin_value_head_dim)
        return {"state": jax.ShapeDtypeStruct(
                    (num_seats + 1, c.lin_num_value_heads // hp,
                     c.lin_key_head_dim, hp * c.lin_value_head_dim),
                    jnp.float32),
                # float32 like the products it is cut from
                # (models/transformer.py _lin_project)
                "conv": jax.ShapeDtypeStruct(
                    (num_seats + 1, c.lin_conv_kernel - 1,
                     *tail_slab(c.lin_conv_dim)), jnp.float32)}
    return {"state": jax.ShapeDtypeStruct(
                (num_seats + 1, c.mamba_n_heads, c.mamba_d_head,
                 c.mamba_d_state), jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                (num_seats + 1, c.mamba_d_conv - 1,
                 *tail_slab(c.mamba_conv_dim)), jnp.dtype(c.dtype))}


def ssm_state_bytes(model_cfg: ModelConfig, num_seats: int) -> int:
    """Bytes :func:`create_ssm_state` allocates for ``num_seats`` seats
    (the trash seat counted): zero for a model without recurrent state."""
    if not model_cfg.has_state:
        return 0
    return len(model_cfg.state_layers) * sum(
        math.prod(x.shape) * x.dtype.itemsize
        for x in _ssm_layer(model_cfg, num_seats).values())


def create_ssm_state(model_cfg: ModelConfig, num_seats: int) -> list[dict]:
    """Zero-initialised recurrent state, one entry a layer that holds one
    (``ModelConfig.state_layers``, in order: every layer of Falcon-H1, the
    linear-attention layers of Olmo-Hybrid and of Ling-3.0-flash, whose
    other layers hold LATENT pages in the cache beside it), each ``{"state": (seats + 1,
    H, P, N) float32 (a linear layer: :func:`_ssm_layer`), "conv": (seats
    + 1, W - 1, channels / 128, 128)}``: one slot a running sequence —
    NOT a page a token like the KV cache beside it — and a last one that
    padding rows read and write (``SeatPool.trash``).  Float32 state: the
    recurrence accumulates over every token of a sequence.  The trunks
    update it in place (models/transformer.py, donated like the cache)."""
    return [{k: jnp.zeros(x.shape, x.dtype)
             for k, x in _ssm_layer(model_cfg, num_seats).items()}
            for _ in model_cfg.state_layers]


def num_blocks_for_budget(model_cfg: ModelConfig, cache_cfg: CacheConfig,
                          hbm_bytes: int, utilization: float = 0.9,
                          weight_bytes: int | None = None,
                          head_shards: int = 1) -> int:
    """How many KV blocks fit in ``hbm_bytes`` after weights, at the given
    utilization fraction.  ``weight_bytes``: the ACTUAL loaded parameter
    bytes when known (int8-quantized weights buy a larger cache); defaults
    to the config-derived estimate.  The single source of the cache-budget
    formula (Engine._auto_num_blocks is the caller)."""
    if weight_bytes is None:
        weight_bytes = (model_cfg.num_params
                        * jnp.dtype(model_cfg.dtype).itemsize)
    budget = int(hbm_bytes * utilization) - weight_bytes
    if budget <= 0:
        # silently clamping to the 16-block floor here would boot an
        # engine whose real problem is "the model does not fit" but whose
        # visible symptom is a ~500-token max_seq_len and constant
        # preemption — fail loudly instead
        raise ValueError(
            f"model weights ({weight_bytes / 2**30:.2f} GiB) exceed the "
            f"memory budget ({hbm_bytes / 2**30:.2f} GiB x {utilization} "
            "utilization) — no room for a KV cache; use a bigger "
            "device/share, quantize the weights, or set num_blocks "
            "explicitly")
    return max(budget // bytes_per_block(model_cfg, cache_cfg, head_shards),
               16)


# --------------------------------------------------------------------------
# Device <-> host page copies (the tiered KV cache's data plane,
# runtime/kv_tiers.py).  Both directions move WHOLE physical blocks keyed
# by block id, preserving dtype — int8 KV pages demote at half the bytes
# of bf16, exactly the capacity ratio they have in HBM.
# --------------------------------------------------------------------------


@jax.jit
def _gather_pages(cache, idx):
    """One fused gather of ``idx`` blocks' pages from every layer/array."""
    return [{k: v[idx] for k, v in layer.items()} for layer in cache]


@partial(jax.jit, donate_argnums=(0,))
def _scatter_pages(cache, idx, pages):
    """Scatter host pages back into the donated cache arrays in place."""
    return [{k: v.at[idx].set(pages[li][k].astype(v.dtype))
             for k, v in layer.items()}
            for li, layer in enumerate(cache)]


def enqueue_block_pages_gather(kv_cache: list[dict],
                               blocks: list[int]) -> list[dict]:
    """Enqueue ONE fused gather of the given physical blocks' pages and
    return its device arrays without waiting: per layer ``{key: (padded
    blocks, block_size, heads, head_dim)}``, row ``i`` is ``blocks[i]``.
    Dispatch-only, like the scatter: the gather's output is a fresh
    buffer, ordered after every write that produced the pages and before
    any later-dispatched step that overwrites them, so the caller may
    dispatch that step at once and leave the copy to the host to
    ``fetch_block_pages`` on another thread.  (Not ``copy_to_host_async``
    here: on the chip it costs the calling thread 20-94 ms for 235-470 MB
    and holds up the enqueues that follow; PERF.md, PR 25.)

    The block-count axis is padded to a power of two (repeating the last
    id; the extra rows are discarded) so the jitted gather compiles a
    log-sized executable ladder instead of one per distinct eviction
    count.
    """
    from tpuserve.utils import next_power_of_2
    n = len(blocks)
    padded = list(blocks) + [blocks[-1]] * (next_power_of_2(n) - n)
    return _gather_pages(kv_cache, jnp.asarray(padded, jnp.int32))


def fetch_block_pages(gathered: list[dict]) -> list[dict]:
    """Copy a gathered batch (``enqueue_block_pages_gather``) to host
    numpy, blocking; the tier store's copier thread runs this.  Leaf by
    leaf, each leaf's copy started while the one before it is awaited:
    starting all of them at once (``jax.device_get`` of the whole batch)
    sets up hundreds of MB of host staging in one go, and the runtime
    holds up the engine thread's enqueues and host-to-device copies
    meanwhile (28 ms stalls behind a 470 MB batch; PERF.md, PR 25)."""
    import numpy as np
    leaves, treedef = jax.tree.flatten(gathered)
    if leaves:
        leaves[0].copy_to_host_async()
    out = []
    for leaf, ahead in zip(leaves, leaves[1:] + [None]):
        if ahead is not None:
            ahead.copy_to_host_async()
        out.append(np.asarray(leaf))
    return jax.tree.unflatten(treedef, out)


def gather_block_pages(kv_cache: list[dict],
                       blocks: list[int]) -> list[list[dict]]:
    """Copy the given physical blocks' KV pages to host numpy, returned
    per block: ``out[i]`` is a per-layer ``{key: (block_size, heads,
    head_dim) ndarray}`` list for ``blocks[i]`` — the value format the
    tier store (kv_tiers.TieredPageStore) files.  The blocking form of
    ``enqueue_block_pages_gather`` (same gather executable): warm-up and
    tests; the engine's demotion never waits here."""
    batched = fetch_block_pages(enqueue_block_pages_gather(kv_cache, blocks))
    return [[{k: v[i] for k, v in layer.items()} for layer in batched]
            for i in range(len(blocks))]


def scatter_block_pages(kv_cache: list[dict], blocks: list[int],
                        pages: list[list[dict]]) -> list[dict]:
    """Write per-block host pages (the ``gather_block_pages`` format)
    back into the cache at ``blocks``; returns the new (donated) cache.
    Dispatch-only — no sync: the copy lands on device asynchronously,
    ordered before any later-dispatched step that reads the pages, which
    is what lets a restore overlap the current fused window.

    Pads the block axis to a power of two by REPEATING the last
    (block, page) pair — duplicate scatters of identical content are
    idempotent — bounding the executable ladder like the gather."""
    import numpy as np

    from tpuserve.utils import next_power_of_2
    n = len(blocks)
    pad = next_power_of_2(n) - n
    padded_blocks = list(blocks) + [blocks[-1]] * pad
    rows = list(range(n)) + [n - 1] * pad
    idx = jnp.asarray(padded_blocks, jnp.int32)
    batched = [{k: np.stack([pages[i][li][k] for i in rows])
                for k in pages[0][li]}
               for li in range(len(pages[0]))]
    return _scatter_pages(kv_cache, idx, batched)


def _kv_head_shards(sharding) -> int:
    """How many ways a K/V page sharding splits the kv-head axis (axis 2)."""
    if sharding is None or len(sharding.spec) < 3 or sharding.spec[2] is None:
        return 1
    axes = sharding.spec[2]
    return math.prod(sharding.mesh.shape[a]
                     for a in ((axes,) if isinstance(axes, str) else axes))


def create_kv_cache(model_cfg: ModelConfig, cache_cfg: CacheConfig,
                    shardings=None) -> list[dict]:
    """Zero-initialised [{"k","v"}] paged cache, one entry a layer that
    holds pages (``ModelConfig.kv_layers``, in order).

    ``shardings``: a single NamedSharding, or a per-layer [{"k","v"}] pytree
    (as from ``tpuserve.parallel.cache_shardings``).  Each buffer is created
    directly in its sharded layout — never materialised on one device first.
    """
    shape = (cache_cfg.num_blocks, cache_cfg.block_size,
             model_cfg.cache_kv_heads, model_cfg.cache_head_dim)
    dtype = jnp.dtype(cache_cfg.dtype)

    def zeros(sh, shape=shape, dtype=dtype):
        if sh is not None:
            return jnp.zeros(shape, dtype, device=sh)
        return jnp.zeros(shape, dtype)

    def scale_sharding(sh):
        """Scale arrays drop the head_dim axis; reuse the KV sharding's
        first three axes so scales co-locate with their pages under tp."""
        if sh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(sh.mesh, PartitionSpec(*sh.spec[:3]))

    def scale_zeros(sh):
        """One 128-lane group of scales per shard of the kv-head axis
        (ops/attention.py pad_scale_lanes)."""
        groups = _kv_head_shards(sh)
        return zeros(scale_sharding(sh),
                     (*shape[:2], groups * SCALE_LANES), jnp.float32)

    cache = []
    for li in range(len(model_cfg.kv_layers)):
        if shardings is None:
            k_sh = v_sh = None
        elif isinstance(shardings, list):
            k_sh = shardings[li]["k"]
            v_sh = shardings[li].get("v")
        else:
            k_sh = v_sh = shardings
        if model_cfg.is_mla:
            # one latent array per layer; the decode path reads it as
            # both K and V (transformer.py absorbed MLA attention).
            # int8 stores TWO scales per token — the rmsnorm'd latent
            # slice and the raw roped-key slice have unrelated dynamic
            # ranges (ops/attention.py write_mla_entry).
            entry = {"k": zeros(k_sh)}
            if cache_cfg.quantized:
                entry["ks"] = zeros(scale_sharding(k_sh), (*shape[:2], 2),
                                    jnp.float32)
            cache.append(entry)
            continue
        entry = {"k": zeros(k_sh), "v": zeros(v_sh)}
        if cache_cfg.quantized:
            entry["ks"] = scale_zeros(k_sh)
            entry["vs"] = scale_zeros(v_sh)
        cache.append(entry)
    return cache
