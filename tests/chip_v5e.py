"""A described TPU v5e for the AOT compiles of ``tests/test_chip_compile*.py``:
the topology and cache fixtures, the sizes the engine dispatches at, and the
published configurations more than one of those files cuts.

Not a test file.  The topology is described inside a fixture, by whichever
xdist worker is given a file that uses it: ``tests/conftest.py`` lets several
processes load the TPU's library at once (``ALLOW_MULTIPLE_LIBTPU_LOAD``), so
the six files run on as many workers.  A file imports the fixtures it uses
by name.
"""

import dataclasses
import importlib.util

import jax
import pytest
from jax.sharding import SingleDeviceSharding

# (num_q_heads, num_kv_heads, head_dim)
WIDTHS = {
    "qwen3-0.6b": (16, 8, 128),
    "llama-8b": (32, 8, 128),        # Mistral-7B's widths too
    "llama-8b-tp4": (8, 2, 128),     # one shard of the four-chip smoke
    # five query heads a KV head: the first group that is not a power of
    # two (the block-size clamps halve rows, never heads, so it needs no
    # rule of its own; these compiles are the check)
    "falcon-h1-34b": (20, 4, 128),
    # eight query heads a KV head, nine layers in twelve behind a 1,024
    # window: the decode kernel alone (its other kernels compile at these
    # head counts inside the 12-layer trunks compiled by hand, PR 35)
    "mellum2-12b": (32, 4, 128),
    # thirty KV heads of ONE query head each (plain multi-head attention),
    # which reach the kernels as 32 and 32 (ModelConfig.cache_kv_heads: a
    # page row of 30 heads is not whole sublane tiles): a page of 32
    # tokens is a (1024, 128) slab, 262 KB a side, where the widths above
    # have 4 to 8 KV heads of 2 to 8 query heads each
    "olmo-hybrid-7b": (32, 32, 128),
}
# latent attention (openPangu-Ultra-MoE-718B, DeepSeek-V3): 128 query heads
# on ONE cached vector of 512 + 64 values a token, stored as 640 lanes; V is
# the first 512 lanes of the K page (tests/test_chip_compile_latent.py)
LATENT = (128, 640, 512)
PAGE = 32            # server default --block-size
NUM_BLOCKS = 2048    # server default --num-blocks
MAX_PAGES = 128      # 4096-token sequences
MAX_NUM_SEQS = 64    # SchedulerConfig.max_num_seqs
CHUNK = 2048         # SchedulerConfig.prefill_chunk_size
MIN_BUCKET = 32      # SchedulerConfig.min_prefill_bucket
MIXED_BUDGET = 2048  # SchedulerConfig.mixed_token_budget
PREFILL_SEQS = 8     # SchedulerConfig.max_prefill_seqs


@pytest.fixture(scope="module")
def topo():
    """Skipped where no TPU library is installed and nowhere else: any
    other failure to describe the chip (a second process refused the
    library's lock file, say) must FAIL, because a skip takes the file's
    tests off the count while the run still ends rc 0."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library is installed: nothing can describe a v5e")
    from jax.experimental import topologies
    # tests/conftest.py's rule for this file: the chip's programs are
    # asserted on as the optimising compiler makes them
    assert not jax.config.read("jax_disable_most_optimizations")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep it out of these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def shapes_on(sharding):
    """``S(shape, dtype)`` and ``place(tree)``: shapes on the described
    chip (nothing can be put there: a compile takes shapes)."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def place(tree):
        return jax.tree.map(lambda x: S(x.shape, x.dtype), tree)
    return S, place


def olmo_hybrid(**cut):
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(get_model_config("allenai/Olmo-Hybrid-7B"),
                               **cut)


def k_exaone_share(**cut):
    """K-EXAONE-236B-A23B's share of the benchmark's cell: 16 of 128
    experts, an eighth of the vocabulary."""
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(
        get_model_config("LGAI-EXAONE/K-EXAONE-236B-A23B"),
        moe_experts_held=16, vocab_size=19200, **cut)


def openpangu_share(**cut):
    """openPangu-Ultra-MoE-718B's share of the benchmark's cell: 16 of 256
    experts, an eighth of the vocabulary."""
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(
        get_model_config("FreedomIntelligence/openPangu-Ultra-MoE-718B"),
        moe_experts_held=16, vocab_size=19200, **cut)


def ling_share(**cut):
    """Ling-3.0-flash-VL's share of the benchmark's cell: routing group 0
    (64 of 512 experts), an eighth of the vocabulary (19,648 rows: the
    first in the benchmark that is not whole lane tiles)."""
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(
        get_model_config("inclusionAI/Ling-3.0-flash-VL"),
        moe_experts_held=64, vocab_size=19648, **cut)
