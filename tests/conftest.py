"""Test harness setup: an 8-virtual-device CPU JAX.

JAX_PLATFORMS=cpu and --xla_force_host_platform_device_count must be in the
environment before JAX creates its CPU client (it is created lazily, so
setting them at conftest import time is early enough).

This is the "fake backend" strategy of SURVEY.md §4: the reference only has
live-cluster smoke tests; unit tests against an 8-virtual-device CPU mesh are
one of the things this framework adds.
"""

import dataclasses
import os
import time

os.environ["JAX_PLATFORMS"] = "cpu"
# the AOT compiles for the chip (tests/test_chip_compile*.py) are six files
# on as many xdist workers, each of which loads the TPU's library to describe
# a v5e; no test attaches a chip, so the lock that keeps two processes off one
# chip guards nothing here
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

import pytest

# Every CPU compile of the ~1,900 tiny-model tests ran XLA's whole optimiser,
# for programs that run once or twice: the tests' own process compiles
# without the expensive passes, module by module as ``skips_xla_optimizations``
# rules.  In this process only, never through the environment: a server or a
# script that a test spawns keeps the compiler users run.
_FLAG = "jax_disable_most_optimizations"
jax.config.update(_FLAG, True)


def skips_xla_optimizations(path) -> bool:
    """Whether the test module at ``path`` compiles without XLA's expensive
    passes.  Not the benchmark's own tests, by DIRECTORY: they rehearse what
    decides ``correct`` and run under the compiler the benchmark runs under.
    Not the chip-compile files: they assert on the optimised program's text
    and bytes.  Every other module does: with the flag the four families'
    route tests spend at most 0.03 of their tolerance, without it 0.06
    (PERF.md §6, PR 48); a module that passed 0.5 would be named here."""
    parts = os.path.normpath(str(path)).split(os.sep)
    return not ("benchmark" in parts[-3:-1]
                or parts[-1].startswith("test_chip_compile"))


@pytest.fixture(scope="module", autouse=True)
def _xla_optimizations_by_module(request):
    """Set the flag as the module's path rules and put it back (compiled
    programs do not outlive a module: the fixture below drops them)."""
    was = jax.config.read(_FLAG)
    jax.config.update(_FLAG, skips_xla_optimizations(request.path))
    yield
    jax.config.update(_FLAG, was)


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert jax.default_backend() == "cpu"
    assert jax.device_count() == 8, (
        "tests expect 8 virtual CPU devices (xla_force_host_platform_device_count)")
    yield


@pytest.fixture(scope="module", autouse=True)
def _clear_jax_caches_between_modules():
    """Drop compiled-executable caches after every test module.

    The full suite compiles 600+ distinct executables in one process;
    around the ~590th test the XLA CPU compiler started SEGFAULTING
    inside backend_compile_and_load (observed twice at the same spot,
    never in isolation) — cumulative JIT code/arena exhaustion, not a
    bug in the test that happens to be standing there when it tips
    over.  Freeing the caches per module bounds the accumulation; each
    module recompiles its own shapes anyway."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def fp32_tiny_qwen3():
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(get_model_config("tiny-qwen3"), dtype="float32")


@pytest.fixture(scope="session")
def fp32_tiny_llama():
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(get_model_config("tiny-llama"), dtype="float32")


@pytest.fixture(scope="session")
def fp32_tiny_opt():
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(get_model_config("tiny-opt"), dtype="float32")


@pytest.fixture
def row_scatter_only(monkeypatch):
    """Call it to force every prefill trunk back onto the row scatter
    (tests only: the program has no such switch: ops/attention.py
    kv_stream_by_page decides from what is static).  Programs traced
    before are dropped, and none traced that way is left behind."""
    from tpuserve.ops import attention
    from tpuserve.runtime import engine

    def force():
        for mod in (attention, engine):
            monkeypatch.setattr(mod, "kv_stream_by_page",
                                lambda *a, **kw: False)
        jax.clear_caches()
    yield force
    monkeypatch.undo()
    jax.clear_caches()


# PR 40's test of the share group asserts of EVERY configuration in
# ``BENCHMARK.json`` that it holds no share, and the benchmark's contract
# appends the configuration that holds one (PR 41).  The file is the
# benchmark's and is not a program PR's to edit.  What the test means to
# hold, of the four accepted files by name, is held by
# ``test_benchmark_k_exaone_metrics.py::test_the_accepted_files_hold_no_share``.
# strict: the ``benchmark`` PR that loops over ``accepted.CONFIGS`` there
# takes this out.
_ASSERTS_NO_FILE_HOLDS_A_SHARE = (
    "tests/benchmark/test_benchmark_share_cut.py"
    "::test_the_accepted_files_cut_depth_alone_and_state_whole_sizes")


# Between the Mellum 2 rehearsal's two sends of one probe prompt, ~3 s of
# traffic must push the probe's blocks out of a 256-block pool; beside busy
# workers the window serves fewer requests, the prompt stays cached, and the
# engine files -1 for a cached position's picks (ISSUE 48: 77-82 requests
# and 0 of 64 tokens cached alone, 56 and 56 beside six busy processes).
# The file is the benchmark's and is not a program PR's to edit: the
# ``benchmark`` PR that gives the test a prompt of its own takes this out
# (ROADMAP.md C26).
_NEEDS_NO_CACHED_PREFIX = (
    "tests/benchmark/test_benchmark_mellum2_rehearsal.py"
    "::test_the_probes_request_is_answered_with_every_pick")


def _evict_every_cached_prefix(engine, timeout_s=60.0):
    """Wait for ``engine`` to drain, then take every block and give it back
    uncached, so that no prefix of an earlier request is found again."""
    manager = engine.block_manager
    deadline = time.monotonic() + timeout_s
    while engine.has_work() or manager.num_seqs():
        assert time.monotonic() < deadline, "the engine did not drain"
        time.sleep(0.05)
    tokens = manager.num_free_blocks * engine.cache_cfg.block_size
    manager.allocate("evict-every-cached-prefix", [0] * tokens)
    manager.free("evict-every-cached-prefix", cache_blocks=False)
    assert manager.num_cached_blocks == 0


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_call(item):
    if item.nodeid.endswith(_NEEDS_NO_CACHED_PREFIX):
        _evict_every_cached_prefix(item.funcargs["served"].engine)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid == _ASSERTS_NO_FILE_HOLDS_A_SHARE:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="loops over every configuration; the fifth holds "
                       "a share"))
