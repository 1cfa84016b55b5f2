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

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

import pytest


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


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid == _ASSERTS_NO_FILE_HOLDS_A_SHARE:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="loops over every configuration; the fifth holds "
                       "a share"))
