"""native/Makefile wired into tier-1: the canonical build entry point must
produce BOTH artifacts (CPython extension + ctypes C ABI) on a toolchain
host, and skip cleanly where g++ is unavailable — CI never needs the .so
(the runtime factory falls back to pure Python), but a Makefile rot would
otherwise ship broken until the next production image build."""

import os
import shutil
import subprocess
import sysconfig

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
OUTDIR = os.path.join(ROOT, "tpuserve", "native")


@pytest.mark.skipif(shutil.which("g++") is None,
                    reason="no C++ toolchain: runtime falls back to the "
                           "pure-Python block manager (clean skip)")
def test_makefile_builds_both_artifacts():
    out = subprocess.run(["make", "-C", NATIVE, "all"],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    ext = os.path.join(OUTDIR, f"_tpuserve_native{suffix}")
    cabi = os.path.join(OUTDIR, "libtpuserve_native.so")
    assert os.path.isfile(ext), "CPython extension missing after make"
    assert os.path.isfile(cabi), "ctypes C ABI library missing after make"


def test_python_fallback_needs_no_toolchain(monkeypatch):
    """impl='python' must never touch the toolchain — the CPU-only CI
    guarantee behind make_block_manager-style auto fallback."""
    from tpuserve.runtime.block_manager import BlockManager, \
        create_block_manager
    monkeypatch.setenv("TPUSERVE_BLOCK_MANAGER", "python")
    bm = create_block_manager(8, 4, impl="auto")
    assert isinstance(bm, BlockManager)


def test_build_from_source_ignores_what_is_on_disk_and_fails_loudly(
        monkeypatch, tmp_path):
    """chip_smoke.py's entry: always compile from the tracked sources
    (a stale or foreign .so on disk is never reused), and raise — not
    fall back — when the compile fails."""
    import os

    import tpuserve.native as native
    out = native.build_from_source()
    assert os.path.isfile(out)
    built = os.path.getmtime(out)
    assert native.build_from_source() == out
    assert os.path.getmtime(out) >= built            # rebuilt, not reused
    bad = tmp_path / "broken.cc"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_EXT_SRC", str(bad))
    with pytest.raises(RuntimeError, match="native build failed"):
        native.build_from_source()
    assert os.path.isfile(out)                       # the good one survives
