"""bench.py runs on the device JAX gives it and says which: a run without
--smoke that finds no TPU exits non-zero instead of measuring the CPU
backend under a TPU metric's name; --smoke (the CPU tiny path) names its
device in the row; the HBM peak comes from a table keyed by device kind."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _bench(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": ""}
    return subprocess.run([sys.executable, os.path.join(ROOT, "bench.py"),
                           *args], capture_output=True, text=True,
                          timeout=600, cwd=ROOT, env=env)


def test_without_smoke_a_cpu_is_refused():
    out = _bench()
    assert out.returncode != 0
    assert '"metric"' not in out.stdout          # no result row of any kind
    assert "cpu" in out.stderr and "--smoke" in out.stderr


def test_smoke_row_names_its_device():
    out = _bench("--smoke")
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    assert len(rows) == 1                        # no provisional line
    row = rows[0]
    # (the count is whatever XLA_FLAGS gives the child: 8 under conftest)
    assert set(row["device"]) == {"platform", "kind", "count"}
    assert row["device"]["platform"] == row["device"]["kind"] == "cpu"
    assert row["device"]["count"] >= 1
    assert row["value"] > 0
    # a roofline share is a device metric, and the fallback's fields are gone
    for key in ("roofline", "degraded", "provisional"):
        assert key not in row


def test_hbm_peak_table_is_keyed_by_device_kind():
    import bench
    assert bench._hbm_gbs("TPU v5 lite") == 819.0
    with pytest.raises(SystemExit, match="no HBM peak recorded"):
        bench._hbm_gbs("TPU v9000")
    with pytest.raises(SystemExit):
        bench._hbm_gbs("cpu")


def test_bench_has_no_fallback_machinery():
    """One process on one device: no re-exec, no child probe, no signal
    re-flush, no carried-over result."""
    src = open(os.path.join(ROOT, "bench.py")).read()
    for gone in ("execve", "import signal", "JAX_PLATFORMS",
                 "_FINAL", "bench_r0"):
        assert gone not in src, gone
    # the only subprocess is `git rev-parse` for the commit stamp
    assert re.findall(r"subprocess\.\w+\(\s*\[([^\]]*)\]", src) == [
        '"git", "rev-parse", "--short", "HEAD"']
