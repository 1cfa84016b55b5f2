"""profile_step.py (step-time attribution) must keep producing its JSON
contract on CPU, so the tool still works when it is next run on the chip."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_profile_smoke_emits_attribution_row():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "profile_step.py"),
         "--smoke", "--windows", "3"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "step_attribution"
    assert row["window_wall_ms"] > 0
    assert row["tok_s_implied"] > 0
    assert row["weight_stream_gb_s"] > 0
    # XLA cost analysis present on the CPU backend too
    assert row.get("xla_bytes_accessed_per_window", 0) > 0
    # a roofline share is a device metric: a CPU row carries none
    assert "residual_ms" not in row and "hbm_fraction" not in row


def test_profile_host_soak_emits_phase_breakdown():
    """--streams N --json: the per-phase host-time breakdown (schedule /
    block-accounting / dispatch / detokenize / flush) — the diffable
    before/after artifact of the host-overhead A/B."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "profile_step.py"),
         "--streams", "8", "--gen-len", "24", "--json"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "host_phase_breakdown"
    assert row["streams"] == 8
    assert row["cycles"] > 0
    assert row["multi_step"] > 1          # the soak exercises fused windows
    for phase in ("schedule", "block", "dispatch", "detokenize", "flush"):
        assert phase in row["phases"], row["phases"].keys()
    assert row["host_ms_per_cycle"] >= 0
    assert isinstance(row["host_batched"], bool)


def test_profile_host_soak_legacy_env_is_recorded():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "profile_step.py"),
         "--streams", "4", "--gen-len", "16", "--json"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "",
             "TPUSERVE_HOST_BATCHED": "0",
             "TPUSERVE_BLOCK_MANAGER": "python"})
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["host_batched"] is False
    assert row["block_manager"] == "BlockManager"
