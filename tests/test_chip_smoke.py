"""CPU rehearsal of chip_smoke.py: every phase of the script at a tiny
size, on the CPU backend with the Pallas kernels in interpret mode.

The script itself refuses to run off the TPU and has no option to make it;
these tests enter BELOW its platform check (``run_one_chip`` /
``run_four_chip`` with a tiny ``Plan``), and steer what the script reads
from the platform here, in the test.  What they prove is control flow:
arguments, phases, assertions, the result line.  Nothing here is a chip
run."""

import dataclasses
import functools
import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _f32_model(base: str) -> str:
    """float32 twin of a tiny model: cross-implementation comparisons at
    bf16 on a 64-wide model sit below rounding (see config.py tiny-mistral)."""
    from tpuserve.models.config import (get_model_config,
                                        register_model_config)
    name = f"{base}-smoke-f32"
    register_model_config(dataclasses.replace(
        get_model_config(base), name=name, dtype="float32"))
    return name


def _tiny_plan(model: str, *extra: str, **kw) -> chip_smoke.Plan:
    return chip_smoke.Plan(
        model=model,
        server_args=("--model", model, "--num-blocks", "0",
                     "--block-size", "4", "--max-blocks-per-seq", "64",
                     "--max-num-seqs", "8", "--kv-cache-dtype", "float32",
                     "--attn-impl", "pallas", "--multi-step", "4",
                     "--pipeline", "--host", "127.0.0.1", "--port", "0",
                     *extra),
        multi_step=4, chunk=64, concurrent=4, max_tokens=8,
        side_cache=dict(block_size=4, num_blocks=256, max_blocks_per_seq=32,
                        dtype="float32"),
        kernel_widths=(4, 2, 16), **kw)


@pytest.fixture
def small_chunks(monkeypatch):
    """The server has no flag for the prefill chunk size; shrink the
    default so a 150-token prompt takes the chunked path."""
    import tpuserve.runtime.scheduler as sched
    monkeypatch.setattr(
        sched, "SchedulerConfig",
        functools.partial(sched.SchedulerConfig, prefill_chunk_size=64))


def test_one_chip_phases_at_tiny_size(tmp_path, small_chunks, capsys):
    plan = _tiny_plan(_f32_model("tiny-qwen3"), long_prompt=150)
    chip_smoke.run_one_chip(plan, chip_smoke.Meter(str(tmp_path)))
    out = capsys.readouterr().out
    for phase in ("kernels vs reference", "server start + warmup",
                  "short completions", "streamed chat", "4 concurrent",
                  "150-token prompt", "the same prompt again",
                  "short completions again", "greedy under pallas",
                  "greedy under reference", "mixed ragged batching",
                  "int8 KV under pallas", "int8 KV under reference"):
        assert f"phase {phase}" in out, phase
    assert "'prefill_chunk'" in out          # the ladder line names it


def test_four_chip_phases_on_four_virtual_devices(tmp_path, monkeypatch,
                                                  capsys):
    """--chips 4 at tiny size: tp=4 over 4 of the virtual CPU devices.
    The per-device-bytes assertion is made here as well as in the script:
    every leaf of weights and KV that is sharded holds a quarter a chip."""
    # interpret mode lowers a Pallas kernel to plain HLO: no custom call
    monkeypatch.setattr(chip_smoke, "check_kernels_in", lambda *a: None)
    seen = {}
    real = chip_smoke.check_sharding

    def check_sharding(engine):
        real(engine)
        held = {}
        for leaf in jax.tree.leaves((engine.params, engine.kv_cache)):
            for shard in leaf.addressable_shards:
                held[shard.device.id] = (held.get(shard.device.id, 0)
                                         + shard.data.nbytes)
        seen["held"] = held
        seen["total"] = sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((engine.params, engine.kv_cache)))
        seen["attn_mesh"] = engine._attn_mesh

    monkeypatch.setattr(chip_smoke, "check_sharding", check_sharding)
    plan = _tiny_plan(_f32_model("tiny-llama"), "--tp", "4", long_prompt=0,
                      warmup=False)
    chip_smoke.run_four_chip(plan, chip_smoke.Meter(str(tmp_path)))
    assert seen["attn_mesh"] is not None
    assert len(seen["held"]) == 4
    for dev, nbytes in seen["held"].items():
        assert nbytes <= 1.1 * seen["total"] / 4, (dev, seen)
    out = capsys.readouterr().out
    assert "all-reduce over 2 layers" in out
    assert "phase greedy under reference" in out


@dataclasses.dataclass
class _Dev:
    """What chip_smoke.main reads of a jax device."""
    platform: str = "tpu"
    device_kind: str = "TPU v5 lite"


def test_refuses_a_cpu(capsys):
    """JAX finds no TPU here: non-zero exit, no result line."""
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "no TPU" in out


@pytest.mark.parametrize("chips", [1, 4])
def test_result_line_shape(chips, monkeypatch, tmp_path, capsys):
    """On a TPU with every phase passing, the LAST stdout line is exactly
    the result object the driver reads, with the device as JAX names it."""
    monkeypatch.setattr(jax, "devices", lambda: [_Dev()] * chips)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    ran = []
    monkeypatch.setattr(chip_smoke, "run_one_chip",
                        lambda plan, meter: ran.append(plan))
    monkeypatch.setattr(chip_smoke, "run_four_chip",
                        lambda plan, meter: ran.append(plan))
    argv = [] if chips == 1 else ["--chips", "4"]
    assert chip_smoke.main(argv) == 0
    assert ran == [chip_smoke.ONE_CHIP if chips == 1
                   else chip_smoke.FOUR_CHIP]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": chips}}
    assert last == json.dumps(json.loads(last))


def test_wrong_chip_count_is_refused(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert '"ok"' not in capsys.readouterr().out
