"""A window whose step records hold MIXED steps (an engine whose decode
rows ride its prompt dispatches: one flat token axis, kind ``mixed``,
filed under the ``prefill`` phase with ``moe_rows`` / ``moe_expert_hits``
and a share's four counts as every other dispatch carries them, and
``ridden_tokens``): the accepted readers that sum step records still read
between 0 and 105 %.  Hand-made device events and step records, so
every number below can be worked out on paper.  No chip, and no number
here is a measurement."""

import os

import pytest

from benchmark.harness import host_spans, plan
from tests.benchmark import test_benchmark_k_exaone_metrics as held
from tests.benchmark import test_benchmark_moe_metrics as moe

V5E = moe.V5E


def mixed(decode_rows, prompt_tokens, padded, **counts):
    """The step record of a mixed step: ``decode_rows`` rows of one token
    and ``prompt_tokens`` of prompt chunks in a dispatch of ``padded``."""
    return {"kind": "mixed", "rows": decode_rows + 1,
            "actual_tokens": decode_rows + prompt_tokens,
            "padded_tokens": padded, "ctx_tokens": 20_000,
            "ridden_tokens": decode_rows, **counts}


def test_the_expert_kernels_roofline_reads_over_mixed_steps(monkeypatch):
    """Ten mixed steps of 512 rows (62 decode rows, 384 prompt tokens,
    padding routed like the rest) beside two decode windows of 2 steps:
    (10 x 512 + 2 x 2 x 64) x 8 x 12 routed rows over every expert, in
    (10 + 4) x 36 calls: the mixed steps' 12.4 ms a step of HBM time, the
    windows' 11.7: under 100 % at 14 ms a step, and over 0."""
    steps = [mixed(62, 384, 512, moe_rows=512 * 8 * 12,
                   moe_expert_hits=64 * 12) for _ in range(10)]
    steps += [moe.window(64, 2, 64), moe.window(64, 2, 64)]
    calls = [(1_000_000 + 400_000 * i, 14_000_000 // 36)
             for i in range(14 * 36)]
    run = moe.run_with(monkeypatch, calls, steps)
    run["trace"] = {"busy_s": 0.25}
    readers = plan.discover_layer_metrics()
    got = {n: readers[n].compute(run) for n in moe.NAMES}
    assert 0 < got["moe.gmm_roofline"] <= 105
    assert 80 < got["moe.gmm_roofline"] < 90
    assert 0 < got["moe.gmm_device_share"] <= 100
    rows = (10 * 512 + 4 * 64) * 8 * 12
    assert got["moe.gmm_ns_per_row"] == pytest.approx(
        14 * 36 * (14_000_000 // 36) / rows)


def test_a_shares_roofline_reads_over_mixed_steps(monkeypatch):
    """K-EXAONE's share: two mixed steps whose 512 rows land 512 of
    their 4,096 picks on the 16 held experts a layer (a piece of 576 buffer
    rows), filed under the ``prefill`` phase their trunk opens, beside a
    decode window of two steps: time and work from the same calls, phase
    by phase."""
    layers = held.LAYERS
    steps = [mixed(64, 448, 512, moe_rows=512 * 8 * layers,
                   moe_expert_hits=128 * layers,
                   moe_held_rows=512 * layers, moe_held_hits=16 * layers,
                   moe_buffer_rows=576 * layers, moe_held_pieces=layers)
             for _ in range(2)]
    steps.append(held.window(64, 2, 64, 16))
    # 16 experts' kernels (1.21 GB) are 1.47 ms of HBM a layer-step: three
    # calls of 550 us each, in both phases, clear of the built fusion
    calls = [(25_000_000 + 600_000 * i, 550_000, "prefill")
             for i in range(2 * layers * 3)]
    calls += [(60_000_000 + 600_000 * i, 550_000)
              for i in range(2 * layers * 3)]
    run = held.run_with(monkeypatch, calls, steps)
    readers = plan.discover_layer_metrics()
    got = {n: readers[n].compute(run) for n in held.HELD_NAMES}
    assert 0 < got["moe.held_gmm_roofline"] <= 105
    assert 85 < got["moe.held_gmm_roofline"] < 95
    assert 0 < got["moe.held_gmm_device_share"] <= 100
    assert got["moe.held_gmm_ns_per_row"] > 0


def test_the_decode_kernels_roofline_joins_no_mixed_step(monkeypatch):
    """``kernel.decode_attn_roofline`` divides the DECODE kernel's time by
    the context its decode windows attend: a mixed step's rows go through
    the ragged kernel and count on neither side, so the reading is what
    the windows alone give."""
    window = {"kind": "window", "rows": 64, "actual_tokens": 128,
              "ctx_tokens": 64_000}
    spans = {"steps_joined": [mixed(62, 384, 512), window,
                              mixed(63, 384, 512)],
             "decode_attn_ns": 0.012e9}
    spans["decode_ctx_tokens"] = sum(host_spans.attended(s)
                                     for s in spans["steps_joined"])
    assert spans["decode_ctx_tokens"] == host_spans.attended(window) \
        == 2 * 64_000 + 64
    monkeypatch.setattr(host_spans, "analyse", lambda run: spans)
    config = plan.read_json(os.path.join(plan.BENCH_ROOT, "configs",
                                         "mistral-7b-l16.json"))
    run = {"config": config, "peaks": V5E, "chips": 1,
           "kv_bytes_per_token": 65_536}
    got = plan.discover_layer_metrics()["kernel.decode_attn_roofline"] \
        .compute(run)
    # 128,064 context tokens x 65,536 B at 819 GB/s are 10.2 ms of the 12
    assert got == pytest.approx(100 * 128_064 * 65_536 / 819e9 / 0.012)
    assert 0 < got <= 105


def test_the_padding_share_counts_a_mixed_steps_flat_bucket():
    """A mixed step pads its decode region and each chunk to the ragged
    block and the whole to its rung: 1 - actual / padded over every
    record, mixed ones as the rest."""
    steps = [mixed(62, 384, 512), mixed(62, 130, 256),
             {"kind": "window", "rows": 62, "actual_tokens": 62 * 32,
              "padded_tokens": 64 * 32}]
    got = plan.discover_layer_metrics()["sched.pad_share"].compute(
        {"steps": steps})
    assert got == pytest.approx(
        100 * (1 - (446 + 192 + 1984) / (512 + 256 + 2048)))
    assert 0 < got <= 105
