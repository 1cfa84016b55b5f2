"""The plain reference (``benchmark/reference/dense_gqa.py``) against the
engine's own forward pass on seeded random weights, at tiny sizes on the
CPU, in float32 so that the comparison is tight."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmark.reference import dense_gqa

# float32 on the CPU, both sides: what is left is the order of summation
ATOL = 2e-4


def f32(name):
    from tpuserve.models.config import get_model_config
    return dataclasses.replace(get_model_config(name), dtype="float32")


def engine_logprobs(params, cfg, tokens):
    from tpuserve.models import transformer
    logits = transformer.forward(params, cfg, tokens)
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


@pytest.mark.parametrize("name", ["tiny-qwen3", "tiny-mistral"])
def test_reference_agrees_with_the_engine(name):
    from tpuserve.models.weights import init_params
    cfg = f32(name)
    params = init_params(cfg, seed=3)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab_size - 1, size=(2, 40)).astype(np.int32)
    rows = [(b, t) for b in range(2) for t in (0, 7, 8, 9, 25, 39)]
    want = engine_logprobs(params, cfg, tokens)
    got = np.asarray(dense_gqa.logprobs_at(params, cfg, tokens, rows))
    assert got.shape == (len(rows), cfg.vocab_size)
    for i, (b, t) in enumerate(rows):
        np.testing.assert_allclose(got[i], want[b, t], atol=ATOL)


def test_the_sliding_window_is_in_the_reference():
    """tiny-mistral's window is 8: past it the reference must differ from
    the same weights run with full attention."""
    from tpuserve.models.weights import init_params
    cfg = f32("tiny-mistral")
    assert cfg.sliding_window == 8
    params = init_params(cfg, seed=3)
    tokens = np.arange(1, 33, dtype=np.int32)[None, :]
    full = dataclasses.replace(cfg, sliding_window=None)
    a = np.asarray(dense_gqa.logprobs_at(params, cfg, tokens, [(0, 31)]))
    b = np.asarray(dense_gqa.logprobs_at(params, full, tokens, [(0, 31)]))
    c = np.asarray(dense_gqa.logprobs_at(params, cfg, tokens, [(0, 5)]))
    d = np.asarray(dense_gqa.logprobs_at(params, full, tokens, [(0, 5)]))
    assert np.abs(a - b).max() > 1e-3
    np.testing.assert_allclose(c, d, atol=1e-6)


def test_right_padding_changes_nothing():
    from tpuserve.models.weights import init_params
    cfg = f32("tiny-qwen3")
    params = init_params(cfg, seed=1)
    rng = np.random.default_rng(1)
    seq = rng.integers(1, cfg.vocab_size - 1, size=(1, 20)).astype(np.int32)
    padded = np.concatenate([seq, np.zeros((1, 12), np.int32)], axis=1)
    a = np.asarray(dense_gqa.logprobs_at(params, cfg, seq, [(0, 19)]))
    b = np.asarray(dense_gqa.logprobs_at(params, cfg, padded, [(0, 19)]))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_the_head_is_applied_in_slices(monkeypatch):
    from tpuserve.models.weights import init_params
    cfg = f32("tiny-mistral")                  # untied head
    params = init_params(cfg, seed=2)
    tokens = np.arange(1, 17, dtype=np.int32)[None, :]
    whole = np.asarray(dense_gqa.logprobs_at(params, cfg, tokens, [(0, 15)]))
    monkeypatch.setattr(dense_gqa, "VOCAB_SLICE", 100)
    sliced = np.asarray(dense_gqa.logprobs_at(params, cfg, tokens, [(0, 15)]))
    np.testing.assert_allclose(whole, sliced, atol=1e-6)


@pytest.mark.parametrize("name", ["tiny-opt", "tiny-gemma2", "tiny-moe"])
def test_another_family_is_refused(name):
    from tpuserve.models.config import get_model_config
    with pytest.raises(ValueError):
        dense_gqa.check_family(get_model_config(name))


def test_probes_are_scored_left_to_right_in_served_order():
    """``score_probes``, the harness's call: served token j of a probe is
    scored after position ``len(prompt) + j - 1``, each probe alone or
    right-padded beside longer ones; the logprobs object is not read."""
    from tpuserve.models.weights import init_params
    cfg = f32("tiny-qwen3")
    params = init_params(cfg, seed=4)
    rng = np.random.default_rng(2)
    probes = [(rng.integers(1, 500, size=n).tolist(),
               rng.integers(1, 500, size=m).tolist(), {"unread": object()})
              for n, m in ((9, 4), (20, 6), (13, 5))]
    got = np.asarray(dense_gqa.score_probes(params, cfg, probes))
    assert got.shape == (15, cfg.vocab_size) and got.dtype == np.float32
    r = 0
    for ids, toks, _ in probes:
        seq = np.asarray([ids + toks], np.int32)
        rows = [(0, len(ids) + j - 1) for j in range(len(toks))]
        want = np.asarray(dense_gqa.logprobs_at(params, cfg, seq, rows))
        np.testing.assert_allclose(got[r:r + len(toks)], want, atol=1e-5)
        r += len(toks)
