"""Persistent grammar-FSM compile cache (runtime/grammar/cache.py): disk
entries keyed by (spec hash, tokenizer fingerprint) skip the inline
determinizing walk at production-vocab size."""

import dataclasses

import numpy as np
import pytest

from tpuserve.models.config import get_model_config
from tpuserve.runtime.engine import Engine, EngineConfig
from tpuserve.runtime.kv_cache import CacheConfig
from tpuserve.runtime.request import SamplingParams
from tpuserve.runtime.scheduler import SchedulerConfig


@pytest.fixture(scope="module")
def fp32_cfg():
    return dataclasses.replace(get_model_config("tiny-qwen3"),
                               dtype="float32")


def _engine(fp32_cfg):
    return Engine(
        EngineConfig(model="tiny-qwen3",
                     cache=CacheConfig(block_size=4, num_blocks=64,
                                       max_blocks_per_seq=16),
                     scheduler=SchedulerConfig(max_num_seqs=4)),
        model_cfg=fp32_cfg)


def test_roundtrip_preserves_fsm_tables(tmp_path):
    from tpuserve.runtime.grammar import load_fsm, save_fsm
    from tpuserve.runtime.grammar.fsm import TokenFSM, pack_masks
    rng = np.random.default_rng(0)
    allow = rng.random((5, 100)) < 0.3
    fsm = TokenFSM(masks=pack_masks(allow),
                   tok_class=rng.integers(0, 7, 100).astype(np.int32),
                   class_next=rng.integers(-1, 5, (5, 7)).astype(np.int32),
                   can_finish=np.asarray([0, 1, 0, 1, 1], bool),
                   complete=np.asarray([0, 0, 0, 1, 1], bool),
                   vocab_size=100, start=0)
    save_fsm(str(tmp_path), "regex", "a+", "tokfp", fsm)
    got = load_fsm(str(tmp_path), "regex", "a+", "tokfp")
    for f in ("masks", "tok_class", "class_next", "can_finish", "complete"):
        np.testing.assert_array_equal(getattr(got, f), getattr(fsm, f))
    assert got.vocab_size == 100 and got.start == 0
    # different spec / different tokenizer = miss
    assert load_fsm(str(tmp_path), "regex", "b+", "tokfp") is None
    assert load_fsm(str(tmp_path), "regex", "a+", "other") is None


def test_corrupt_entry_is_a_miss_not_an_error(tmp_path):
    from tpuserve.runtime.grammar import load_fsm
    from tpuserve.runtime.grammar.cache import _entry_path
    path = _entry_path(str(tmp_path), "json", None, "fp")
    with open(path, "wb") as f:
        f.write(b"not an npz")
    assert load_fsm(str(tmp_path), "json", None, "fp") is None


def test_engine_persists_and_reloads_compiled_fsm(fp32_cfg, tmp_path,
                                                  monkeypatch):
    """Second engine (fresh process analog) serves the grammar from disk
    without re-walking the vocabulary: the compiler must not run at all
    on the hit path, and the guided stream is identical."""
    monkeypatch.setenv("TPUSERVE_FSM_CACHE_DIR", str(tmp_path))
    prompts = [[1, 2, 3, 4, 5]]
    params = SamplingParams(max_tokens=10, temperature=0.0, guided="json")
    first = _engine(fp32_cfg)
    a = first.generate(prompts, params)[0].output_token_ids
    assert first.stats.guided_fsm_requests == 1
    entries = list(tmp_path.iterdir())
    assert len(entries) == 1 and entries[0].name.startswith("fsm-")

    import tpuserve.runtime.grammar.compile as compile_mod

    def boom(*a, **k):
        raise AssertionError("inline FSM compile ran despite a disk hit")

    monkeypatch.setattr(compile_mod, "compile_token_fsm", boom)
    second = _engine(fp32_cfg)
    b = second.generate(prompts, params)[0].output_token_ids
    assert b == a
    assert second.stats.guided_fsm_requests == 1
    assert second._fsm_texts is None     # the 151k-text build was skipped


def test_no_cache_dir_disables_persistence(fp32_cfg, monkeypatch):
    monkeypatch.delenv("TPUSERVE_FSM_CACHE_DIR", raising=False)
    from tpuserve.runtime.grammar import resolve_cache_dir
    assert resolve_cache_dir(None) is None
    assert resolve_cache_dir("/ckpt").endswith("fsm_cache")


def test_fingerprint_separates_tokenizers(fp32_cfg):
    from tpuserve.models.tokenizer import ByteTokenizer
    from tpuserve.runtime.grammar import tokenizer_fingerprint
    a = tokenizer_fingerprint(ByteTokenizer(300), 300, {2})
    b = tokenizer_fingerprint(ByteTokenizer(400), 400, {2})
    c = tokenizer_fingerprint(ByteTokenizer(300), 300, {2})
    assert a != b and a == c
