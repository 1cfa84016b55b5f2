"""Host hot-path batching: window-batched detokenize/emit and the batched
block-manager boundary must be CONTENT-IDENTICAL to the historical
per-token / per-request path (TPUSERVE_HOST_BATCHED=0) — same tokens,
same text bytes, same finish reasons, same logprob entries — with only
the chunk granularity allowed to change (one multi-token chunk per fused
window instead of one per token).  Also covers the batched
IncrementalDetokenizer.add_many equivalence and the per-phase host
profiler's report."""

import dataclasses
import json
import urllib.request

import pytest

from tpuserve.models.config import get_model_config
from tpuserve.models.tokenizer import ByteTokenizer, IncrementalDetokenizer
from tpuserve.runtime.engine import Engine, EngineConfig
from tpuserve.runtime.kv_cache import CacheConfig
from tpuserve.runtime.request import SamplingParams
from tpuserve.runtime.scheduler import SchedulerConfig

PROMPTS = [[5, 6, 7], [11, 12, 13, 14, 15, 16, 17], [200, 201], [9, 9, 9]]


def _engine(multi_step=4, **eng_kw):
    cfg = EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=96,
                          max_blocks_per_seq=16, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=4, min_prefill_bucket=8,
                                  min_decode_bucket=4),
        attn_impl="reference", multi_step=multi_step, **eng_kw)
    mc = dataclasses.replace(get_model_config("tiny-qwen3"),
                             dtype="float32")
    return Engine(cfg, model_cfg=mc)


def _run_both(monkeypatch, params):
    batched = _engine().generate(PROMPTS, params)
    monkeypatch.setenv("TPUSERVE_HOST_BATCHED", "0")
    per_token = _engine().generate(PROMPTS, params)
    monkeypatch.delenv("TPUSERVE_HOST_BATCHED")
    return batched, per_token


def _same(a, b):
    assert [r.output_token_ids for r in a] == \
        [r.output_token_ids for r in b]
    assert [r.output_text for r in a] == [r.output_text for r in b]
    assert [r.finish_reason for r in a] == [r.finish_reason for r in b]


def test_window_emit_token_identity_greedy(monkeypatch):
    params = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    _same(*_run_both(monkeypatch, params))


def test_window_emit_token_identity_seeded_temperature(monkeypatch):
    params = [SamplingParams(max_tokens=9, temperature=0.8, seed=s,
                             ignore_eos=True) for s in (1, 2, 3, 4)]
    _same(*_run_both(monkeypatch, params))


def test_window_emit_identity_eos_and_stop_ids_and_min_tokens(monkeypatch):
    # EOS cuts mid-window (no ignore_eos), stop_token_ids cut, min_tokens
    # suppression crossing a window boundary — all must truncate at the
    # same TOKEN position as the per-token path
    params = [SamplingParams(max_tokens=12, temperature=0.9, seed=5),
              SamplingParams(max_tokens=12, temperature=0.9, seed=6,
                             stop_token_ids=(17, 301)),
              SamplingParams(max_tokens=11, temperature=0.7, seed=7,
                             min_tokens=6),
              SamplingParams(max_tokens=10, temperature=0.0)]
    _same(*_run_both(monkeypatch, params))


def test_window_emit_identity_stop_strings_fall_back(monkeypatch):
    # stop-string rows take the per-token path inside the batched flush:
    # both modes must agree on stored text AND stop hold-back semantics
    params = [SamplingParams(max_tokens=12, temperature=0.8, seed=2,
                             ignore_eos=True, stop=("ab", "Q")),
              SamplingParams(max_tokens=12, temperature=0.8, seed=3,
                             ignore_eos=True)]
    batched = _engine().generate(PROMPTS[:2], params)
    monkeypatch.setenv("TPUSERVE_HOST_BATCHED", "0")
    per_token = _engine().generate(PROMPTS[:2], params)
    monkeypatch.delenv("TPUSERVE_HOST_BATCHED")
    _same(batched, per_token)


def test_window_emit_identity_logprobs(monkeypatch):
    params = SamplingParams(max_tokens=9, temperature=0.8, seed=1,
                            ignore_eos=True, logprobs=3)
    batched = _engine().generate(PROMPTS[:2], params)
    monkeypatch.setenv("TPUSERVE_HOST_BATCHED", "0")
    per_token = _engine().generate(PROMPTS[:2], params)
    monkeypatch.delenv("TPUSERVE_HOST_BATCHED")
    _same(batched, per_token)
    for a, b in zip(batched, per_token):
        assert a.logprobs == b.logprobs


def test_batched_emit_chunks_tokens_per_window():
    """The batched flush emits ONE multi-token RequestOutput per row per
    window (the host win), not S single-token outputs."""
    eng = _engine(multi_step=4)
    rid = eng.add_request(prompt_token_ids=[5, 6, 7],
                          params=SamplingParams(max_tokens=8,
                                                temperature=0.0,
                                                ignore_eos=True))
    sizes = []
    while eng.has_work():
        for out in eng.step():
            assert out.request_id == rid
            sizes.append(len(out.new_token_ids))
    assert sum(sizes) == 8
    assert max(sizes) > 1          # at least one real window-sized chunk


def test_legacy_admission_matches_batched(monkeypatch):
    """TPUSERVE_HOST_BATCHED=0 restores the pre-batching inline admission
    loop; it must pick the identical batch (requests AND bucket) as
    block_manager.admit_prefill or the host-overhead A/B would compare
    different schedulers."""
    from tpuserve.runtime.block_manager import BlockManager
    from tpuserve.runtime.request import Request
    from tpuserve.runtime.scheduler import Scheduler, SchedulerConfig

    def build():
        bm = BlockManager(32, 4)
        s = Scheduler(SchedulerConfig(max_num_seqs=8, max_prefill_seqs=4,
                                      max_prefill_tokens=64,
                                      min_prefill_bucket=8), bm, 512)
        for i, n in enumerate((5, 9, 3, 30, 2)):
            s.add(Request(request_id=f"r{i}",
                          prompt_token_ids=list(range(n)),
                          params=SamplingParams()))
        return s

    a = build().schedule()
    monkeypatch.setenv("TPUSERVE_HOST_BATCHED", "0")
    b = build().schedule()
    monkeypatch.delenv("TPUSERVE_HOST_BATCHED")
    assert a.kind == b.kind == "prefill"
    assert [r.request_id for r in a.requests] == \
        [r.request_id for r in b.requests]
    assert a.padded_len == b.padded_len


# ---------------------------------------------------------------------
# IncrementalDetokenizer.add_many
# ---------------------------------------------------------------------

def test_add_many_matches_add_loop_randomized():
    import random
    rng = random.Random(0)
    tok = ByteTokenizer()
    # byte soup incl. multibyte UTF-8 runes split across windows and
    # invalid sequences (trailing-rune fallback path)
    corpus = ("hello wörld ✓ 你好 " * 3).encode("utf-8")
    for trial in range(200):
        ids = [rng.randrange(3, 259) for _ in range(rng.randrange(0, 24))]
        if rng.random() < 0.5 and len(corpus) > 8:
            off = rng.randrange(0, len(corpus) - 8)
            ids = [b + 3 for b in corpus[off:off + rng.randrange(1, 12)]]
        a, b = IncrementalDetokenizer(tok), IncrementalDetokenizer(tok)
        # split ids into random windows; add_many per window must equal
        # per-token adds in both emitted deltas-concat and final state
        i = 0
        combined = []
        while i < len(ids):
            w = min(len(ids) - i, rng.randrange(1, 6))
            combined.append(a.add_many(ids[i:i + w]))
            for t in ids[i:i + w]:
                b.add(t)
            i += w
        assert "".join(combined) == b.text, (trial, ids)
        assert a.text == b.text
        # follow-up token resolves any held partial rune identically
        assert a.add(ord("x") + 3) == b.add(ord("x") + 3), (trial, ids)


def test_add_many_empty_and_single():
    tok = ByteTokenizer()
    d = IncrementalDetokenizer(tok)
    assert d.add_many([]) == ""
    assert d.add_many([ord("h") + 3]) == "h"
    assert d.text == "h"


# ---------------------------------------------------------------------
# SSE stream content identity (window-batched + coalesced writes vs the
# per-token host path) over real HTTP
# ---------------------------------------------------------------------

def _stream_request(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    chunks = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for line in r:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                break
            chunks.append(json.loads(line[len("data: "):]))
    return chunks


def test_sse_stream_content_identical_batched_vs_per_token(monkeypatch):
    """The streamed BODY content — concatenated text, token id sequence,
    finish reason — must be identical between the window-batched/
    coalesced path and per-token flushing (greedy + seeded temperature).
    Chunk ids/timestamps are request-scoped, so identity is asserted on
    the content the client assembles, and the batched stream must
    actually carry multi-token chunks (the coalescing win)."""
    from tpuserve.server.openai_api import OpenAIServer, ServerConfig

    def collect(batched: bool):
        if not batched:
            monkeypatch.setenv("TPUSERVE_HOST_BATCHED", "0")
        eng = _engine(multi_step=4)
        if not batched:
            monkeypatch.delenv("TPUSERVE_HOST_BATCHED")
        srv = OpenAIServer(eng, ServerConfig(host="127.0.0.1", port=0))
        port = srv.start()
        try:
            rows = []
            for temp, seed in ((0.0, None), (0.8, 11)):
                body = {"model": "tiny-qwen3", "prompt": [5, 9, 12],
                        "max_tokens": 10, "temperature": temp,
                        "ignore_eos": True, "stream": True,
                        "return_token_ids": True}
                if seed is not None:
                    body["seed"] = seed
                chunks = _stream_request(port, body)
                text = "".join(c["choices"][0].get("text", "")
                               for c in chunks if c.get("choices"))
                ids = [t for c in chunks if c.get("choices")
                       for t in c["choices"][0].get("token_ids", [])]
                finish = [c["choices"][0]["finish_reason"]
                          for c in chunks if c.get("choices")
                          if c["choices"][0]["finish_reason"]]
                widths = [len(c["choices"][0].get("token_ids", []))
                          for c in chunks if c.get("choices")]
                rows.append((text, ids, finish, widths))
            return rows
        finally:
            srv.shutdown()

    fast = collect(batched=True)
    slow = collect(batched=False)
    for (ft, fi, ff, fw), (st, si, sf, sw) in zip(fast, slow):
        assert ft == st
        assert fi == si
        assert ff == sf
        assert len(fi) == 10
        assert max(fw) > 1        # window-sized chunks on the fast path
        assert max(sw) == 1       # per-token chunks on the legacy path


# ---------------------------------------------------------------------
# host phase profiler contract
# ---------------------------------------------------------------------

def test_hostprof_report_shape():
    from tpuserve.runtime.hostprof import PROF
    # PROF is the process's one profiler: reset around the test so the
    # counts are this test's own
    PROF.reset()
    try:
        PROF.bump_cycle()
        with PROF.phase("block"):
            pass
        with PROF.phase("schedule"):
            pass
        cycles, seconds, counts = (PROF.cycles, dict(PROF.seconds),
                                   dict(PROF.counts))
    finally:
        PROF.reset()
    assert cycles == 1
    assert set(seconds) == set(counts) == {"block", "schedule"}
    assert all(s >= 0 for s in seconds.values())
    assert counts["block"] == 1


def test_engine_soak_fills_host_phases():
    from tpuserve.runtime.hostprof import PROF
    eng = _engine(multi_step=4)
    PROF.reset()
    try:
        eng.generate(PROMPTS, SamplingParams(max_tokens=8, temperature=0.0,
                                             ignore_eos=True))
        cycles, seconds = PROF.cycles, dict(PROF.seconds)
    finally:
        PROF.reset()
    assert cycles > 0
    for name in ("schedule", "block", "dispatch", "detokenize", "flush"):
        assert name in seconds, seconds.keys()
