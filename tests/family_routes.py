"""One harness for the model families' route tests (``tests/test_mellum2.py``,
``test_k_exaone.py``, ``test_falcon_h1.py``, ``test_olmo_hybrid.py``,
``test_seat_pool.py``): a paged cache driven by hand through every trunk,
each dispatch's logits against the plain reference the benchmark scores the
family by (``benchmark/reference/``: float32, no code shared with
``tpuserve``).

Not a test file.  A new family adds an entry to ``FAMILIES`` and its own
cases, not a harness.

The reference compiles once.  Its layers are jitted by the SHAPE of the
token array, and a route test asks for logits after ~20 prefixes of
different lengths a case, which was ~20 compiles of every layer.
``ref_logits`` pads the sequence to ``REF_LEN`` tokens before it calls the
reference (which is the benchmark's and is not edited) and reads the rows
of the real positions.  That is sound because nothing at a position can
depend on a later one: every attention layer is causal (a windowed layer's
window ENDS at the position read), the state-space and linear layers are
recurrences over earlier tokens, a causal convolution reaches back only, and
norms, MLPs and experts act a token at a time.  ``tests/test_family_routes.py``
pins it: padded rows against unpadded, a case a family, to a fifth of the
family's tolerance.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.models import transformer
from tpuserve.ops.attention import PAD_SLOT
from tpuserve.runtime import CacheConfig, Engine, EngineConfig, SamplingParams
from tpuserve.runtime.kv_cache import create_kv_cache, create_ssm_state
from tpuserve.runtime.scheduler import SchedulerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:            # ``benchmark`` is a package of the root
    sys.path.insert(0, ROOT)
from benchmark.harness import plan  # noqa: E402

BLOCK = 4               # KV block size of the hand-driven caches
SEATS = 6               # seats of a hand-driven state pool (and a trash seat)
REF_LEN = 64            # every sequence a test hands a reference is shorter


@dataclasses.dataclass(frozen=True)
class Family:
    """What the harness is told of a family."""
    model: str              # the registered tiny model
    reference: str          # its module under benchmark/reference/
    atol: float             # a route's logits against the reference's
    # a trunk's last result: the routing counts of its expert layers
    # ("counts"), or the seat pool of its recurrent layers, which it also
    # takes, with the rows' seats, after the paged cache ("pool"); a family
    # with both returns the pool and then the counts ("pool+counts")
    returns_last: str
    prompts: tuple          # three uneven prompts, batched or packed
    chunked: int            # the prompt a chunked route takes 16 rows a time
    engine: dict            # its SchedulerConfig and CacheConfig fields

    @property
    def ref(self):
        return _reference(self.reference)


@functools.lru_cache(maxsize=None)
def _reference(name):
    """A family's plain reference, loaded as the harness loads it, once:
    every load is a module of its own, with jitted layers of its own."""
    return plan.load_reference({"reference": name})


_EXPERTS = {"scheduler": {"min_prefill_bucket": 8, "min_decode_bucket": 2},
            "cache": {"num_blocks": 96, "max_blocks_per_seq": 24}}
_SEATED = {"scheduler": {"max_num_seqs": 4, "prefill_chunk_size": 16},
           "cache": {"num_blocks": 128, "max_blocks_per_seq": 32}}
FAMILIES = {
    # the prompt of 48 is three windows of 16 long; the prompt of 14
    # crosses the window while it decodes
    "mellum2": Family("tiny-mellum2", "mellum2", 2e-4, "counts",
                      (48, 14, 29), 48, _EXPERTS),
    # five windows of 8; the prompt of 6 crosses the window
    "k_exaone": Family("tiny-k-exaone", "k_exaone", 2e-4, "counts",
                       (40, 6, 29), 40, _EXPERTS),
    # none a multiple of the scan's chunk of 8; 16 + 16 + 11 rows
    "falcon_h1": Family("tiny-falcon-h1", "falcon_h1", 2e-4, "pool",
                        (5, 19, 12), 43, _SEATED),
    "olmo_hybrid": Family("tiny-olmo-hybrid", "olmo_hybrid", 5e-4, "pool",
                          (5, 19, 12), 43, _SEATED),
    # latent attention: none a multiple of a ragged block of 8; the prompt
    # of 40 is two and a half chunks of 16 (a chunk against cached latents)
    "openpangu": Family("tiny-pangu", "openpangu_moe", 2e-4, "counts",
                        (40, 6, 29), 40, _EXPERTS),
    # Kimi-delta layers beside latent attention behind grouped experts: a
    # pool AND latent pages AND routing counts.  None a multiple of the
    # scan's chunk of 32 or of its sub-block of 16; 16 + 16 + 11 rows.  The
    # tolerance is Olmo-Hybrid's, for its reason: the chunked form against
    # the reference's row-by-row recurrence, whose float32 orders of
    # summation differ over a chunk's 32 rows
    "ling_hybrid": Family("tiny-ling-hybrid", "ling_hybrid", 5e-4,
                          "pool+counts", (5, 19, 37), 43,
                          {"scheduler": {**_SEATED["scheduler"],
                                         "min_prefill_bucket": 8,
                                         "min_decode_bucket": 2},
                           "cache": _SEATED["cache"]}),
}


def prompts_of(*lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(2, 256, n)] for n in lengths]


def ref_logits(family, params, cfg, seq, positions):
    """Reference logits after each of ``positions`` of one sequence, read
    from a forward pass over the sequence padded to ``REF_LEN`` (the
    module's docstring says why that is the same)."""
    positions = list(positions)
    assert len(seq) <= REF_LEN and all(0 <= p < len(seq) for p in positions)
    tokens = np.zeros((1, REF_LEN), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(family.ref.logits_at(
        params, cfg, tokens, [(0, p) for p in positions]))


def ref_greedy(family, params, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(ref_logits(family, params, cfg, seq,
                                            [len(seq) - 1])[0])))
    return seq[len(prompt):]


class Served:
    """A paged cache driven by hand: sequence ``i`` owns the blocks
    ``[i * mb, (i + 1) * mb)`` and, where the family keeps a state, seat
    ``i`` of a pool of ``SEATS`` and the trash seat.  ``counts`` sums the
    routing counts an expert family's trunks return last."""

    mb = 20                                     # blocks a sequence

    def __init__(self, family, cfg, params, n_seqs, attn_impl="reference",
                 dtype="float32"):
        self.family, self.cfg, self.params = family, cfg, params
        self.attn_impl = attn_impl
        self.kv = create_kv_cache(cfg, CacheConfig(
            block_size=BLOCK, num_blocks=n_seqs * self.mb,
            max_blocks_per_seq=self.mb, dtype=dtype))
        self.pool, self.counts = None, 0
        self.counted = family.returns_last.endswith("counts")
        if family.returns_last.startswith("pool"):
            # what a seat held before must not matter: fill it with junk
            self.pool = jax.tree.map(lambda x: jnp.full_like(x, 3.0),
                                     create_ssm_state(cfg, SEATS))
        self.tables = np.arange(n_seqs * self.mb, dtype=np.int32).reshape(
            n_seqs, self.mb)

    def _run(self, trunk, *args, seats, **kw):
        """``trunk`` on the cache (and the pool with the rows' ``seats``):
        what it returns, with the cache, the pool and the counts kept."""
        state = () if self.pool is None else (
            None, self.pool, jnp.asarray(seats, jnp.int32))
        res = trunk(self.params, self.cfg, *map(jnp.asarray, args), self.kv,
                    *state, attn_impl=self.attn_impl, **kw)
        self.kv = res[1]
        if self.counted:
            self.counts = self.counts + np.asarray(res[-1][0], np.int64)
        if self.pool is not None:
            self.pool = res[-2 if self.counted else -1]
        return res

    def slots(self, i, start, n):
        t = np.arange(start, start + n)
        return (self.tables[i, t // BLOCK] * BLOCK + t % BLOCK).astype(
            np.int32)

    def prefill(self, prompts):
        B, L = len(prompts), 64
        tokens = np.zeros((B, L), np.int32)
        slot_ids = np.full((B, L), PAD_SLOT, np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            slot_ids[i, :len(p)] = self.slots(i, 0, len(p))
        return np.asarray(self._run(
            transformer.prefill, tokens,
            np.asarray([len(p) for p in prompts], np.int32), slot_ids,
            seats=np.arange(B))[0])

    def packed(self, prompts, blk=8, riding=()):
        """Several prompts on one flat token axis, each starting on a
        ``blk``-row boundary, as Engine._pack_ragged lays them out.
        ``riding``: a mixed step, in which each of these running sequences
        (the first of the cache) rides with its last token, a decode row at
        the head of the stream (flat row == sequence), and the prompts, the
        next sequences', start behind the decode region.  A row of logits
        a sequence, the riding ones first."""
        B, n_dec = 4, len(riding)                       # a spare row
        starts = []
        cursor = transformer.decode_region(B, blk) if riding else 0
        for p in prompts:
            starts.append(cursor)
            cursor += -(-len(p) // blk) * blk
        T = cursor + blk                                # a padding block
        tokens = np.zeros((T,), np.int32)
        positions = np.zeros((T,), np.int32)
        slot_ids = np.full((T,), PAD_SLOT, np.int32)
        row_seq = np.zeros((T,), np.int32)
        kv_lens, q_lens = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        q_starts = np.full((B,), T, np.int32)
        last_rows = np.zeros((B,), np.int32)
        tables = np.zeros((B, self.mb), np.int32)
        blk_seq = np.full((T // blk,), -1, np.int32)
        for i, s in enumerate(riding):
            tokens[i], positions[i] = s[-1], len(s) - 1
            slot_ids[i] = self.slots(i, len(s) - 1, 1)[0]
            row_seq[i] = q_starts[i] = last_rows[i] = i
            kv_lens[i], q_lens[i] = len(s), 1
            tables[i] = self.tables[i]
        for i, (p, s) in enumerate(zip(prompts, starts), start=n_dec):
            n = len(p)
            tokens[s:s + n], positions[s:s + n] = p, np.arange(n)
            slot_ids[s:s + n], row_seq[s:s + n] = self.slots(i, 0, n), i
            kv_lens[i] = q_lens[i] = n
            q_starts[i], last_rows[i] = s, s + n - 1
            tables[i] = self.tables[i]
            blk_seq[s // blk:(s + -(-n // blk) * blk) // blk] = i
        used = n_dec + len(prompts)
        seats = np.full((B,), SEATS, np.int32)          # spare row: trash
        seats[:used] = np.arange(used)
        logits = self._run(
            transformer.forward_ragged, tokens, positions, slot_ids, row_seq,
            tables, kv_lens, q_starts, q_lens,
            np.asarray([n_dec, -(-n_dec // blk)], np.int32), blk_seq,
            last_rows, seats=seats, ragged_blk=blk,
            decode_rows=bool(riding))[0]
        return np.asarray(logits)[:used]

    def chunks(self, prompt, C=16):
        """One prompt, ``C`` rows a dispatch (state and convolution memory
        carried from chunk to chunk); the logits after each."""
        out = []
        for done in range(0, len(prompt), C):
            part = prompt[done:done + C]
            tokens = np.zeros((1, C), np.int32)
            tokens[0, :len(part)] = part
            slot_ids = np.full((1, C), PAD_SLOT, np.int32)
            slot_ids[0, :len(part)] = self.slots(0, done, len(part))
            out.append(np.asarray(self._run(
                transformer.prefill_chunk, tokens,
                np.asarray([done], np.int32),
                np.asarray([len(part)], np.int32), slot_ids, self.tables[:1],
                seats=np.zeros((1,)))[0])[0])
        return out

    def decode(self, seqs):
        """One token a row: ``seqs[i]`` ends in the token to decode."""
        B = len(seqs)
        n = np.asarray([len(s) for s in seqs], np.int32)
        return np.asarray(self._run(
            transformer.decode_step,
            np.asarray([s[-1] for s in seqs], np.int32), n - 1,
            np.asarray([self.slots(i, n[i] - 1, 1)[0] for i in range(B)]),
            self.tables[:B], n, seats=np.arange(B))[0])

    def window(self, seqs, steps):
        """A fused greedy window with one padding row: tokens and the
        chosen tokens' log-probabilities, (B, steps) each."""
        cfg, B = self.cfg, len(seqs) + 1
        n = np.ones((B,), np.int32)
        n[:len(seqs)] = [len(s) for s in seqs]
        tokens = np.zeros((B,), np.int32)
        tokens[:len(seqs)] = [s[-1] for s in seqs]
        tables = np.zeros((B, self.mb), np.int32)
        tables[:len(seqs)] = self.tables[:len(seqs)]
        active = np.arange(B) < len(seqs)
        toks, _, lp, *_, last = self._run(
            transformer.decode_multi, tokens, n - 1, tables, n, active,
            np.zeros((B, 2), np.uint32), np.zeros((B,), np.float32),
            seats=np.where(active, np.arange(B), SEATS), steps=steps,
            mode="greedy", logprobs_n=1)
        if self.counted:
            # the rows' picks ride fourth with the logprobs, [row, step],
            # one entry an EXPERT layer (a dense layer has none)
            sparse, E = cfg.num_layers - cfg.moe_first_k_dense, cfg.num_experts
            assert lp[3].shape == (B, steps, sparse, cfg.num_experts_per_tok)
            assert last[1:] == (None, None)
            counts = np.asarray(last[0], np.int64)
            # B rows, k picks, every expert layer, every fused step
            assert counts[:E].sum() == (B * cfg.num_experts_per_tok * sparse
                                        * steps)
            assert 0 < counts[E] <= E * sparse * steps
        return np.asarray(toks)[:len(seqs)], np.asarray(lp[0])[:len(seqs)]


def then_decode(served, seqs, first_logits):
    """After any prefill route: its logits, three decode steps and a fused
    window of four, each against the reference's full forward."""
    family, atol = served.family, served.family.atol

    def ref_rows(seq, positions):
        return ref_logits(family, served.params, served.cfg, seq, positions)

    seqs = [list(s) for s in seqs]
    for i, s in enumerate(seqs):
        np.testing.assert_allclose(
            first_logits[i], ref_rows(s, [len(s) - 1])[0], atol=atol)
        s.append(int(np.argmax(first_logits[i])))
    for _ in range(3):
        logits = served.decode(seqs)
        for i, s in enumerate(seqs):
            np.testing.assert_allclose(
                logits[i], ref_rows(s, [len(s) - 1])[0], atol=atol)
            s.append(int(np.argmax(logits[i])))
    toks, lps = served.window(seqs, 4)
    for i, s in enumerate(seqs):
        assert list(toks[i]) == ref_greedy(family, served.params, served.cfg,
                                           s, 4)
        full = s + list(toks[i])
        rows = np.asarray(jax.nn.log_softmax(ref_rows(
            full, range(len(s) - 1, len(full) - 1))))
        np.testing.assert_allclose(
            lps[i], rows[np.arange(4), toks[i]], atol=atol)


def run_route(family, cfg, params, route, attn_impl):
    """(B, L) ``prefill``, a ``packed`` prefill of the family's three uneven
    prompts, one prompt over three ``chunks``, or a ``mixed`` step (two
    running rows riding the third prompt's dispatch); then ``decode_step``
    and a fused ``decode_multi`` window.  ``pallas``: the kernels in
    interpret mode.  Returns the hand-driven cache."""
    if route == "chunks":
        seqs = prompts_of(family.chunked)
        served = Served(family, cfg, params, 1, attn_impl)
        per_chunk = served.chunks(seqs[0])
        for logits, upto in zip(per_chunk, (16, 32, family.chunked)):
            np.testing.assert_allclose(
                logits,
                ref_logits(family, params, cfg, seqs[0], [upto - 1])[0],
                atol=family.atol)
        first = [per_chunk[-1]]
    elif route == "mixed":
        seqs = prompts_of(*family.prompts)
        served = Served(family, cfg, params, 3, attn_impl)
        for s, logits in zip(seqs[:2], served.packed(seqs[:2])):
            np.testing.assert_allclose(
                logits, ref_logits(family, params, cfg, s, [len(s) - 1])[0],
                atol=family.atol)
            s.append(int(np.argmax(logits)))
        first = served.packed(seqs[2:], riding=seqs[:2])
    else:
        seqs = prompts_of(*family.prompts)
        served = Served(family, cfg, params, 3, attn_impl)
        first = served.prefill(seqs) if route == "prefill" \
            else served.packed(seqs)
    then_decode(served, seqs, first)
    return served


def engine_for(family, params=None, cfg=None, *, scheduler=(), cache=(),
               **kw):
    """The family's engine on float32 pages of ``BLOCK`` tokens: its
    ``FAMILIES`` entry's scheduler and cache, a test's own fields over
    them."""
    return Engine(EngineConfig(
        model=family.model,
        scheduler=SchedulerConfig(**{**family.engine["scheduler"],
                                     **dict(scheduler)}),
        cache=CacheConfig(**{"block_size": BLOCK, "dtype": "float32",
                             **family.engine["cache"], **dict(cache)}),
        **kw), params=params, model_cfg=cfg)


def serve(engine, prompts, max_tokens=10):
    """Greedy tokens of ``prompts`` through ``Engine.step``."""
    rids = [engine.add_request(
        prompt_token_ids=p, params=SamplingParams(
            max_tokens=max_tokens, temperature=0.0, ignore_eos=True))
        for p in prompts]
    out = {r: [] for r in rids}
    while engine.has_work():
        for o in engine.step():
            out[o.request_id] += o.new_token_ids
    return [out[r] for r in rids]
