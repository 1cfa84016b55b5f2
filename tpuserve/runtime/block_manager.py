"""Paged KV-cache block manager with hash-based prefix caching.

Python implementation of the block-table bookkeeping that vLLM does inside
the container the reference deploys (the reference delegates the whole
engine: kubernetes-single-node.yaml:14, llm-d-deploy.yaml:140-193).
The interface is deliberately ctypes-friendly; ``tpuserve.native`` provides a
C++ drop-in replacement for the hot bookkeeping when built.

Design: physical blocks of ``block_size`` token slots; per-sequence block
tables map logical block index -> physical block id.  Full prompt blocks are
content-hashed (chained, so a hash identifies the whole prefix) for
copy-free prefix reuse.  Freed hashed blocks move to an LRU "cached" pool:
still holding their KV contents, reusable by a later request with the same
prefix, evicted only when fresh blocks run out.
"""

from __future__ import annotations

import dataclasses
import logging
from collections import OrderedDict
from typing import Optional

from tpuserve.utils import cdiv, next_power_of_2

logger = logging.getLogger("tpuserve.block_manager")


# Sentinel in a sequence's block table for a leading block returned to the
# pool by the sliding-window rolling buffer (release_out_of_window): the
# logical index keeps its place so tail slot arithmetic is unchanged.
RELEASED = -1


@dataclasses.dataclass
class SeqAlloc:
    blocks: list[int]
    num_tokens: int                  # tokens written so far
    released_upto: int = 0           # logical blocks returned to the pool


class SeatPool:
    """Seats of the engine's recurrent-state pool (a model with
    state-space layers keeps one slot of state a SEQUENCE beside its pages
    of keys and values; runtime/kv_cache.create_ssm_state).  A manager that
    is given one (``manager.seats``) takes a seat with a sequence's first
    blocks and gives it back when it frees them, so every path that drops
    a sequence's KV — finish, abort, pre-emption, salvage — drops its
    state with it.  ``trash`` is the extra slot padding rows share."""

    def __init__(self, num_seats: int):
        self.num_seats = self.trash = num_seats
        self._free = list(range(num_seats - 1, -1, -1))
        self._seat: dict[str, int] = {}
        self.acquired = 0               # seats handed out, ever

    def acquire(self, seq_id: str) -> int:
        if not self._free:
            raise RuntimeError(
                f"no free state seat for {seq_id}: {self.num_seats} "
                "sequences already hold one (the scheduler admits at most "
                "max_num_seqs)")
        seat = self._seat[seq_id] = self._free.pop()
        self.acquired += 1
        return seat

    def release(self, seq_id: str) -> None:
        seat = self._seat.pop(seq_id, None)
        if seat is not None:
            self._free.append(seat)

    def of(self, seq_id: str) -> int:
        return self._seat[seq_id]

    @property
    def in_use(self) -> int:
        return len(self._seat)


class BlockManager:
    """Allocates physical cache blocks to sequences; optional prefix cache."""

    #: a SeatPool when the model has recurrent state (set by the engine)
    seats: Optional[SeatPool] = None

    def __init__(self, num_blocks: int, block_size: int, enable_prefix_caching: bool = True):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self._free: list[int] = list(range(num_blocks - 1, -1, -1))
        # freed-but-hashed blocks, LRU order (oldest first), KV still valid
        self._cached: OrderedDict[int, None] = OrderedDict()
        self._seqs: dict[str, SeqAlloc] = {}
        self._refcount: dict[int, int] = {}
        self._prefix: dict[int, int] = {}       # chain-hash -> physical block
        self._block_hash: dict[int, int] = {}   # physical block -> chain-hash
        self.prefix_hits = 0
        self.prefix_queries = 0
        # Tiered KV cache (runtime/kv_tiers.py): with recording on, an
        # eviction that kills a live prefix entry is LOGGED instead of
        # silently forgotten — the engine drains the log before its next
        # dispatch and demotes the block's still-intact device pages to
        # the host tier.  Off by default: without a tier store the log
        # would only grow.
        self.record_evictions = False
        self._evicted: list[tuple[int, int]] = []   # (block, chain-hash)
        # restore-in-flight blocks (block -> chain-hash): popped from the
        # free pool, being filled by an async host->HBM copy; in NO other
        # pool until commit_restore parks them in the cached pool, so they
        # can neither be evicted nor double-charged mid-copy.
        self._restoring: dict[int, int] = {}

    # ---- capacity -------------------------------------------------------

    @property
    def num_free_blocks(self) -> int:
        """Blocks available for allocation (fresh + evictable cached)."""
        return len(self._free) + len(self._cached)

    def blocks_needed(self, num_tokens: int) -> int:
        return cdiv(num_tokens, self.block_size)

    def can_allocate(self, num_tokens: int) -> bool:
        return self.blocks_needed(num_tokens) <= self.num_free_blocks

    def _pop_free_block(self) -> int:
        if self._free:
            return self._free.pop()
        # evict the LRU cached block: its prefix entry dies with it — or,
        # with eviction recording on, is demoted to a lower tier by the
        # engine (which drains the log before the dispatch that would
        # overwrite the block's device pages)
        block, _ = self._cached.popitem(last=False)
        if self.record_evictions:
            h = self._block_hash.get(block)
            if h is not None and self._prefix.get(h) == block:
                self._evicted.append((block, h))
        self._drop_hash(block)
        return block

    def take_evictions(self) -> list[tuple[int, int]]:
        """Drain the (block, chain-hash) eviction log.  The blocks' device
        pages are still intact — nothing writes KV outside a dispatch, and
        the engine drains this before dispatching — so they can be copied
        host-side and the hash stays resolvable in a lower tier."""
        ev, self._evicted = self._evicted, []
        return ev

    def _drop_hash(self, block: int) -> None:
        h = self._block_hash.pop(block, None)
        if h is not None and self._prefix.get(h) == block:
            del self._prefix[h]

    # ---- prefix cache ---------------------------------------------------

    @staticmethod
    def _chain_hash(prev_hash: int, tokens: tuple[int, ...]) -> int:
        return hash((prev_hash, tokens))

    def lookup_prefix(self, token_ids: list[int],
                      count_stats: bool = True) -> tuple[list[int], int]:
        """Longest cached prefix: returns (physical blocks, num cached tokens).

        Only whole blocks are reusable, and at least one token must remain
        un-cached so prefill has something to compute.
        ``count_stats=False`` for routing peeks (the scheduler probes the
        cache to pick a prefill path; only the engine's real lookup should
        move the hit-rate metrics).
        """
        if not self.enable_prefix_caching:
            return [], 0
        if count_stats:
            self.prefix_queries += 1
        blocks: list[int] = []
        h = 0
        max_full = (len(token_ids) - 1) // self.block_size
        for i in range(max_full):
            chunk = tuple(token_ids[i * self.block_size:(i + 1) * self.block_size])
            h = self._chain_hash(h, chunk)
            phys = self._prefix.get(h)
            if phys is None:
                break
            blocks.append(phys)
        if blocks and count_stats:
            self.prefix_hits += 1
        return blocks, len(blocks) * self.block_size

    def prefix_chain(self, token_ids: list[int]) -> list[int]:
        """Chain hashes of EVERY full prompt block (same at-least-one-
        token-uncached bound as lookup_prefix), regardless of residency —
        the keys the tier store files demoted blocks under, so the engine
        can probe lower tiers past the HBM hit.  Hash values are impl-
        internal (Python hash() here, FNV-1a in native/): tier keys must
        come from the same manager that will restore against them."""
        if not self.enable_prefix_caching:
            return []
        hashes: list[int] = []
        h = 0
        for i in range((len(token_ids) - 1) // self.block_size):
            chunk = tuple(token_ids[i * self.block_size:
                                    (i + 1) * self.block_size])
            h = self._chain_hash(h, chunk)
            hashes.append(h)
        return hashes

    def prefix_resolvable(self, h: int) -> bool:
        """Whether a chain hash currently resolves in HBM.  The engine's
        demote drain filters on this: a block evicted early in a cycle
        whose hash was RE-registered by a later allocation in the same
        cycle (two requests sharing the prefix in one batch) must not be
        demoted — HBM already holds the canonical copy, and a store copy
        would violate exactly-one-tier."""
        return h in self._prefix

    # ---- tier restore (host/PVC -> HBM) ---------------------------------

    def begin_restore(self, hashes: list[int]) -> Optional[list[int]]:
        """Claim one free block per hash for an in-flight host->HBM
        restore.  The blocks leave every pool (not free, not cached, not
        owned by a sequence) until ``commit_restore``, so concurrent
        allocation can neither evict nor double-charge them mid-copy.
        Returns None without mutating when the pool can't cover it."""
        if len(hashes) > self.num_free_blocks:
            return None
        blocks = [self._pop_free_block() for _ in hashes]
        for b, h in zip(blocks, hashes):
            self._restoring[b] = h
        return blocks

    def commit_restore(self, hashes: list[int], blocks: list[int]) -> int:
        """Publish restored blocks: each becomes a cached-pool prefix
        entry (MRU), exactly as if its original sequence had just freed
        it — the next lookup_prefix resolves the hash in HBM again.  A
        hash re-registered meanwhile (an identical prompt recomputed it)
        returns its redundant block to the free list.  Returns the number
        of prefix entries published."""
        published = 0
        for h, b in zip(hashes, blocks):
            self._restoring.pop(b, None)
            if h in self._prefix or b in self._block_hash:
                self._free.append(b)    # raced with a fresh registration
                continue
            self._prefix[h] = b
            self._block_hash[b] = h
            self._cached[b] = None
            self._cached.move_to_end(b)
            published += 1
        return published

    def abort_restore(self, blocks: list[int]) -> None:
        """Return claimed restore blocks to the free pool (copy failed or
        the tier entry vanished); their pages were never published."""
        for b in blocks:
            self._restoring.pop(b, None)
            self._free.append(b)

    @property
    def num_restoring_blocks(self) -> int:
        return len(self._restoring)

    @property
    def num_cached_blocks(self) -> int:
        """Freed-but-hashed blocks currently parked in the HBM cached
        pool (the tier-0 occupancy the kv-tier gauges report)."""
        return len(self._cached)

    def _register_prefix_blocks(self, seq_id: str, token_ids: list[int]) -> None:
        """Hash and publish this sequence's fully-written prompt blocks."""
        if not self.enable_prefix_caching:
            return
        alloc = self._seqs[seq_id]
        h = 0
        for i in range(len(token_ids) // self.block_size):
            chunk = tuple(token_ids[i * self.block_size:(i + 1) * self.block_size])
            h = self._chain_hash(h, chunk)
            phys = alloc.blocks[i]
            if h not in self._prefix and phys not in self._block_hash:
                self._prefix[h] = phys
                self._block_hash[phys] = h

    # ---- allocation -----------------------------------------------------

    def allocate(self, seq_id: str, prompt_token_ids: list[int],
                 shared_blocks: Optional[list[int]] = None) -> SeqAlloc:
        """Allocate blocks for a prompt; ``shared_blocks`` are prefix-cache
        hits (revived / ref-counted, never copied).

        Sharing dedups KV memory across identical prefixes.  The batched
        prefill path still rewrites identical KV into shared blocks (one
        shared padded shape, no per-request skip); the chunked path starts
        at the cached offset and skips the recompute entirely
        (engine._run_prefill_chunk)."""
        assert seq_id not in self._seqs, f"{seq_id} already allocated"
        shared_blocks = shared_blocks or []
        need = self.blocks_needed(len(prompt_token_ids)) - len(shared_blocks)
        # shared blocks sitting in the cached pool don't count as consumable
        free_after_revive = (self.num_free_blocks
                             - sum(1 for b in shared_blocks if b in self._cached))
        if need > free_after_revive:
            raise MemoryError(f"out of KV blocks (need {need}, free {free_after_revive})")
        for b in shared_blocks:
            if b in self._cached:           # revive: refcount was 0
                del self._cached[b]
                self._refcount[b] = 1
            else:
                self._refcount[b] = self._refcount.get(b, 0) + 1
        fresh = [self._pop_free_block() for _ in range(max(need, 0))]
        for b in fresh:
            self._refcount[b] = 1
        alloc = SeqAlloc(blocks=shared_blocks + fresh,
                         num_tokens=len(prompt_token_ids))
        self._seqs[seq_id] = alloc
        self._register_prefix_blocks(seq_id, prompt_token_ids)
        if self.seats is not None:
            self.seats.acquire(seq_id)
        return alloc

    def needs_new_block(self, seq_id: str) -> bool:
        """True when the next append_slot will have to grab a fresh block."""
        alloc = self._seqs[seq_id]
        return (alloc.num_tokens % self.block_size == 0
                and alloc.num_tokens // self.block_size == len(alloc.blocks))

    def can_append(self, seq_id: str) -> bool:
        return not self.needs_new_block(seq_id) or self.num_free_blocks >= 1

    def append_slot(self, seq_id: str) -> int:
        """Reserve the next token slot; returns the flat slot id
        (block * block_size + offset).  Grows the block table as needed."""
        alloc = self._seqs[seq_id]
        offset = alloc.num_tokens % self.block_size
        if self.needs_new_block(seq_id):
            if self.num_free_blocks == 0:
                raise MemoryError("out of KV blocks on append")
            b = self._pop_free_block()
            self._refcount[b] = 1
            alloc.blocks.append(b)
        block = alloc.blocks[alloc.num_tokens // self.block_size]
        alloc.num_tokens += 1
        return block * self.block_size + offset

    def reserve(self, seq_id: str, total_tokens: int) -> None:
        """Grow the block table to hold ``total_tokens`` slots WITHOUT
        advancing the written-token counter (speculative decoding writes a
        draft window first and only commits the accepted length)."""
        alloc = self._seqs[seq_id]
        need = self.blocks_needed(total_tokens) - len(alloc.blocks)
        if need > self.num_free_blocks:
            raise MemoryError("out of KV blocks on reserve")
        for _ in range(need):
            b = self._pop_free_block()
            self._refcount[b] = 1
            alloc.blocks.append(b)

    def advance(self, seq_id: str, n: int) -> None:
        """Commit ``n`` written tokens (slots must already be reserved)."""
        alloc = self._seqs[seq_id]
        if alloc.num_tokens + n > len(alloc.blocks) * self.block_size:
            raise ValueError("advance beyond reserved capacity")
        alloc.num_tokens += n

    def slot_for_token(self, seq_id: str, token_idx: int) -> int:
        alloc = self._seqs[seq_id]
        if token_idx < 0:
            raise IndexError("token index out of range")
        b = alloc.blocks[token_idx // self.block_size]
        if b == RELEASED:
            raise IndexError(
                f"token {token_idx} of {seq_id} is in a window-released "
                "block — writes must stay at or after the window start")
        return b * self.block_size + token_idx % self.block_size

    def block_table(self, seq_id: str) -> list[int]:
        """Physical block ids by logical index.  Window-released entries
        are reported as block 0: the attention kernels never DMA (Pallas)
        or un-mask (reference) positions before the window, so any valid
        id is safe — and a valid id keeps gathers in bounds."""
        return [0 if b == RELEASED else b
                for b in self._seqs[seq_id].blocks]

    def _release_block(self, b: int, cache_blocks: bool = True) -> None:
        rc = self._refcount.get(b, 1) - 1
        if rc > 0:
            self._refcount[b] = rc
            return
        self._refcount.pop(b, None)
        if not cache_blocks:
            self._drop_hash(b)
        if b in self._block_hash:       # keep KV around for prefix reuse
            self._cached[b] = None
            self._cached.move_to_end(b)
        else:
            self._free.append(b)

    def release_out_of_window(self, seq_id: str,
                              first_needed_token: int) -> int:
        """Sliding-window rolling buffer: return the blocks holding only
        positions before ``first_needed_token`` to the pool (the window
        will never attend them again), keeping the logical table length so
        tail slot arithmetic is unchanged.  Cache capacity for a windowed
        model thus scales with the WINDOW, not the context.  Returns the
        number of blocks released."""
        alloc = self._seqs[seq_id]
        # never release the newest written position's block (or beyond):
        # the next append / spec-verify rewrite targets it, and a write
        # into a released block would corrupt whoever owns it now
        first_needed_token = min(first_needed_token,
                                 max(alloc.num_tokens - 1, 0))
        first_block = min(first_needed_token // self.block_size,
                          len(alloc.blocks))
        released = 0
        for i in range(alloc.released_upto, first_block):
            b = alloc.blocks[i]
            if b != RELEASED:
                self._release_block(b)
                alloc.blocks[i] = RELEASED
                released += 1
        alloc.released_upto = max(alloc.released_upto, first_block)
        return released

    def free(self, seq_id: str, cache_blocks: bool = True) -> None:
        """Release a sequence's blocks.  ``cache_blocks=False`` drops their
        prefix-cache hashes instead of parking them in the cached pool — for
        sequences whose KV was never fully written (e.g. a chunked prefill
        aborted mid-prompt), whose blocks would otherwise be served as
        cached prefixes full of garbage."""
        alloc = self._seqs.pop(seq_id, None)
        if alloc is None:
            return
        if self.seats is not None:
            self.seats.release(seq_id)
        for b in alloc.blocks:
            if b == RELEASED:               # already back in the pool
                continue
            self._release_block(b, cache_blocks)

    def num_seqs(self) -> int:
        return len(self._seqs)

    def seq_ids(self) -> set:
        return set(self._seqs)

    # ---- per-cycle batched ops ------------------------------------------
    # One call per engine cycle instead of 2-3 per request — the Python
    # reference for the native batched boundary (block_manager.hh carries
    # the C++ twins; tests/test_native.py drives both with identical op
    # traces).  The engine calls ONLY these on its decode hot path, so
    # impl="python" and impl="native" share one code shape.

    def decode_shortfall(self, seq_ids) -> int:
        """Non-mutating capacity probe: blocks missing for one decode
        append across these rows (0 = charge_decode will succeed); the
        engine preempts while this is positive."""
        need = sum(self.needs_new_block(s) for s in seq_ids)
        return max(need - self.num_free_blocks, 0)

    def charge_decode(self, seq_ids, slots_out) -> int:
        """Charge one decode append per sequence: either every row fits
        (slots written into ``slots_out[i]``, returns 0) or NOTHING is
        mutated and the block shortfall is returned — the engine preempts
        and retries."""
        need = sum(self.needs_new_block(s) for s in seq_ids)
        short = need - self.num_free_blocks
        if short > 0:
            return short
        for i, s in enumerate(seq_ids):
            slots_out[i] = self.append_slot(s)
        return 0

    def fill_block_tables(self, seq_ids, out) -> int:
        """Write each sequence's block table into row i of ``out`` (a
        zeroed (n, max_blocks_per_seq) int32 array); returns the longest
        table written."""
        longest = 0
        for i, s in enumerate(seq_ids):
            bt = self.block_table(s)
            out[i, :len(bt)] = bt
            if len(bt) > longest:
                longest = len(bt)
        return longest

    def reserve_batch(self, seq_ids, totals) -> bool:
        """Reserve each sequence up to ``totals[i]`` slots; False on OOM
        with earlier reservations KEPT (Engine._try_reserve_window
        semantics: over-reserved blocks stay attached and get used as the
        sequence grows)."""
        try:
            for s, t in zip(seq_ids, totals):
                self.reserve(s, t)
        except MemoryError:
            return False
        return True

    def advance_batch(self, seq_ids, steps: int) -> None:
        for s in seq_ids:
            self.advance(s, steps)

    def admit_prefill(self, counts, max_seats: int,
                      max_prefill_tokens: int,
                      min_bucket: int) -> tuple[int, int]:
        """Scheduler admission arithmetic over the waiting queue's head
        segment (prompt token counts): greedy pick sharing one power-of-2
        length bucket, charging bucket*(picked+1) against the token
        budget and blocks_needed+1 decode headroom against the free pool.
        Returns (picked, bucket)."""
        picked = bucket = reserved = 0
        free = self.num_free_blocks
        for c in counts:
            if picked >= max_seats:
                break
            cand = max(bucket, max(next_power_of_2(c), min_bucket))
            if cand * (picked + 1) > max_prefill_tokens and picked:
                break
            need = self.blocks_needed(c) + 1
            if reserved + need > free:
                break
            picked += 1
            reserved += need
            bucket = cand
        return picked, bucket

    def check_integrity(self, expected_seq_ids=None,
                        tier_hashes=None) -> None:
        """Debug strict mode (``TPUSERVE_STRICT_BLOCKS``): verify the
        block accounting invariants the engine relies on, raising
        RuntimeError with every violation found.  The runtime complement
        to tpulint's static kv-leak pass: the lint proves allocate/free
        pairing on exception edges at review time; this catches the
        dynamic leaks (double-free, refcount drift, orphaned sequences)
        each engine cycle while chaos tests are running.

        ``expected_seq_ids``: when given, the exact set of sequence ids
        that should currently hold allocations (the engine passes its
        live running + mid-chunk requests) — a sequence holding blocks
        with no live request is a leak; a live request without blocks is
        corruption.

        ``tier_hashes``: when given (the engine passes its tier store's
        resolvable hashes), the exactly-one-tier invariant is checked at
        the hash level too: a chain hash resolvable in HBM must not also
        be resolvable in a lower tier, and a restore-in-flight hash must
        already have LEFT the tier store (``take`` removed it).
        """
        problems: list[str] = []
        owned: dict[int, int] = {}
        for sid, alloc in self._seqs.items():
            for b in alloc.blocks:
                if b != RELEASED:
                    owned[b] = owned.get(b, 0) + 1
        free_set = set(self._free)
        cached_set = set(self._cached)
        if len(free_set) != len(self._free):
            problems.append("duplicate block ids in the free list")
        if free_set & cached_set:
            problems.append(
                f"blocks in BOTH free and cached: {sorted(free_set & cached_set)}")
        for b, n in sorted(owned.items()):
            rc = self._refcount.get(b, 0)
            if rc != n:
                problems.append(
                    f"block {b}: refcount {rc} != {n} owning sequence(s)")
            if b in free_set:
                problems.append(
                    f"block {b} owned by a live sequence AND free")
            if b in cached_set:
                problems.append(
                    f"block {b} owned by a live sequence AND cached")
        for b, rc in sorted(self._refcount.items()):
            if b not in owned:
                problems.append(
                    f"block {b} has refcount {rc} but no owning sequence")
        restoring_set = set(self._restoring)
        for b in sorted(restoring_set):
            # restore-in-flight blocks live in NO pool until commit: any
            # overlap means the async host->HBM copy races an eviction or
            # a sequence write into the same device page
            if b in free_set:
                problems.append(f"restore-in-flight block {b} also free")
            if b in cached_set:
                problems.append(f"restore-in-flight block {b} also cached")
            if b in owned:
                problems.append(
                    f"restore-in-flight block {b} also owned by a live "
                    "sequence (double-charged)")
            if b in self._refcount:
                problems.append(
                    f"restore-in-flight block {b} carries a refcount")
        accounted = free_set | cached_set | set(owned) | restoring_set
        if len(accounted) != self.num_blocks:
            lost = self.num_blocks - len(accounted)
            problems.append(
                f"{lost} block(s) leaked: in neither the free list, the "
                "cached pool, the restore-in-flight set, nor any sequence "
                "table")
        for h, b in self._prefix.items():
            if self._block_hash.get(b) != h:
                problems.append(
                    f"prefix hash {h} maps to block {b} but the reverse "
                    "mapping disagrees")
        if tier_hashes is not None:
            tiered = set(tier_hashes)
            both = tiered & set(self._prefix)
            if both:
                problems.append(
                    f"{len(both)} chain hash(es) resolvable in BOTH HBM "
                    f"and a lower tier (exactly-one-tier violated): "
                    f"{sorted(both)[:4]}")
            stuck = tiered & set(self._restoring.values())
            if stuck:
                problems.append(
                    f"restore-in-flight hash(es) still resolvable in a "
                    f"lower tier: {sorted(stuck)[:4]}")
        if expected_seq_ids is not None:
            extra = set(self._seqs) - set(expected_seq_ids)
            missing = set(expected_seq_ids) - set(self._seqs)
            if extra:
                problems.append(
                    "sequences holding blocks with no live request "
                    f"(leak): {sorted(extra)}")
            if missing:
                problems.append(
                    "live requests without block allocations "
                    f"(corruption): {sorted(missing)}")
        if problems:
            raise RuntimeError(
                "KV block integrity violated (TPUSERVE_STRICT_BLOCKS): "
                + "; ".join(problems))


def create_block_manager(num_blocks: int, block_size: int,
                         enable_prefix_caching: bool = True,
                         impl: str = "auto"):
    """Factory selecting the C++ block manager (tpuserve.native) when the
    shared library is available, else this module's pure-Python one.

    impl: "auto" | "native" | "python".  TPUSERVE_BLOCK_MANAGER overrides.

    ``TPUSERVE_STRICT_BLOCKS`` (the debug refcount cross-check) steers
    "auto" to the Python manager — the C++ one exposes no sequence-table
    introspection, so the per-cycle ``check_integrity`` would silently
    no-op.  An explicit impl="native" request still wins (and runs
    unchecked).
    """
    import os
    impl = os.environ.get("TPUSERVE_BLOCK_MANAGER", impl)
    if impl == "auto" and os.environ.get("TPUSERVE_STRICT_BLOCKS"):
        impl = "python"
    if impl in ("auto", "native"):
        from tpuserve.native import NativeBlockManager, native_available
        if native_available():
            return NativeBlockManager(
                num_blocks, block_size,
                enable_prefix_caching=enable_prefix_caching)
        if impl == "native":
            raise RuntimeError("native block manager requested but "
                               "library unavailable")
        import jax
        if jax.default_backend() == "tpu":
            # serving on the chip without the C++ host path is a broken
            # deployment (no toolchain in the image, or the build failed),
            # not a choice: say so where an operator will see it
            logger.error("native block manager unavailable on a TPU host; "
                         "serving on the pure-Python manager")
    return BlockManager(num_blocks, block_size,
                        enable_prefix_caching=enable_prefix_caching)
