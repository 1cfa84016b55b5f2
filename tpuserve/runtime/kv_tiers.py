"""Host-DRAM and PVC spill tiers under the HBM prefix cache.

The HBM prefix cache (runtime/block_manager.py) keeps freed-but-hashed
blocks until fresh blocks run out; a cold ``_pop_free_block`` then evicts
the LRU cached block and its prefix entry dies — every later request with
that prefix pays full prefill.  At fleet scale (millions of conversations
sharing system prompts and chat history) the working set of reusable KV is
far larger than HBM, and re-prefill dominates TTFT at realistic reuse
rates ("Cost-Efficient LLM Serving in the Cloud: VM Selection with KV
Cache Offloading", arxiv 2504.11816 — PAPERS.md).

This module is the demotion target: a chain-hash-keyed store of KV block
pages with two tiers under HBM —

- tier ``host``: pinned-host numpy pages under a byte budget (a gather
  of the evicted blocks enqueued BEFORE the dispatch that overwrites
  their device pages; int8 KV pages stay half-size because the dtype
  rides through the copy).  The device-to-host copy of that gather runs
  while the chip works: a demotion is IN FLIGHT from ``put_async`` until
  the engine loop ``land``s it, and counts as tier ``host`` from the
  first moment (``has``/``where``/``hashes``/``len``/``take``/``drop``
  all see it), so a lookup hits exactly as if the copy had blocked;
- tier ``spill``: ``.npz`` files on a directory (the model PVC in-cluster
  — provision/manifests.py mounts it), absorbing host-budget overflow.
  Spill WRITES run on a background thread (the engine loop must never
  block on PVC latency between scheduling and a dispatch); entries are
  resolvable from memory the moment they enter the write queue.  On
  init the directory is rescanned, so spill files survive pod restarts
  — restart reuse needs process-stable chain hashes, which the native
  manager's FNV-1a provides (Python's salted ``hash()`` does not; under
  the pure-Python manager pre-restart files are cap-bounded dead weight
  that ages out).

ADMISSION: a block's pages enter the tier only when its chain hash has
left HBM before (``admit``).  The first eviction of a hash is declined —
nothing is gathered or copied, the KV dies as it did before the tier
existed, and the store remembers the hash alone.  A hash can be evicted a
second time only if some request recomputed (or restored) it in between:
the proof that this prefix comes back at a distance HBM cannot bridge, so
from then on every eviction of it is demoted.  This is the cache-on-
second-request rule of CDN disk caches and the ghost list of 2Q/ARC:
traffic whose prompts never repeat costs the tier nothing, and a prefix
that does return is recomputed once more before the tier holds it.  The
memory of hashes seen is bounded, insertion-ordered and in NO tier.

A hash lives in EXACTLY ONE tier: HBM (the block manager's prefix map),
host, or spill — ``put`` demotes out of HBM, host-budget pressure moves
host entries to spill, and ``take`` (the restore path) removes the entry
as its pages are scattered back into HBM.  The ``TPUSERVE_STRICT_BLOCKS``
integrity checker cross-checks this invariant every engine cycle
(engine._check_block_integrity).

Writers: the engine loop (put/put_async/land/take/drop) and the spill-
writer thread (pending -> file transitions); shared maps are guarded by
one lock held only for dict surgery, never for file I/O.  The copier
thread runs the ``fetch`` it was handed (the wait for one batch's
device-to-host copy) and touches no map: what is in flight, and the
host tier it lands in, stay engine-loop only.
"""

from __future__ import annotations

import contextlib
import logging
import os
import queue
import threading
from collections import OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

logger = logging.getLogger("tpuserve.kv_tiers")

# Spill-tier entry cap: a backstop against unbounded PVC growth when the
# workload never reuses what it demotes (the PVC also holds the model
# weights and compile caches).  Oldest entries are dropped past it — at
# init-rescan time too, so crashed pods can't accumulate files forever.
DEFAULT_MAX_SPILL_ENTRIES = 1 << 16

# Hashes ``admit`` remembers as having left HBM (8-byte keys, no pages):
# the same order as the spill cap, minutes of evictions at hundreds a
# second.  Oldest forgotten first; a forgotten hash proves itself again.
MAX_SEEN_HASHES = 1 << 16

# Demotion batches whose device-to-host copy may be in flight at once:
# the one being gathered plus one older.  A gathered batch holds its
# device buffer until its copy is done, so this (with
# ``device_budget_bytes``) is what bounds the HBM the tier borrows; a
# third batch waits for the oldest.
MAX_IN_FLIGHT = 2


def pages_nbytes(pages: list[dict]) -> int:
    """Host bytes one block's per-layer page dict consumes."""
    return sum(int(a.nbytes) for layer in pages for a in layer.values())


def _row_pages(layers: list[dict], i: int) -> list[dict]:
    """Block ``i``'s pages (views) out of a gathered batch's arrays."""
    return [{k: v[i] for k, v in layer.items()} for layer in layers]


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name incl. the ml_dtypes extension types (bfloat16
    KV pages round-trip the spill tier as raw bytes + this name)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _encode_npz(a: np.ndarray) -> tuple[np.ndarray, str]:
    """(savable array, dtype tag): np.savez silently stores extension
    dtypes (bfloat16) as opaque void records that np.load cannot hand
    back to jax — view them as bytes and carry the dtype in the key."""
    if a.dtype.isbuiltin == 1:
        return a, ""
    return np.ascontiguousarray(a).view(np.uint8), str(a.dtype)


class _InFlight:
    """One demotion batch between ``put_async`` and its landing."""

    __slots__ = ("rows", "future", "nbytes")

    def __init__(self, rows: dict[int, int], future: Future, nbytes: int):
        self.rows = rows          # live hash -> its row of the batch
        self.future = future      # -> per-layer {key: (rows, ...) ndarray}
        self.nbytes = nbytes      # device bytes the gathered batch holds


class TieredPageStore:
    """Chain-hash-keyed KV block pages in host DRAM with PVC overflow.

    ``pages`` values are ``list[dict[str, np.ndarray]]`` — one dict per
    model layer, same keys as the device cache entries ("k"/"v" plus
    "ks"/"vs" scales when quantized), each array one block's
    ``(block_size, kv_heads, head_dim)`` page.
    """

    def __init__(self, host_bytes: int, spill_dir: str | None = None,
                 max_spill_entries: int = DEFAULT_MAX_SPILL_ENTRIES,
                 sync=contextlib.nullcontext,
                 device_budget_bytes: float = float("inf")):
        self.host_budget_bytes = int(host_bytes)
        # demotions in flight, oldest first (engine-loop only).  ``sync``
        # opens the span around a wait for a copy (the engine passes
        # devprof's ``sync.demote``); ``device_budget_bytes`` is the
        # device memory the gathered batches may hold while a dispatch
        # runs (the engine: what no warmed dispatch has ever touched).
        self._in_flight: deque[_InFlight] = deque()
        self._copier: ThreadPoolExecutor | None = None
        self._sync = sync
        self.device_budget_bytes = device_budget_bytes
        self.spill_dir = spill_dir
        self.max_spill_entries = max_spill_entries
        # hash -> (pages, nbytes); LRU order, oldest first.  Engine-loop
        # only — no lock needed for the host tier.
        self._host: OrderedDict[int, tuple[list, int]] = OrderedDict()
        # spill tier, split by write progress; BOTH under _lock:
        #   _spill_pending: hash -> pages, queued for the writer thread
        #   _spill:         hash -> path, durably on disk
        self._spill_pending: OrderedDict[int, list] = OrderedDict()
        self._spill: OrderedDict[int, str] = OrderedDict()
        self._lock = threading.Lock()
        # hashes that have left HBM before, oldest first (engine-loop
        # only).  Not a tier: no lookup below ever reads it.
        self._seen: OrderedDict[int, None] = OrderedDict()
        self._writeq: "queue.Queue[int | None]" = queue.Queue()
        self._writer: threading.Thread | None = None
        self.host_bytes_used = 0
        # cumulative flow counters (the engine mirrors these into
        # EngineStats so server/runner.py can export them)
        self.spilled_blocks = 0     # host -> PVC demotions (at enqueue)
        self.dropped_blocks = 0     # fell off the last tier (KV lost)
        self.waited_blocks = 0      # demoted blocks whose copy was waited for
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
            self._rescan_spill_dir()

    # ---- introspection --------------------------------------------------

    @property
    def host_count(self) -> int:
        return len(self._host)

    @property
    def spill_count(self) -> int:
        with self._lock:
            return len(self._spill) + len(self._spill_pending)

    @property
    def in_flight_batches(self) -> int:
        return len(self._in_flight)

    @property
    def in_flight_count(self) -> int:
        """Blocks demoted whose copy has not landed in the host tier."""
        return sum(len(b.rows) for b in self._in_flight)

    def __len__(self) -> int:
        return len(self._host) + self.spill_count + self.in_flight_count

    def _batch_of(self, h: int) -> _InFlight | None:
        for batch in self._in_flight:
            if h in batch.rows:
                return batch
        return None

    def has(self, h: int) -> bool:
        if h in self._host or self._batch_of(h) is not None:
            return True
        with self._lock:
            return h in self._spill or h in self._spill_pending

    def where(self, h: int) -> str | None:
        if h in self._host or self._batch_of(h) is not None:
            return "host"
        with self._lock:
            if h in self._spill or h in self._spill_pending:
                return "spill"
        return None

    def hashes(self):
        """Every resolvable hash across both tiers (host first, what is
        in flight to it included)."""
        yield from list(self._host)
        for batch in list(self._in_flight):
            yield from list(batch.rows)
        with self._lock:
            snap = list(self._spill_pending) + list(self._spill)
        yield from snap

    # ---- spill writer ---------------------------------------------------

    def _spill_path(self, h: int) -> str:
        # mask to the uint64 domain so Python's signed hash() and the
        # native FNV both name files injectively
        return os.path.join(self.spill_dir,
                            f"kvt_{h & 0xFFFFFFFFFFFFFFFF:016x}.npz")

    def _rescan_spill_dir(self) -> None:
        """Adopt pre-existing spill files (pod restart / crashed sibling):
        keyed back from the filename, oldest-first so cap trimming drops
        the stalest.  A filename with the top bit set is ambiguous between
        a native uint64 hash and a negative Python hash — both candidate
        keys map to the file; the alias that never matches is harmlessly
        shed as a read miss if it is ever probed."""
        try:
            ents = []
            for name in os.listdir(self.spill_dir):
                if not (name.startswith("kvt_") and name.endswith(".npz")):
                    continue
                path = os.path.join(self.spill_dir, name)
                try:
                    ents.append((os.path.getmtime(path), name, path))
                except OSError:
                    continue
            ents.sort()
            for _, _, path in ents[:-self.max_spill_entries or None]:
                self._drop_spill_file(path)
            for _, name, path in ents[-self.max_spill_entries:]:
                try:
                    v = int(name[4:20], 16)
                except ValueError:
                    continue
                self._spill[v] = path
                if v >= 1 << 63:
                    self._spill[v - (1 << 64)] = path
            if self._spill:
                logger.info("adopted %d spill-tier entr(ies) from %s",
                            len(self._spill), self.spill_dir)
        except OSError:
            pass

    def _ensure_writer(self) -> None:
        if self._writer is None or not self._writer.is_alive():
            self._writer = threading.Thread(target=self._writer_loop,
                                            daemon=True,
                                            name="tpuserve-kv-spill")
            self._writer.start()

    def _writer_loop(self) -> None:
        while True:
            h = self._writeq.get()
            try:
                if h is None:
                    return
                with self._lock:
                    pages = self._spill_pending.get(h)
                if pages is None:
                    continue             # taken/dropped before the write
                ok = self._write_spill_file(h, pages)
                victims: list[str] = []
                with self._lock:
                    if self._spill_pending.pop(h, None) is None:
                        # taken/dropped DURING the write: orphaned file
                        if ok:
                            victims.append(self._spill_path(h))
                    elif ok:
                        self._spill[h] = self._spill_path(h)
                        while len(self._spill) > self.max_spill_entries:
                            _, p = self._spill.popitem(last=False)
                            victims.append(p)
                            self.dropped_blocks += 1
                    else:
                        self.dropped_blocks += 1
                for p in victims:
                    self._drop_spill_file(p)
            finally:
                self._writeq.task_done()

    def _write_spill_file(self, h: int, pages: list[dict]) -> bool:
        path = self._spill_path(h)
        try:
            flat = {}
            for li, layer in enumerate(pages):
                for k, a in layer.items():
                    enc, tag = _encode_npz(np.asarray(a))
                    flat[f"{li}.{k}@{tag}" if tag else f"{li}.{k}"] = enc
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                np.savez(f, **flat)
            os.replace(tmp, path)       # atomic publish, like the FSM cache
            return True
        except OSError as e:
            logger.warning("KV spill write failed (%s); dropping block", e)
            return False

    def _spill_one(self, h: int, pages: list[dict]) -> bool:
        """Move one block's pages to the spill tier — resolvable from the
        pending map immediately; the file write happens on the writer
        thread so the engine loop never blocks on PVC latency."""
        if not self.spill_dir:
            return False
        with self._lock:
            self._spill_pending[h] = pages
        self.spilled_blocks += 1
        self._ensure_writer()
        self._writeq.put(h)
        return True

    def _drop_spill_file(self, path: str) -> None:
        try:
            os.remove(path)
        except OSError:
            pass

    def flush(self) -> None:
        """Block until every demotion in flight is filed and queued spill
        writes have landed (tests/shutdown)."""
        self.land(wait=True)
        self._writeq.join()

    # ---- demote ---------------------------------------------------------

    def admit(self, h: int) -> bool:
        """Whether the block evicted under hash ``h`` is worth its copy:
        True when ``h`` has left HBM before (an earlier eviction asked
        here, or a restore took it out of this store), so some request
        brought the prefix back after it went cold.  A first eviction is
        declined and only remembered."""
        if h in self._seen:
            return True
        self._remember(h)
        return False

    def _remember(self, h: int) -> None:
        self._seen[h] = None
        if len(self._seen) > MAX_SEEN_HASHES:
            self._seen.popitem(last=False)

    def reserve(self, nbytes: int) -> None:
        """Make room for a gather of ``nbytes`` device bytes BEFORE it is
        enqueued: wait for the oldest batches until fewer than
        ``MAX_IN_FLIGHT`` remain and theirs plus the new one's bytes fit
        ``device_budget_bytes``.  Nothing else is filed here: the caller
        is about to dispatch, and the chip may be waiting for it."""
        while self._in_flight and (
                len(self._in_flight) >= MAX_IN_FLIGHT
                or nbytes + sum(b.nbytes for b in self._in_flight)
                > self.device_budget_bytes):
            self._land_oldest(forced=True)

    def put_async(self, hashes: list[int], fetch, nbytes: int) -> None:
        """Demote a batch whose gather has been enqueued: ``fetch()``,
        run on the copier thread, copies it to the host and returns it as
        per-layer ``{key: ndarray}`` with row ``i`` the pages of
        ``hashes[i]`` (more rows are padding).  The hashes are resolvable
        from now; ``land`` files them (``put``, budget cascade and all).
        A batch the device budget cannot hold through a dispatch is
        waited for here, so the caller's next dispatch finds it gone."""
        rows = {h: i for i, h in enumerate(hashes) if not self.has(h)}
        if self._copier is None:
            self._copier = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpuserve-kv-demote")
        self._in_flight.append(
            _InFlight(rows, self._copier.submit(fetch), nbytes))
        if nbytes > self.device_budget_bytes:
            while self._in_flight:
                self._land_oldest(forced=True)

    def land(self, wait: bool = False) -> None:
        """File the batches whose copy is done, oldest first (engine loop;
        the engine calls this where the chip has work queued).  With
        ``wait`` every batch in flight, waiting for its copy: the loop
        has gone idle or is stopping, so nothing waits behind it and the
        blocks do not count as waited for."""
        while self._in_flight and (wait or self._in_flight[0].future.done()):
            self._land_oldest(forced=False)

    def _fetched(self, batch: _InFlight, waited: int) -> list[dict] | None:
        """The batch's host arrays, waiting for the copy under the sync
        span if it is still running (and counting ``waited`` blocks as
        waited for); None, and the batch's blocks counted as dropped,
        if the copy failed."""
        try:
            if batch.future.done():
                return batch.future.result()
            self.waited_blocks += waited
            with self._sync():
                return batch.future.result()
        except Exception:       # whatever the copy raised: the KV is lost
            logger.exception("KV demotion copy failed; dropping %d block(s)",
                             len(batch.rows))
            self.dropped_blocks += len(batch.rows)
            batch.rows.clear()
            return None

    def _land_oldest(self, forced: bool) -> None:
        """File the oldest batch; ``forced`` says the loop needs its room
        now (the in-flight bound), so a wait for it counts."""
        batch = self._in_flight[0]
        layers = self._fetched(batch, len(batch.rows) if forced else 0)
        self._in_flight.popleft()        # before put: has() must miss them
        for h, i in batch.rows.items():
            self.put(h, _row_pages(layers, i))

    def put(self, h: int, pages: list[dict]) -> None:
        """Demote one evicted HBM block's pages under hash ``h``.  Host-
        budget overflow cascades the LRU host entry to the spill tier (or
        drops it when no spill dir is configured)."""
        if self.has(h):                 # already demoted (shouldn't happen:
            return                      # HBM held the hash until now)
        nbytes = pages_nbytes(pages)
        if nbytes > self.host_budget_bytes:
            # a single block bigger than the whole host budget goes
            # straight to spill (degenerate config, but stay correct)
            if not self._spill_one(h, pages):
                self.dropped_blocks += 1
            return
        self._host[h] = (pages, nbytes)
        self.host_bytes_used += nbytes
        while self.host_bytes_used > self.host_budget_bytes and self._host:
            old, (old_pages, old_n) = self._host.popitem(last=False)
            self.host_bytes_used -= old_n
            if not self._spill_one(old, old_pages):
                self.dropped_blocks += 1

    # ---- restore --------------------------------------------------------

    def take(self, h: int) -> list | None:
        """Remove and return the pages for ``h`` (restore path: the hash
        is about to become resolvable in HBM again, and a block must live
        in exactly one tier).  None when unresolvable or the spill file is
        unreadable (the caller falls back to recompute; the loss is
        counted — that KV is gone).  A hash in flight waits for that one
        batch's copy.  What was taken has left HBM before, whoever put it
        here (a spill file adopted at start too): ``admit`` will say so."""
        pages = self._take(h)
        if pages is not None:
            self._remember(h)
        return pages

    def _take(self, h: int) -> list | None:
        ent = self._host.pop(h, None)
        if ent is not None:
            self.host_bytes_used -= ent[1]
            return ent[0]
        batch = self._batch_of(h)
        if batch is not None:
            layers = self._fetched(batch, 1)
            if layers is None:
                return None
            return _row_pages(layers, batch.rows.pop(h))
        with self._lock:
            pending = self._spill_pending.pop(h, None)
            if pending is not None:
                return pending          # writer skips / cleans the file
            path = self._spill.pop(h, None)
        if path is None:
            return None
        try:
            with np.load(path) as z:
                flat = {k: z[k] for k in z.files}
        except (OSError, ValueError) as e:
            logger.warning("KV spill read failed for %s (%s); treating as "
                           "a miss", path, e)
            self._drop_spill_file(path)
            self.dropped_blocks += 1    # the KV is LOST, not restored —
            return None                 # the tier-loss counter must say so
        self._drop_spill_file(path)
        n_layers = 1 + max(int(k.split(".", 1)[0]) for k in flat)
        pages: list[dict] = [{} for _ in range(n_layers)]
        for k, a in flat.items():
            li, key = k.split(".", 1)
            key, _, tag = key.partition("@")
            if tag:
                a = a.view(_np_dtype(tag))
            pages[int(li)][key] = a
        return pages

    def drop(self, h: int) -> None:
        ent = self._host.pop(h, None)
        if ent is not None:
            self.host_bytes_used -= ent[1]
            return
        batch = self._batch_of(h)
        if batch is not None:
            del batch.rows[h]           # the landing files nothing for it
            return
        with self._lock:
            if self._spill_pending.pop(h, None) is not None:
                return                  # writer cleans any half-born file
            path = self._spill.pop(h, None)
        if path is not None:
            self._drop_spill_file(path)

    def clear(self) -> None:
        for batch in self._in_flight:   # wiped like the rest, but counted:
            self.dropped_blocks += len(batch.rows)  # they reached no tier
            batch.future.cancel()
        self._in_flight.clear()
        with self._lock:
            self._spill_pending.clear()
            paths = list(self._spill.values())
            self._spill.clear()
        for path in paths:
            self._drop_spill_file(path)
        self._host.clear()
        self.host_bytes_used = 0
        self._seen.clear()
