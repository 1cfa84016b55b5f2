"""Bindings for the native (C++) runtime components.

``NativeBlockManager`` is an API-compatible drop-in for
``tpuserve.runtime.block_manager.BlockManager`` backed by
native/block_manager.hh.  The primary binding is a CPython extension
(_tpuserve_native, built from native/block_manager_ext.cc) — ctypes adds
microseconds per call, which swamps these micro-operations, so it is kept
only as a C ABI for non-Python hosts.  The extension is built on demand
with g++ (no pybind11 in the environment — plain C API); when the
toolchain is unavailable everything falls back to pure Python.
"""

from __future__ import annotations

import importlib
import logging
import os
import subprocess
import sys
import sysconfig
import threading

logger = logging.getLogger("tpuserve.native")

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(_PKG_DIR)),
                           "native")
_EXT_SRC = os.path.join(_NATIVE_DIR, "block_manager_ext.cc")
_HDR = os.path.join(_NATIVE_DIR, "block_manager.hh")
_lock = threading.Lock()
_ext = None
_ext_tried = False


def _ext_path() -> str:
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_PKG_DIR, f"_tpuserve_native{suffix}")


def build_from_source() -> str:
    """Compile the extension from native/*.cc, whatever is already on
    disk, and return its path.  Raises if the sources, the toolchain or
    the compile fail — for callers (chip_smoke.py) that must know the
    manager they run was built from the tracked sources."""
    out = _ext_path()
    include = sysconfig.get_paths()["include"]
    # Compile to a private temp path and os.replace() it into place: the
    # publish is atomic, so a concurrent process (pytest-xdist worker,
    # sibling replica on a shared volume) never dlopens a half-written .so.
    tmp = f"{out}.tmp{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-fPIC", "-shared",
             f"-I{include}", "-o", tmp, _EXT_SRC],
            check=True, capture_output=True, timeout=180)
        os.replace(tmp, out)
    except subprocess.CalledProcessError as e:
        raise RuntimeError("native build failed: "
                           + e.stderr.decode(errors="replace")[:500]) from e
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    logger.info("built %s", out)
    return out


def _build() -> bool:
    out = _ext_path()
    if not (os.path.isfile(_EXT_SRC) and os.path.isfile(_HDR)):
        return os.path.isfile(out)
    src_mtime = max(os.path.getmtime(_EXT_SRC), os.path.getmtime(_HDR))
    if os.path.isfile(out) and os.path.getmtime(out) >= src_mtime:
        return True
    try:
        build_from_source()
        return True
    except (OSError, RuntimeError, subprocess.SubprocessError) as e:
        logger.warning("%s; using pure Python", e)
        return False


def _load():
    global _ext, _ext_tried
    with _lock:
        if _ext_tried:
            return _ext
        _ext_tried = True
        if not _build():
            return None
        if _PKG_DIR not in sys.path:
            sys.path.insert(0, _PKG_DIR)
        try:
            _ext = importlib.import_module("_tpuserve_native")
        except ImportError as e:
            logger.warning("cannot import _tpuserve_native: %s", e)
            _ext = None
        return _ext


def native_available() -> bool:
    return _load() is not None


class NativeBlockManager:
    """Drop-in for runtime.block_manager.BlockManager (see that module for
    the semantics; native/block_manager.hh mirrors them)."""

    #: a runtime.block_manager.SeatPool when the model has recurrent
    #: state (set by the engine): taken and given back with the blocks
    seats = None

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = True):
        ext = _load()
        if ext is None:
            raise RuntimeError("native extension unavailable")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self._core = ext.BlockManagerCore(
            num_blocks, block_size,
            enable_prefix_caching=enable_prefix_caching)
        self._record_evictions = False

    # ---- capacity -------------------------------------------------------

    @property
    def num_free_blocks(self) -> int:
        return self._core.num_free_blocks()

    def blocks_needed(self, num_tokens: int) -> int:
        return self._core.blocks_needed(num_tokens)

    def can_allocate(self, num_tokens: int) -> bool:
        return self._core.can_allocate(num_tokens)

    @property
    def prefix_hits(self) -> int:
        return self._core.prefix_hits()

    @property
    def prefix_queries(self) -> int:
        return self._core.prefix_queries()

    # ---- prefix cache ---------------------------------------------------

    def lookup_prefix(self, token_ids,
                      count_stats: bool = True) -> tuple[list[int], int]:
        blocks = self._core.lookup_prefix(list(token_ids), count_stats)
        return blocks, len(blocks) * self.block_size

    def prefix_chain(self, token_ids) -> list[int]:
        return self._core.prefix_chain(list(token_ids))

    def prefix_resolvable(self, h: int) -> bool:
        return self._core.prefix_resolvable(int(h))

    # ---- tiered KV cache: eviction log + restore state machine ----------

    @property
    def record_evictions(self) -> bool:
        return self._record_evictions

    @record_evictions.setter
    def record_evictions(self, on: bool) -> None:
        self._record_evictions = bool(on)
        self._core.set_record_evictions(bool(on))

    def take_evictions(self) -> list[tuple[int, int]]:
        return self._core.take_evictions()

    def begin_restore(self, hashes):
        return self._core.begin_restore([int(h) for h in hashes])

    def commit_restore(self, hashes, blocks) -> int:
        return self._core.commit_restore([int(h) for h in hashes],
                                         [int(b) for b in blocks])

    def abort_restore(self, blocks) -> None:
        self._core.abort_restore([int(b) for b in blocks])

    @property
    def num_restoring_blocks(self) -> int:
        return self._core.num_restoring_blocks()

    @property
    def num_cached_blocks(self) -> int:
        return self._core.num_cached_blocks()

    # ---- allocation -----------------------------------------------------

    def allocate(self, seq_id: str, prompt_token_ids, shared_blocks=None):
        blocks = self._core.allocate(seq_id, list(prompt_token_ids),
                                     list(shared_blocks or []))
        from tpuserve.runtime.block_manager import SeqAlloc
        if self.seats is not None:
            self.seats.acquire(seq_id)
        return SeqAlloc(blocks=blocks, num_tokens=len(prompt_token_ids))

    def needs_new_block(self, seq_id: str) -> bool:
        return self._core.needs_new_block(seq_id)

    def can_append(self, seq_id: str) -> bool:
        return self._core.can_append(seq_id)

    def append_slot(self, seq_id: str) -> int:
        return self._core.append_slot(seq_id)

    def reserve(self, seq_id: str, total_tokens: int) -> None:
        self._core.reserve(seq_id, total_tokens)

    def advance(self, seq_id: str, n: int) -> None:
        self._core.advance(seq_id, n)

    def slot_for_token(self, seq_id: str, token_idx: int) -> int:
        return self._core.slot_for_token(seq_id, token_idx)

    def block_table(self, seq_id: str) -> list[int]:
        return self._core.block_table(seq_id)

    def release_out_of_window(self, seq_id: str,
                              first_needed_token: int) -> int:
        return self._core.release_out_of_window(seq_id, first_needed_token)

    def free(self, seq_id: str, cache_blocks: bool = True) -> None:
        self._core.free(seq_id, cache_blocks)
        if self.seats is not None:
            self.seats.release(seq_id)

    def num_seqs(self) -> int:
        return self._core.num_seqs()

    # ---- per-cycle batched ops (ONE boundary crossing per engine cycle;
    # results land in caller-owned numpy buffers via the buffer protocol)

    def decode_shortfall(self, seq_ids) -> int:
        return self._core.decode_shortfall(list(seq_ids))

    def charge_decode(self, seq_ids, slots_out) -> int:
        return self._core.charge_decode(list(seq_ids), slots_out)

    def fill_block_tables(self, seq_ids, out) -> int:
        return self._core.fill_block_tables(list(seq_ids), out)

    def reserve_batch(self, seq_ids, totals) -> bool:
        return self._core.reserve_batch(list(seq_ids),
                                        [int(t) for t in totals])

    def advance_batch(self, seq_ids, steps: int) -> None:
        self._core.advance_batch(list(seq_ids), steps)

    def admit_prefill(self, counts, max_seats: int,
                      max_prefill_tokens: int,
                      min_bucket: int) -> tuple[int, int]:
        return self._core.admit_prefill([int(c) for c in counts],
                                        max_seats, max_prefill_tokens,
                                        min_bucket)
