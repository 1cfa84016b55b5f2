"""Disaggregated prefill/decode serving with KV-cache handoff.

This is llm-d's core deployment topology, which the reference installs from
upstream charts (reference: llm-d-deploy.yaml:147-151 uses the base-slim
preset; BASELINE.json north star: "prefill<->decode KV-cache transfer over
ICI rather than NCCL").  TPU-native version: the prefill worker and decode
worker hold separate paged caches (separate devices/meshes in production —
here expressed as two engines); after prefill, the sequence's KV blocks are
gathered from the prefill cache and scattered into freshly allocated blocks
of the decode cache with ``jax.device_put`` — a device-to-device copy that
rides ICI on TPU, no host round-trip, replacing vLLM/llm-d's NCCL/NIXL
connector.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.runtime.engine import Engine, EngineConfig
from tpuserve.runtime.request import Request, RequestOutput, SamplingParams


from functools import partial

from tpuserve.utils import next_power_of_2


@partial(jax.jit, donate_argnames=("cache",))
def _gather_pages(cache: list[dict], idx: jnp.ndarray):
    # donate so XLA needn't keep a second copy of the source cache alive;
    # generic over entry keys so int8 caches move their ks/vs scale pages
    # along with the values
    gathered = [{key: layer[key][idx] for key in layer} for layer in cache]
    return gathered, cache


@partial(jax.jit, donate_argnames=("cache",))
def _scatter_pages(cache: list[dict], seq_kv: list[dict], idx: jnp.ndarray):
    return [
        {key: layer[key].at[idx].set(moved[key].astype(layer[key].dtype))
         for key in layer}
        for layer, moved in zip(cache, seq_kv)
    ]


def _pad_blocks(blocks: Sequence[int]) -> list[int]:
    """Pad the block list to a power-of-two bucket (bounded recompiles);
    padding repeats the first block — rewriting identical data is a no-op."""
    blocks = list(blocks)
    target = next_power_of_2(len(blocks))
    return blocks + [blocks[0]] * (target - len(blocks))


def extract_seq_kv(cache: list[dict], blocks: Sequence[int]) -> tuple[list[dict], list[dict]]:
    """Gather one sequence's KV pages: per-layer {"k","v"} of shape
    (bucketed_num_blocks, block_size, Hkv, D).  Returns (pages, cache)."""
    idx = jnp.asarray(_pad_blocks(blocks), jnp.int32)
    return _gather_pages(cache, idx)


def insert_seq_kv(cache: list[dict], seq_kv: list[dict],
                  blocks: Sequence[int], device=None) -> list[dict]:
    """Scatter transferred pages into the target cache's allocated blocks —
    an in-place donated update.  ``device``: target device/sharding for the
    transfer hop (rides ICI on TPU; no host round-trip).

    Raises ``ValueError`` on a cache-format mismatch between pools: an
    int8 prefill pool handing pages to a bf16 decode pool (or vice versa)
    would otherwise scatter raw quantization codes as values and silently
    drop the scales — corrupted KV with no error anywhere."""
    if seq_kv and cache:
        src_keys, dst_keys = set(seq_kv[0]), set(cache[0])
        if src_keys != dst_keys:
            raise ValueError(
                f"KV cache format mismatch between pools: transferred pages "
                f"carry {sorted(src_keys)} but this pool stores "
                f"{sorted(dst_keys)} — both pools must use the same "
                "--kv-cache-dtype")
        src_dt = jnp.asarray(seq_kv[0]["k"]).dtype
        dst_dt = cache[0]["k"].dtype
        if (src_dt == jnp.int8) != (dst_dt == jnp.int8):
            raise ValueError(
                f"KV cache dtype mismatch between pools: transferred pages "
                f"are {src_dt}, this pool stores {dst_dt} — both pools "
                "must use the same --kv-cache-dtype")
    idx = jnp.asarray(_pad_blocks(blocks), jnp.int32)
    if device is not None:
        seq_kv = jax.device_put(seq_kv, device)
    return _scatter_pages(cache, seq_kv, idx)


@dataclasses.dataclass
class DisaggStats:
    kv_transfers: int = 0
    kv_bytes_transferred: int = 0
    transfer_time_s: float = 0.0


class DisaggregatedEngine:
    """Prefill pool + decode pool with KV handoff.

    The prefill engine only ever runs prefill steps; finished prefills hand
    their KV pages and first sampled token to the decode engine, which runs
    the continuous decode batch.  One process may host both (sharing a chip)
    or each side runs in its own pod — the handoff path is the same.
    """

    def __init__(self, prefill_config: EngineConfig, decode_config: EngineConfig,
                 decode_device=None, mesh=None):
        import dataclasses as _dc
        if prefill_config.lora_modules or decode_config.lora_modules:
            # the migrated Request doesn't carry adapter_idx, and the two
            # pools' adapter banks could differ — decode would silently
            # run base weights on adapter KV
            raise ValueError("multi-LoRA (lora_modules) is not supported "
                             "on disaggregated topologies; use "
                             "merge-at-load lora_dir")
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            # extract_seq_kv / insert_seq_kv move per-layer page lists; the
            # pipeline engine's cache is stage-stacked — fail at pair
            # construction, not with a KeyError mid-transfer
            raise ValueError("disaggregation is not supported on pipeline "
                             "(pp) meshes; use tp or plain engines")
        if decode_device is None:
            # colocated: both engines live on the same chip — split the
            # auto-sizing budget or each would claim ~all of HBM and the
            # second cache allocation OOMs (cache.num_blocks == 0 path)
            def _halved(cfg: EngineConfig) -> EngineConfig:
                if cfg.cache.num_blocks == 0 and cfg.hbm_share == 1.0:
                    return _dc.replace(cfg, hbm_share=0.5)
                return cfg
            prefill_config = _halved(prefill_config)
            decode_config = _halved(decode_config)
        # The prefill side must never window-release: migration ships its
        # block_table() pages, and released entries would transfer block
        # 0's unrelated KV and poison the decode pool's prefix cache.
        prefill_config = _dc.replace(prefill_config, window_release=False)
        self.prefill = Engine(prefill_config, mesh=mesh)
        self.decode = Engine(decode_config, mesh=mesh)
        self.decode_device = decode_device
        self.stats = DisaggStats()
        # Prefilled requests whose KV still lives in the prefill cache,
        # waiting for decode-pool capacity (admission-controlled migration).
        self._ready: list[Request] = []

    def add_request(self, prompt: str | None = None,
                    prompt_token_ids: Optional[Sequence[int]] = None,
                    params: Optional[SamplingParams] = None,
                    request_id: Optional[str] = None,
                    deadline: Optional[float] = None) -> str:
        params = params or SamplingParams()
        # Validate against BOTH pools at intake: a prompt the decode pool can
        # never admit must be rejected here, not discovered as a MemoryError
        # in step() after it has already prefilled (which would fail every
        # other in-flight request via the runner's engine-failure path).
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("need prompt or prompt_token_ids")
            prompt_token_ids = self.prefill.tokenizer.encode(prompt)
            prompt = None
        n = len(prompt_token_ids)
        # max_tokens == 1 finishes during prefill and never migrates, so only
        # requests that will actually decode are held to the decode pool cap.
        if params.max_tokens > 1 and n >= self.decode.max_seq_len:
            raise ValueError(
                f"prompt of {n} tokens exceeds the decode pool capacity "
                f"({self.decode.max_seq_len} tokens)")
        rid = self.prefill.add_request(prompt=prompt,
                                       prompt_token_ids=prompt_token_ids,
                                       params=params, request_id=request_id,
                                       deadline=deadline)
        # Mirror the record decode-side immediately: every request is claimed
        # from (and popped off) decode.requests regardless of where it ends.
        self.decode.requests[rid] = self.prefill.requests[rid]
        return rid

    def _decode_has_capacity(self, req: Request) -> bool:
        dst = self.decode
        if dst.scheduler.num_running >= dst.config.scheduler.max_num_seqs:
            return False
        # prompt blocks + 1 headroom block for the first decode append
        need = dst.block_manager.blocks_needed(req.num_prompt_tokens) + 1
        return need <= dst.block_manager.num_free_blocks

    def _migrate(self, req: Request) -> None:
        """Move a prefilled sequence: KV pages + state -> decode pool.
        Caller guarantees decode capacity (_decode_has_capacity)."""
        rid = req.request_id
        src_blocks = self.prefill.block_manager.block_table(rid)
        seq_kv, self.prefill.kv_cache = extract_seq_kv(self.prefill.kv_cache,
                                                       src_blocks)
        dst = self.decode
        dst_alloc = dst.block_manager.allocate(rid, req.prompt_token_ids)
        t0 = time.monotonic()
        try:
            dst.kv_cache = insert_seq_kv(dst.kv_cache, seq_kv,
                                         dst_alloc.blocks,
                                         device=self.decode_device)
        except Exception:
            # the pages never landed in the decode cache: without this the
            # decode pool permanently leaks the allocation (the request is
            # not yet registered decode-side, so no abort/salvage path can
            # free it — tpulint kv-leak pass)
            dst.block_manager.free(rid, cache_blocks=False)
            raise
        self.stats.transfer_time_s += time.monotonic() - t0
        self.stats.kv_transfers += 1
        per_block = (self.prefill.kv_cache[0]["k"].nbytes
                     // self.prefill.cache_cfg.num_blocks)
        self.stats.kv_bytes_transferred += (
            2 * len(src_blocks) * per_block * len(self.prefill.kv_cache))

        # Adopt the request into the decode engine mid-flight.
        dst.requests[rid] = req
        dst._detok[rid] = self.prefill._detok.pop(rid)
        g = self.prefill._guided.pop(rid, None)
        if g is not None:
            # the JSON acceptor follows the request, or guided decoding
            # silently stops at the pool boundary (and prefill leaks state)
            dst._guided[rid] = g
        gf = self.prefill._guided_fsm.pop(rid, None)
        if gf is not None:
            # the grammar-FSM mirror state follows the same way (the fsm
            # object is engine-agnostic host data; the decode engine
            # uploads its own device tables on first window)
            dst._guided_fsm[rid] = gf
        plan = self.prefill._guided_plan.pop(rid, None)
        if plan:
            # a committed canonical-suffix plan follows too — dropping it
            # mid-rune would strand dangling bytes in ctx (see
            # adopt_prefilled's guided_plan for the cross-pod twin)
            dst._guided_plan[rid] = plan
        if dst._adaptive_window and (dst.scheduler.running
                                     or dst._pending_window is not None):
            # a migration into a busy decode pool is an arrival: without
            # this stamp, adaptive window sizing (engine.py _window_steps)
            # never engages under disaggregation — migrations bypass
            # Engine.add_request
            dst._last_busy_arrival = time.monotonic()
        dst.scheduler.running.append(req)
        self.prefill.block_manager.free(rid)
        self.prefill.requests.pop(rid, None)

    def _try_migrations(self) -> bool:
        """Migrate every parked request the decode pool can admit."""
        migrated = False
        still_ready = []
        for req in self._ready:
            if self._decode_has_capacity(req):
                self._migrate(req)
                migrated = True
            else:
                still_ready.append(req)
        self._ready = still_ready
        return migrated

    def step(self) -> list[RequestOutput]:
        """One iteration: drain ready migrations under decode admission
        control, run prefill intake, then the decode batch."""
        outputs: list[RequestOutput] = []
        self._try_migrations()

        if self.prefill.scheduler.num_waiting:
            outputs.extend(self.prefill.step())
            # the handoff ships a request WITH its first token: read it
            # now (a pipelined prefill engine leaves it on the device)
            outputs.extend(self.prefill._flush_first())
            # Park freshly prefilled requests for migration; pull them out of
            # the prefill scheduler so it never decodes them.
            for req in list(self.prefill.scheduler.running):
                self.prefill.scheduler.running.remove(req)
                self._ready.append(req)
            self._try_migrations()
            # Requests that finished during prefill (e.g. max_tokens=1) never
            # migrate; hand their records to the decode side for claiming.
            for out in outputs:
                if out.finished and out.request_id in self.prefill.requests:
                    self.decode.requests[out.request_id] = \
                        self.prefill.requests.pop(out.request_id)
        # Engine-level has_work, NOT scheduler-level: a pending pipelined
        # window whose rows all finished (zombie-only) leaves the scheduler
        # idle while the flush is still owed — gating on the scheduler
        # would spin generate() forever without ever flushing it.
        if self.decode.has_work():
            outputs.extend(self.decode.step())
        if self._ready and not self.decode.scheduler.has_work():
            # Decode went idle this step; its free block count is now at its
            # maximum, so one more migration attempt is decisive: if nothing
            # moves, the parked request can never be admitted.
            if not self._try_migrations():
                req = self._ready[0]
                raise MemoryError(
                    f"decode pool cannot admit request {req.request_id} "
                    f"({req.num_prompt_tokens} prompt tokens): needs "
                    f"{self.decode.block_manager.blocks_needed(req.num_prompt_tokens) + 1}"
                    f" blocks / 1 seq slot, pool has "
                    f"{self.decode.cache_cfg.num_blocks} blocks total")
        return outputs

    def has_work(self) -> bool:
        return (bool(self._ready) or self.prefill.has_work()
                or self.decode.has_work())

    @property
    def requests(self) -> dict:
        """Request records, mirrored into the decode engine's dict from
        intake (so callers can look up / pop from one real dict)."""
        return self.decode.requests

    def abort_request(self, request_id: str) -> bool:
        aborted = False
        for req in list(self._ready):
            if req.request_id == request_id:
                self._ready.remove(req)
                self.prefill.block_manager.free(request_id)
                self.prefill._detok.pop(request_id, None)
                aborted = True
        if not aborted:
            aborted = (self.prefill.abort_request(request_id)
                       or self.decode.abort_request(request_id))
        if aborted:
            self.prefill.requests.pop(request_id, None)
            self.decode.requests.pop(request_id, None)
        return aborted

    def generate(self, prompts, params=None) -> list[Request]:
        if params is None:
            params = SamplingParams()
        if isinstance(params, SamplingParams):
            params = [params] * len(prompts)
        if len(params) != len(prompts):
            raise ValueError("prompts/params length mismatch")
        rids = []
        for prompt, p in zip(prompts, params):
            if isinstance(prompt, str):
                rids.append(self.add_request(prompt=prompt, params=p))
            else:
                rids.append(self.add_request(prompt_token_ids=prompt, params=p))
        while self.has_work():
            self.step()
        return [self.decode.requests.pop(rid) for rid in rids]
