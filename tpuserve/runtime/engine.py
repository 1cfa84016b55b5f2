"""The serving engine: continuous batching over a paged KV cache.

This is the component the reference outsources to the vLLM container image
(reference: kubernetes-single-node.yaml:14, llm-d-deploy.yaml:176-193 — the
"hot path" of SURVEY.md §3.2).  Rebuilt TPU-first:

- prefill and decode are two jitted functions with bucketed static shapes
  (powers of two) so XLA compiles a small executable set once;
- the KV cache is paged device memory, donated through every step (in-place
  scatter updates, no copies);
- attention runs as Pallas TPU kernels on TPU and as the pure-JAX reference
  implementation on CPU;
- sampling happens on-device; only the sampled (B,) token vector crosses to
  host per step.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.models import transformer
from tpuserve.models.config import ModelConfig, get_model_config
from tpuserve.models.tokenizer import IncrementalDetokenizer, load_tokenizer
from tpuserve.models.transformer import (LAYER_CALLS, LAYER_TRACES,
                                         decode_region, moe_plain_moves)
from tpuserve.models.weights import load_or_init, param_dtype
from tpuserve.ops import sampling as sampling_ops
from tpuserve.ops.attention import PAD_SLOT, kv_stream_by_page
from tpuserve.runtime.block_manager import BlockManager, create_block_manager
from tpuserve.runtime.hostprof import PROF, STARTUP
from tpuserve.runtime.kv_cache import CacheConfig, create_kv_cache
from tpuserve.runtime.request import (
    FinishReason, Request, RequestOutput, RequestState, SamplingParams, check_stop)
from tpuserve.runtime.scheduler import (
    ScheduledBatch, Scheduler, SchedulerConfig, packed_prefill_bucket)
from tpuserve.runtime.slo import (
    ShedError, SloConfig, SloController, class_rank)
from tpuserve.utils import env_flag, next_power_of_2

logger = logging.getLogger("tpuserve.engine")


@dataclasses.dataclass
class EngineConfig:
    model: str = "Qwen/Qwen3-0.6B"
    checkpoint_dir: Optional[str] = None      # HF safetensors dir; None = random init
    # PEFT LoRA adapter directory, merged into the dense weights at load
    # (models/weights.py apply_lora) — full base-model speed, one adapter
    # per engine
    lora_dir: Optional[str] = None
    # Multi-LoRA serving (vLLM --lora-modules): {name: adapter_dir} loaded
    # as STACKED low-rank factors (weights.load_lora_stack); requests pick
    # an adapter by name and mixed batches contract per-row one-hot
    # weights against the stack — no merge, composes with int8
    lora_modules: Optional[dict] = None
    # Weight-only quantization: "int8" halves the per-step HBM weight
    # traffic that bounds decode throughput (models/weights.py
    # quantize_params_int8).  None = full precision.
    quantization: Optional[str] = None
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    # Fraction of device memory this engine may budget when auto-sizing
    # its cache (cache.num_blocks == 0).  The colocated disagg topology
    # runs TWO engines on one chip — each gets 0.5 so they don't
    # double-book the HBM.
    hbm_share: float = 1.0
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    attn_impl: str = "auto"                   # "auto" | "reference" | "pallas"
    enable_prefix_caching: bool = True
    seed: int = 0
    # Pipelined decode: sampled tokens stay on device and feed the next
    # dispatch directly; host bookkeeping (detokenize, stop checks,
    # emission) resolves one dispatch behind, overlapped with the next
    # dispatch's device work, so the loop never stalls on a device->host
    # read with the chip's queue empty.  That holds for a fused window's
    # tokens, a single decode step's, and a prefill's first token (read
    # behind the next window or prefill, Engine._flush_first).  What the
    # host must know before the next dispatch is read at once instead:
    # count-dependent penalties, an active min_tokens floor and guided
    # rows (first tokens and windows), logprobs on the single-step path,
    # and every dispatch that is not a fused window or a prefill.
    # None = auto: on for TPU (async dispatch, real overlap), off for CPU
    # (synchronous backend — nothing overlaps, the extra dispatches only
    # cost).
    pipeline_decode: Optional[bool] = None
    # Sliding-window rolling buffer (Engine._release_window_blocks).
    # Disabled for disagg PREFILL engines: migration ships block_table()
    # pages, and released entries would transfer block 0's unrelated KV
    # and register garbage prefix hashes in the decode pool.
    window_release: bool = True
    # Speculative decoding (n-gram prompt-lookup drafts + one verify pass,
    # runtime/spec.py).  None disables.  Greedy batches only; sampled /
    # penalty / logprob batches run the normal decode path.
    speculative: Optional["SpecConfig"] = None
    # Multi-step decode: run N fused decode+sample iterations per dispatch
    # (models/transformer.decode_multi) — the host syncs once per window
    # instead of once per token.  Batches needing penalties, logprobs or
    # top-k/top-p truncation fall back to single-step.  None = auto: 32 on
    # TPU (dispatch latency amortised N-fold; decisive on multi-host
    # backends), 1 (off) on CPU where the synchronous backend gains
    # little and tests expect per-token streaming.  The window length
    # is not measured on this code (ROADMAP.md A3).
    multi_step: Optional[int] = None
    # Adaptive window sizing: a full multi_step window blocks admission
    # for its whole duration.  What that costs a timed arrival's TTFT is
    # not measured on this code (ROADMAP.md A3; no cell has timed
    # arrivals yet).  When an arrival lands while decode is busy,
    # subsequent windows shrink to ``min_multi_step`` for
    # ``adaptive_window_hold_s`` seconds, bounding a new request's wait
    # to one small window; burst workloads (arrivals into an idle
    # engine) and arrival-free steady state keep the full window, so
    # peak throughput is unaffected.
    adaptive_multi_step: bool = True
    min_multi_step: int = 4
    adaptive_window_hold_s: float = 0.5
    # Deterministic fault injection (runtime/faults.py): a chaos spec
    # string like "decode_dispatch:raise:0.02" arms named injection sites
    # in the hot path.  None = read TPUSERVE_FAULTS from the environment
    # (the manifests wire it through for chaos drills); empty/absent =
    # disabled, and the checks cost two attribute loads per dispatch.
    faults: Optional[str] = None
    # Hang watchdog (server/runner.py): a dispatch that blocks longer than
    # this is declared stuck — the realistic TPU failure mode, where the
    # device call never returns instead of raising.  The runner scales the
    # threshold up during the first steps (compiles legitimately take
    # longer) and fails a stuck step the same way an exception would.
    # 0 disables (the CPU-test default: interpreted kernels have no hang
    # bound worth enforcing).
    step_watchdog_s: float = 0.0
    # Tiered KV cache (runtime/kv_tiers.py, ROADMAP item 1): when HBM
    # pressure evicts a cached prefix block, demote its pages to a host-
    # DRAM tier (bounded byte budget) and from there to a PVC spill dir,
    # instead of freeing the KV; a later prompt whose prefix resolves in
    # a lower tier is restored asynchronously ahead of admission and
    # prefills only the uncached suffix.  None = auto: on whenever prefix
    # caching is on (single-process, non-pp), subject to the
    # TPUSERVE_KV_TIERS env kill switch (=0 restores byte-identical
    # HBM-only behaviour — the same-commit A/B lever).
    kv_tiers: Optional[bool] = None
    # Host-DRAM tier byte budget; 0 = TPUSERVE_KV_HOST_BYTES or 1 GiB.
    kv_host_bytes: int = 0
    # PVC spill directory (third tier); None = TPUSERVE_KV_SPILL_DIR
    # (unset: no spill tier, host-budget overflow is dropped).
    kv_spill_dir: Optional[str] = None
    # SLO class scheduling + overload robustness (runtime/slo.py):
    # request classes (interactive/standard/batch) order admission,
    # reserve prefill/mixed budget headroom for strict classes, preempt
    # batch rows for interactive arrivals (token-identical re-prefill
    # replay), and walk a hysteretic brownout ladder (spec off for
    # batch -> batch max_tokens cap -> shed) under sustained overload.
    # None = TPUSERVE_SLO_CLASSES env (default on; =0 restores classless
    # FIFO byte-identically).
    slo_classes: Optional[bool] = None
    # Brownout/estimator knobs; None = SloConfig() defaults.
    slo: Optional["SloConfig"] = None
    # Injectable monotonic-time source (runtime/clock.py): None = the
    # shared real clock.  The trace-replay harness (tpuserve/replay/)
    # installs a VirtualClock here so recorded incidents re-run in
    # seconds without distorting queue-delay EWMAs, brownout hysteresis,
    # admission deadlines or flight-recorder timelines — every
    # engine-side timestamp flows through this seam (tpulint P1's
    # monotonic-outside-clock-seam rule keeps it that way).
    clock: Optional[object] = None
    # Grammar-FSM guided decoding (runtime/grammar/): compile guided
    # specs to token-level FSMs whose per-state masks ride the fused
    # decode window (true logit masking, distribution-correct), so
    # guided requests keep multi_step throughput instead of pinning to
    # S=1.  Specs the compiler can't bound (state/walk budgets,
    # unspellable chars) fall back per-request to the legacy per-step
    # candidate-substitution path.  The first guided window per
    # (grammar-size bucket, mode, steps) compiles its executable on
    # demand; the FSM itself compiles once per grammar at admission.
    guided_fsm: bool = True

    def resolve_pipeline_decode(self) -> bool:
        # Multi-host lockstep serialises every device computation through the
        # broadcast protocol; the pipelined path's _select_tokens jit over
        # device-resident global tokens cannot run on the coordinator alone,
        # and the per-step host sync it avoids is exactly what lockstep
        # requires anyway.  See parallel/multihost.py "Limitations".
        if jax.process_count() > 1:
            return False
        if self.pipeline_decode is not None:
            return self.pipeline_decode
        return jax.default_backend() == "tpu"

    def resolve_attn_impl(self) -> str:
        if self.attn_impl != "auto":
            return self.attn_impl
        return "pallas" if jax.default_backend() == "tpu" else "reference"

    def resolve_multi_step(self) -> int:
        if self.multi_step is not None:
            return max(1, self.multi_step)
        # Each window ends in one host sync, so wider windows amortise the
        # host round-trip (32 is not measured on this code: ROADMAP.md
        # A3); overrun waste (window_overrun_tokens) stays bounded by S-1
        # per finished sequence.
        return 32 if jax.default_backend() == "tpu" else 1


@dataclasses.dataclass
class EngineStats:
    num_prefill_steps: int = 0
    num_decode_steps: int = 0
    # ragged mixed prefill+decode dispatches (scheduler mixed mode); each
    # also counts once in num_decode_steps when it carried decode rows
    num_mixed_steps: int = 0
    # answer tokens produced by a dispatch that also carried prompt tokens
    # (a mixed step's decode rows): their weights were read with the
    # prompt's; over generated_tokens, the share of decode that rode
    decode_tokens_ridden: int = 0
    # padding-waste observability (the bucketing win is invisible without
    # it): the LAST dispatch's token count including padding vs its real
    # tokens (exported as the tpuserve_step_padded/actual_tokens gauges),
    # plus running totals for before/after efficiency ratios
    step_padded_tokens: int = 0
    step_actual_tokens: int = 0
    # context tokens the LAST dispatch's attention read, summed over its
    # real rows (the step record's ctx_tokens; _note_step_tokens)
    step_ctx_tokens: int = 0
    padded_tokens_total: int = 0
    actual_tokens_total: int = 0
    # the same pair over batched-prefill and prefill-chunk dispatches
    # alone (decode pads little, so the sum above hides what prefill
    # bucketing costs), and how many batched prefills went out packed on
    # one flat token axis (Engine._packed_prefill)
    prefill_tokens_total: int = 0
    prefill_padded_tokens_total: int = 0
    prefill_packed_steps: int = 0
    # of prefill_tokens_total, the tokens whose K and V went into the
    # cache a page at a time (ops/pallas_kv_write.py) and not a scatter
    # row each: the packed prefills and whole-page chunks of an engine
    # whose Pallas kernels are on (ops/attention.py kv_stream_by_page)
    prefill_kv_tokens_paged_total: int = 0
    # context tokens that an MLA model's dispatches other than prefills
    # (decode, a window at its first step, verify, mixed) attended against
    # latent pages: the step records' ctx_tokens summed (host integers)
    kv_latent_tokens_attended_total: int = 0
    # requests whose first token was still on the device when the next
    # dispatch was enqueued (read behind it), against those whose record
    # had to be read before it (Engine._flush_first): deferred /
    # (deferred + flushed_early) is the share of prefills that do not
    # drain the chip's queue
    prefill_first_token_deferred: int = 0
    prefill_first_token_flushed_early: int = 0
    prompt_tokens: int = 0
    generated_tokens: int = 0
    preemptions: int = 0
    requests_finished: int = 0
    spec_steps: int = 0
    spec_proposed: int = 0           # draft tokens offered to the verifier
    spec_accepted: int = 0           # draft tokens accepted
    spec_pauses: int = 0             # adaptive governor pauses (spec.py)
    released_blocks: int = 0         # rolling-buffer KV blocks recycled
    latency_windows: int = 0         # fused windows shrunk for arrivals
    guided_fallbacks: int = 0        # guided steps that left the top-K
    guided_plans: int = 0            # committed canonical-suffix completions
    guided_fsm_requests: int = 0     # requests served by grammar-FSM masks
    guided_fsm_windows: int = 0      # fused windows that carried FSM masks
    # multi-step windows: tokens computed past a request's stop point
    # (EOS / max_tokens mid-window) and dropped at emit — the cost of the
    # fused window, worth watching when tuning multi_step
    window_overrun_tokens: int = 0
    # crash-only recovery (server/runner.py salvage path + watchdog):
    # requests re-queued through the preemption re-prefill path after a
    # faulted/stuck step; requests isolated as poison (or out of salvage
    # budget) and failed individually; watchdog trips on stuck dispatches;
    # whole-engine fail-all fallbacks (the pre-salvage behaviour)
    requests_salvaged: int = 0
    requests_poisoned: int = 0
    watchdog_trips: int = 0
    engine_restarts: int = 0
    # overload robustness (runtime/slo.py): requests shed at intake by
    # the brownout ladder / queue-full class eviction (429 + Retry-After
    # at the API edge, never any prefill spent); batch rows preempted
    # for stricter-class admissions (also counted in ``preemptions``);
    # current brownout level (0 = normal), exported as the
    # tpuserve_brownout_level gauge
    requests_shed: int = 0
    slo_preemptions: int = 0
    brownout_level: int = 0
    # flight recorder (runtime/flight.py): post-mortem bundles written
    # (watchdog trip / fault-storm fail-all / poison isolation); the
    # tpuserve_flight_postmortems_total metric points operators at the
    # bundle files on the model PVC
    flight_postmortems: int = 0
    # tiered KV cache (runtime/kv_tiers.py): blocks demoted out of HBM
    # into the host tier; host->PVC spills; blocks dropped off the last
    # tier (KV lost, re-prefill on next use); blocks restored back into
    # HBM; restore operations begun.  restore_latencies holds the
    # begin->commit wall times of recent restores (drained into the
    # tpuserve_kv_restore_latency_seconds histogram by server/runner.py;
    # bounded so a runner-less engine can't grow it without bound).
    # kv_demote_waited_blocks: demoted blocks whose device-to-host copy
    # the loop had to wait for (the in-flight bound, or a restore of a
    # hash still in flight); 1 - waited/demoted is the share of demotion
    # copies that ran wholly behind the chip's work.
    # kv_demote_declined_blocks: evicted blocks the store did not admit
    # (their hash had never left HBM before): nothing gathered, KV lost
    # as with no tier; demoted / (demoted + declined) is the admitted
    # share of evictions.
    kv_demoted_blocks: int = 0
    kv_demote_declined_blocks: int = 0
    kv_demote_waited_blocks: int = 0
    kv_spilled_blocks: int = 0
    kv_tier_dropped_blocks: int = 0
    kv_restored_blocks: int = 0
    kv_restores: int = 0
    restore_latencies: list = dataclasses.field(default_factory=list)
    # model pool (tpuserve/modelpool/): hot-swaps executed through
    # Engine.swap_model, keyed by the warmth of the incoming weights
    # ("resident"/"host"/"spill"/"cold"/"failed" — the outcome label on
    # tpuserve_model_swaps_total).  swap_latencies holds recent
    # (outcome, seconds) pairs drained into tpuserve_model_swap_seconds
    # by server/runner.py; bounded like restore_latencies.
    # recurrent state (models with state-space layers): seats zeroed for
    # a sequence's first window, and tokens prefilled AGAIN because a
    # pre-empted or salvaged sequence's state was dropped (no snapshot)
    ssm_state_resets: int = 0
    ssm_rebuilt_tokens: int = 0
    # expert layers (models/transformer.py _moe_mlp): rows the sparse
    # dispatch routed (summed over layers and fused steps, a dispatch's
    # padding rows included), expert-layers that got at least one row,
    # and the rows by expert — counted on the device, read with each
    # dispatch's tokens (Engine._moe_note)
    moe_routed_rows: int = 0
    moe_expert_hits: int = 0
    moe_expert_rows: Optional[np.ndarray] = None
    # of a routed row's two moves around the grouped product (into expert
    # order, and back beside its token's other picks; under a share one
    # move of a buffer row), those the layer made by the plain row gather:
    # a static choice from each dispatch's shapes (transformer.
    # moe_plain_moves, no dispatch: a function of shapes), so a host integer.  Over 2 x moe_routed_rows: the
    # share of moves that took the fast form
    moe_row_moves_plain: int = 0
    # under a share (ModelConfig.moe_experts_held; zero without one): of
    # the routed rows those that landed on the experts held here, the held
    # expert-layers that got at least one, the rows of buffer the layer
    # gathered and multiplied for them and the pieces it moved them in,
    # each three grouped products (_moe_held_experts)
    moe_held_rows: int = 0
    moe_held_hits: int = 0
    moe_buffer_rows: int = 0
    moe_held_pieces: int = 0
    # behind a group-limited router (moe_n_group > 1) under a share: the
    # rows one of whose surviving groups is held here, which is the rows a
    # chip of the deployment is sent at all (over moe_routed_rows / k: the
    # share of rows that reach this chip)
    moe_group_rows: int = 0
    # row-layers the channel-gated (Kimi-delta) state update served on
    # decode: a decode dispatch's real tokens times the linear layers, from
    # host integers
    kda_state_row_layers: int = 0
    model_swaps: int = 0
    model_swaps_by_outcome: dict = dataclasses.field(default_factory=dict)
    swap_latencies: list = dataclasses.field(default_factory=list)
    ttft_sum: float = 0.0
    ttft_count: int = 0
    # recent per-token latencies (decode step wall time / batch)
    last_step_time: float = 0.0


@dataclasses.dataclass
class PendingDecode:
    """An in-flight decode step: tokens sampled on device, host bookkeeping
    (append/detokenize/stop/emit) deferred to the next engine step."""
    reqs: list
    toks: jax.Array                  # (B,) int32, device-resident
    seq: int = 0                     # the dispatching cycle's step record


@dataclasses.dataclass
class PendingWindow:
    """An in-flight fused multi-step window (pipelined): the (B, S) token
    block stays on device while the NEXT window is dispatched from its last
    column, so the host sync that ends every window overlaps the next
    window's device time instead of serialising with it."""
    reqs: list
    # (B, S) int32, device-resident; a mixed step's decode rows are a
    # window of one step and keep the sampler's (B,) as it is
    toks: jax.Array
    steps: int
    # in-window logprobs: (chosen_lp (B,S), top_ids (B,S,N), top_lps
    # (B,S,N)) device arrays when the window computed them, else None
    lp: tuple | None = None
    # grammar-FSM states after the window's last iteration ((B,) int32,
    # -1 = unguided row) — the NEXT guided window chains off these on
    # device, exactly like toks[:, -1] chains the input tokens; the host
    # mirror advances at flush through the same table
    gstate: jax.Array | None = None
    seq: int = 0                     # the dispatching cycle's step record

    @property
    def tail(self) -> jax.Array:
        """(B,): each row's newest token, the next dispatch's input."""
        return self.toks if self.toks.ndim == 1 else self.toks[:, -1]

    def rows(self) -> dict:
        return {r.request_id: i for i, r in enumerate(self.reqs)}


@dataclasses.dataclass
class PendingFirst:
    """A prefill whose first tokens are sampled but not read: the (B,)
    tokens stay on device and feed the next window's rows directly, and
    the host reads them behind that dispatch (Engine._flush_first)."""
    reqs: list
    toks: jax.Array                  # (B,) int32, device-resident
    # (chosen_lp (B,), top_ids (B, N), top_lps (B, N)) device arrays when
    # a row asked for logprobs, else None
    lp: tuple | None = None
    # the sampled-from logits, kept only for rows on the guided
    # substitution path (read at once: the host picks their token)
    logits: jax.Array | None = None
    seq: int = 0                     # the dispatching cycle's step record
    # row of ``toks`` that holds ``reqs[0]``'s token (a mixed step's
    # completing prompts lie behind its decode rows)
    offset: int = 0

    @property
    def tail(self) -> jax.Array:
        return self.toks

    def rows(self) -> dict:
        return {r.request_id: self.offset + i
                for i, r in enumerate(self.reqs)}


def decode_step_bytes(params, kv_cache, model_cfg, cache_cfg,
                      seats: int) -> tuple[int, int]:
    """``(weights, K/V)``: the bytes one decode step of a full batch
    reads.  Weights: every leaf of ``params``, all held experts included
    (a batch of rows touches them all), less the embedding table where the
    head is untied (a lookup reads ``seats`` rows of it).  K/V: what the
    seats make a step read from the pool, layer by layer: a seat reaches
    back ``max_model_len`` tokens or its layer's window, all seats
    together no more than the pool holds, and HALF of that: a pool the
    seats fill holds sequences at every stage of their lives, so the
    context a step's rows attend is on average half of what they will hold
    at their ends.  Shapes suffice (``jax.eval_shape`` trees): nothing is
    read from the device."""
    def nbytes(tree) -> int:
        return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(tree))

    weights = nbytes(params)
    if "lm_head" in params:
        weights -= nbytes(params["embed"])
    pool = cache_cfg.num_blocks * cache_cfg.block_size
    kv = 0
    for layer, pages in zip(model_cfg.kv_layers, kv_cache):
        reach = min(cache_cfg.max_model_len,
                    model_cfg.layer_window(layer) or cache_cfg.max_model_len)
        kv += nbytes(pages) // pool * min(pool, seats * reach)
    return weights, kv // 2


# Weights a decode step reads in less time than the host takes to launch
# it bind nothing: 1 GiB is 1.3 ms of a v5e's HBM, about one cycle of the
# engine loop, which is what a mixed step costs more than the fused window
# it displaces (one step a dispatch, not multi_step).  Such an engine's
# decode is bound by the host, and riding saves it nothing.
HOST_BOUND_WEIGHT_BYTES = 1 << 30


def route_excluded(model_cfg: ModelConfig, *, staged: bool, mesh: bool,
                   packed: bool) -> str | None:
    """Why no mixed step is scheduled for an engine by observation,
    whatever its decode is bound by; None where one may be.  ``staged``:
    a pipeline or multi-host engine; ``mesh``: any mesh; ``packed``: the
    engine's batched prefills take the ragged trunk
    (``Engine._packed_prefill``: pages in the model's own dtype).  The
    kind of attention is no reason: a latent engine's route falls to
    ``decode_route``'s two numbers, its latent pages counted as K/V."""
    if model_cfg.has_state:
        return ("recurrent state: a chunk of the scan would straddle the "
                "decode rows' sequences")
    if staged:
        return ("the ragged trunk is neither stage-stacked nor in the "
                "lockstep protocol")
    if mesh:
        return "the ragged kernel has no tp wrapper"
    if not packed:
        return ("pages narrower than the model's dtype: a mixed step "
                "attends a prompt's K/V read back from them")
    return None


def decode_route(weight_bytes, kv_bytes, *, forced: bool = False,
                 excluded: str | None = None) -> dict:
    """The engine's verdict on its decode route, with its two numbers
    (logged once at build, shown on /debug/engine): ``"mixed"`` where a
    decode step reads more bytes of weights than of K/V, so a prompt
    dispatch that carries the decode rows saves the larger part of a
    decode step; ``"phase_split"`` where the K/V read sets the pace (the
    weights saved are the smaller part, and a mixed step gives up the
    fused window) or where ``excluded`` names why no mixed step can
    serve this engine.  ``forced`` (SchedulerConfig.mixed_batching)
    overrides the observation, not the numbers.  Weights under
    ``HOST_BOUND_WEIGHT_BYTES`` bind nothing either way."""
    if forced:
        rides, why = True, "forced by mixed_batching"
    elif excluded is not None:
        rides, why = False, excluded
    elif weight_bytes < HOST_BOUND_WEIGHT_BYTES:
        rides, why = False, ("a decode step's weights are read in less time "
                             "than a dispatch takes: bound by the host")
    elif weight_bytes >= kv_bytes:
        rides, why = True, "a decode step is bound by its weights"
    else:
        rides, why = False, "a decode step is bound by its K/V read"
    return {"route": "mixed" if rides else "phase_split", "rides": rides,
            "why": why, "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}


def _host_shapes_token(r: Request, slack: int) -> bool:
    """Whether the host shapes or picks ``r``'s next token from history it
    holds, so a dispatch cannot chain it off tokens still on the device:
    penalties and logprobs read the host's token history, guided
    validation substitutes tokens host-side each step, and the min_tokens
    floor reads the host's output length (``slack`` tokens stale under the
    pipeline: the mask could lift that much late or early)."""
    return (r.params.needs_penalties or r.params.logprobs is not None
            or r.params.guided is not None
            or (r.params.needs_min_tokens and r.params.min_tokens_active(
                len(r.output_token_ids), slack=slack)))


def _warm_each(family: str, rnd: int, buckets):
    """A warm-up loop's buckets, each handed out under a span
    ``startup.warmup.<family>`` that stays open for the loop's body (the
    ``with`` closes when the loop asks for the next one): which family and
    which rung the warm-up's seconds went to (runtime/hostprof.py)."""
    for bucket in buckets:
        with STARTUP.phase("startup.warmup." + family, round=rnd,
                           bucket=str(bucket)):
            yield bucket


@jax.jit
def _select_tokens(toks, gather, host, use_host):
    """Next-step input tokens without a host round-trip: previous step's
    device tokens where available, host-known tokens (fresh prefills)
    elsewhere."""
    return jnp.where(use_host, host, toks[gather])


class Engine:
    """Single-replica serving engine (one model, one device/mesh)."""

    def __init__(self, config: EngineConfig, *, params=None,
                 model_cfg: ModelConfig | None = None, mesh=None):
        self.config = config
        # ONE time source for everything replay-reachable (scheduler,
        # SLO controller, flight recorder, request stamps): the
        # injectable clock seam.  Replay swaps in a VirtualClock.
        from tpuserve.runtime.clock import MONOTONIC
        self.clock = config.clock or MONOTONIC
        if config.quantization not in (None, "int8"):
            # reject before the (potentially multi-GB) checkpoint load
            raise ValueError(f"unknown quantization {config.quantization!r};"
                             " supported: int8")
        self.model_cfg = model_cfg or get_model_config(config.model)
        self.cache_cfg = config.cache
        self.attn_impl = config.resolve_attn_impl()
        if self.model_cfg.is_mla and self.attn_impl == "pallas":
            # MLA attends in latent space against a 1-head latent cache.
            # The paged decode and ragged kernels read it in the absorbed
            # form (V the first kv_lora_rank lanes of the K page they
            # landed: ops/pallas_paged_attention.py ``v_lanes``) on one
            # device and in the model's own dtype; what they cannot do is
            # refused here, each by name.
            if mesh is not None or jax.process_count() > 1:
                self._no_pallas(
                    "a latent cache is ONE kv head, which the Pallas "
                    "kernels' head-parallel tp wrappers cannot split: MLA "
                    "under a mesh attends on the reference path")
            elif jnp.dtype(config.cache.dtype) != param_dtype(self.model_cfg):
                self._no_pallas(
                    f"the latent kernels read pages in the model's own "
                    f"dtype: a {config.cache.dtype} latent cache is "
                    "dequantized by slice scales (latent, rope) that only "
                    "the reference path applies")
        self.mesh = mesh
        from tpuserve.parallel.mesh import AXIS_PP
        self._pp = mesh.shape.get(AXIS_PP, 1) if mesh is not None else 1
        if self.model_cfg.moe_experts_held and mesh is not None:
            raise ValueError(
                f"{self.model_cfg.name} holds "
                f"{self.model_cfg.moe_experts_held} of "
                f"{self.model_cfg.num_experts} experts, which is one "
                "device's share: under a mesh the expert layer runs its "
                "dense form over ALL experts, and the exchange that would "
                "join the shares does not exist yet")
        # Recurrent state (state-space layers beside attention): one slot
        # of state a running sequence, in a pool beside the paged KV cache
        # (self.ssm_state, below).  Nothing snapshots a slot, so every
        # route that would need the state at some EARLIER token is closed
        # here or observed off below, each with its sentence — no option.
        recurrent = self.model_cfg.has_state
        if recurrent:
            name = self.model_cfg.name
            if mesh is not None or jax.process_count() > 1:
                raise ValueError(
                    f"{name} keeps recurrent state, which has no sharding "
                    "yet: the seat pool, the chunked scan and the "
                    "state-update kernel run on one device (no tp/pp mesh, "
                    "single process)")
            if config.speculative:
                raise ValueError(
                    f"{name} keeps recurrent state: a rejected draft token "
                    "has already advanced it and there is no snapshot to "
                    "roll back to, so speculative decoding is not "
                    "supported")
            if config.lora_modules:
                raise ValueError(
                    f"{name}: multi-LoRA serving is not supported with "
                    "state-space layers (the mixer's projections carry no "
                    "per-row adapter path); merge one adapter at load "
                    "(lora_dir)")
        self.tokenizer = load_tokenizer(config.checkpoint_dir or config.model,
                                        vocab_size=self.model_cfg.vocab_size)
        with STARTUP.phase("startup.weights"):
            # (no sync closes the span: initialisers are enqueued, and
            # what the device still owes surfaces under the warm-up)
            if params is None:
                # random init is born tp-sharded (a model that needs the
                # mesh to fit cannot pass through one device); the pp
                # engine restacks whole layers itself below
                params = load_or_init(self.model_cfg, config.checkpoint_dir,
                                      config.seed,
                                      mesh=mesh if self._pp == 1 else None)
            if config.lora_dir:
                # before quantization/sharding: the merge targets bf16
                # kernels
                from tpuserve.models.weights import apply_lora
                params = apply_lora(params, self.model_cfg, config.lora_dir)
                logger.info("merged LoRA adapter from %s", config.lora_dir)
            if config.quantization == "int8":
                from tpuserve.models.weights import quantize_params_int8
                if "scale" not in params["embed"]:  # not already quantized
                    params = quantize_params_int8(params)
        self._lora_names: Optional[list] = None
        if config.lora_modules:
            # after quantization on purpose: the stacked deltas apply
            # AFTER the dequantizing matmul, so int8 base + bf16 adapters
            # compose (unlike apply_lora's merge)
            if jax.process_count() > 1:
                raise ValueError("multi-LoRA serving is single-process "
                                 "(the lockstep protocol doesn't broadcast "
                                 "adapter weights)")
            if mesh is not None:
                raise ValueError("multi-LoRA with a tp/pp mesh is not "
                                 "supported yet (the stacked factors have "
                                 "no shardings); use merge-at-load "
                                 "lora_dir under TP")
            if config.speculative:
                raise ValueError("multi-LoRA cannot combine with "
                                 "speculative decoding (the verify trunk "
                                 "doesn't thread adapter weights)")
            from tpuserve.models.weights import load_lora_stack
            self._lora_names = load_lora_stack(params, self.model_cfg,
                                               config.lora_modules)
            self._lora_index = {n: i for i, n in
                                enumerate(self._lora_names)}
            logger.info("loaded %d LoRA adapter(s): %s",
                        len(self._lora_names), self._lora_names)
        self.params = params
        # the paged cache, the state pool, the block manager and what
        # stands on them, up to devprof.set_hbm: closed by hand at this
        # method's end, 470 lines on
        pools = STARTUP.phase("startup.pools").__enter__()
        if self.cache_cfg.num_blocks == 0:
            # vLLM gpu_memory_utilization analog: size the KV cache to
            # what the HBM budget leaves after the (possibly quantized)
            # weights actually loaded
            self.cache_cfg = dataclasses.replace(
                self.cache_cfg, num_blocks=self._auto_num_blocks(mesh))
            logger.info("auto-sized KV cache: %d blocks of %d tokens",
                        self.cache_cfg.num_blocks, self.cache_cfg.block_size)
        if self._pp > 1:
            # Pipeline placement: layers + KV stage-stacked over 'pp'
            # (parallel/pipeline.py) — per-device weight AND cache bytes
            # divide by the stage count; _exec_prefill/_exec_decode route
            # to the pipelined trunk (incl. fused decode windows via
            # pp_decode_multi).  Single-process, pure-pp mesh, no chunked
            # prefill / speculation (gated below and at the scheduler).
            from tpuserve.parallel.mesh import AXIS_DP, AXIS_EP, AXIS_TP
            from tpuserve.parallel.pipeline import (create_stacked_cache,
                                                    stack_pipeline_params)
            extra = {a: mesh.shape.get(a, 1)
                     for a in (AXIS_DP, AXIS_EP, AXIS_TP)}
            if any(v > 1 for v in extra.values()):
                raise ValueError(
                    f"pipeline engine needs a pure ('pp',) mesh, got extra "
                    f"axes {extra} (tp-within-stage composition is future "
                    "work — use tp OR pp)")
            if jax.process_count() > 1:
                raise ValueError("pipeline engine is single-process; "
                                 "multi-host serving uses the lockstep tp "
                                 "path (parallel/multihost.py)")
            if config.speculative:
                raise ValueError(
                    "speculative decoding is not supported on the pipeline "
                    "engine (the verify window would serialise through "
                    "every stage)")
            if self.model_cfg.is_mla or self.model_cfg.moe_first_k_dense:
                raise ValueError(
                    "pipeline parallelism is not supported for latent "
                    "attention or leading dense layers yet: the staged trunk "
                    "stacks homogeneous layer pytrees and materialised "
                    "{'k','v'} pages, which MLA's latent cache and "
                    "first_k_dense_replace's mixed layer structure both "
                    "break — use tp instead")
            if self.attn_impl == "pallas":
                self._no_pallas("the pipeline engine runs reference "
                                "attention; Pallas-under-pp is future work")
            self._pp_head, self._pp_stages = stack_pipeline_params(
                self.params, self.model_cfg, mesh)
            self.kv_cache = create_stacked_cache(self.model_cfg,
                                                 self.cache_cfg, mesh)
            # the unstacked copy would pin a full set of weights on one
            # device for nothing — the pipelined trunk owns the params now
            self.params = None
        elif mesh is not None:
            # Tensor-parallel placement: GSPMD inserts the ICI collectives.
            from tpuserve.parallel.sharding import cache_shardings, shard_params
            self.params = shard_params(self.params, self.model_cfg, mesh)
            self.kv_cache = create_kv_cache(
                self.model_cfg, self.cache_cfg,
                shardings=cache_shardings(self.model_cfg, mesh))
        else:
            self.kv_cache = create_kv_cache(self.model_cfg, self.cache_cfg)
        # a model with expert layers: its cache trunks return each
        # dispatch's routing last (_keep_pool), and under a mesh they run
        # the dense GSPMD form of the layer (_moe_mlp)
        self._moe_counted = self.model_cfg.routes_experts
        # the last dispatch's picks for the rows it returned logits of,
        # on the device until a row's logprobs take them
        # (_logprobs_enqueue), and for all its rows where it was a
        # prefill (_note_prompt_picks)
        self._moe_picks = self._moe_prompt_picks = None
        self._moe_kw = ({"moe_dense": True}
                        if self._moe_counted and mesh is not None else {})
        # the recurrent-state pool: NOT a leaf of self.kv_cache, which
        # stays "bytes a token" for everything that sizes or copies pages
        self.ssm_state = None
        if recurrent:
            from tpuserve.runtime.kv_cache import create_ssm_state
            self.ssm_state = create_ssm_state(
                self.model_cfg, config.scheduler.max_num_seqs)
        # Pallas under TP: head-parallel shard_map (ops/pallas_tp.py) keeps
        # the fused kernels, each shard on its own kv heads.  (kv heads
        # that do not split evenly over tp never get here: the kv-head
        # sharded cache above cannot be created for them.)
        self._attn_mesh = None
        if mesh is not None and self.attn_impl == "pallas":
            from tpuserve.ops.pallas_tp import tp_partitionable
            if tp_partitionable(self.model_cfg.num_kv_heads, mesh):
                self._attn_mesh = mesh
        if self.attn_impl == "pallas" and jax.default_backend() == "tpu":
            # Mosaic slices a K/V page (page, Hkv, D) only in whole 32-bit
            # sublane rows: a shard's kv heads times the cache itemsize
            # must fill one (int8 needs 4 heads, bf16 2) or the paged
            # kernels are refused at compile time — e.g. int8 KV under
            # tp=4 on an 8-kv-head model.  Interpret mode has no such rule.
            heads = self.model_cfg.num_kv_heads
            if self._attn_mesh is not None:
                from tpuserve.parallel.mesh import AXIS_TP
                heads //= mesh.shape[AXIS_TP]
            if heads * jnp.dtype(self.cache_cfg.dtype).itemsize < 4:
                self._no_pallas(
                    f"{heads} kv head(s) per shard of {self.cache_cfg.dtype} "
                    "pages are narrower than one 32-bit sublane row, which "
                    "the TPU compiler cannot slice")
                self._attn_mesh = None
        prefix_caching = config.enable_prefix_caching
        if prefix_caching and config.lora_modules:
            # cached KV is adapter-specific: a base-model prefix hit reused
            # for an adapter request (or across adapters) would serve KV
            # computed under different weights
            logger.info("multi-LoRA: prefix caching disabled (cached KV "
                        "is adapter-specific)")
            prefix_caching = False
        if prefix_caching and recurrent:
            # a prefix hit's pages hold keys and values, not the recurrent
            # state at the prefix's end; with the prefix cache goes the KV
            # tier below, which files blocks by prefix hash
            logger.info("%s keeps recurrent state: prefix caching and the "
                        "KV tier are off (cached pages do not hold the "
                        "state at a prefix's end)", self.model_cfg.name)
            prefix_caching = False
        self.block_manager = create_block_manager(
            self.cache_cfg.num_blocks, self.cache_cfg.block_size,
            enable_prefix_caching=prefix_caching)
        if recurrent:
            # a sequence takes its seat with its blocks and gives it back
            # with them: finish, abort, pre-emption and salvage all free
            # through the manager
            from tpuserve.runtime.block_manager import SeatPool
            self.block_manager.seats = SeatPool(
                config.scheduler.max_num_seqs)
        # Tiered KV cache (runtime/kv_tiers.py): demote evicted prefix
        # blocks to host DRAM / PVC instead of losing the KV; restore
        # asynchronously ahead of admission.  Gated off under pp (the
        # stage-stacked cache has a different page layout) and multi-host
        # (the lockstep protocol doesn't mirror the scatter dispatches).
        import os as _os_t
        self._kv_tiers = None
        self._restores: dict[str, tuple] = {}   # rid -> (hashes, blocks, t0)
        tiers_on = config.kv_tiers
        if tiers_on is None:
            tiers_on = env_flag("TPUSERVE_KV_TIERS")
        if (tiers_on and prefix_caching and self._pp == 1
                and jax.process_count() == 1):
            from tpuserve.runtime.kv_tiers import TieredPageStore
            host_bytes = config.kv_host_bytes or int(
                _os_t.environ.get("TPUSERVE_KV_HOST_BYTES", 0) or (1 << 30))
            spill = (config.kv_spill_dir
                     or _os_t.environ.get("TPUSERVE_KV_SPILL_DIR") or None)
            self._kv_tiers = TieredPageStore(
                host_bytes, spill_dir=spill,
                sync=lambda: self.devprof.sync("demote"))
            self.block_manager.record_evictions = True
            # bytes one block's pages take on one device (under tp the
            # kv-head axis is sharded, the block axis is not)
            self._kv_block_bytes = sum(
                leaf.addressable_shards[0].data.nbytes
                for leaf in jax.tree.leaves(self.kv_cache)
            ) // self.cache_cfg.num_blocks
        # whether the store's device budget was read (_read_demote_budget)
        self._demote_budget_read = False
        sched_cfg = config.scheduler
        if sched_cfg.mixed_batching and recurrent:
            # decode rows lie one a row on the flat axis, so a chunk of
            # the scan would straddle sequences
            logger.warning("%s keeps recurrent state: mixed ragged "
                           "batching is off; falling back to phase-split "
                           "scheduling", self.model_cfg.name)
            sched_cfg = dataclasses.replace(sched_cfg, mixed_batching=False)
        if sched_cfg.mixed_batching and (self._pp > 1
                                         or jax.process_count() > 1):
            # the ragged trunk is neither stage-stacked nor in the
            # lockstep broadcast protocol — phase-split scheduling there
            logger.warning("mixed ragged batching is single-process, "
                           "non-pp only; falling back to phase-split "
                           "scheduling")
            sched_cfg = dataclasses.replace(sched_cfg, mixed_batching=False)
        if self._pp > 1 and sched_cfg.allow_chunked_prefill:
            # the pipelined trunk has no chunked-prefill path; the flag
            # closes ALL chunk routes (length, prefix-hit-by-choice,
            # preempt-requeue continuation), so long prompts batch-prefill
            # at a big bucket instead of crashing _exec_prefill_chunk
            sched_cfg = dataclasses.replace(sched_cfg,
                                            allow_chunked_prefill=False)
        # Ragged mixed batching: flat-row block granularity (the Pallas
        # kernel's grid block AND the host packing alignment — one source
        # of truth, ops/pallas_ragged_attention.ragged_block) and the
        # FIXED descriptor width, so the flat-token bucket is the ONLY
        # varying dimension across mixed executables.
        # The block follows the model's shape (64 query heads do not fit
        # 128 rows of them in the kernel's VMEM budget): observed, no option.
        from tpuserve.ops.pallas_ragged_attention import ragged_block_for
        self._ragged_blk = ragged_block_for(
            self.model_cfg.cache_q_heads, self.model_cfg.cache_kv_heads,
            self.model_cfg.cache_head_dim, self.cache_cfg.block_size,
            jnp.dtype(self.cache_cfg.dtype).itemsize,
            jnp.dtype(self.model_cfg.dtype).itemsize,
            self.cache_cfg.quantized)
        self._ragged_seqs = next_power_of_2(sched_cfg.max_num_seqs)
        # the rows at the head of a mixed step that its decode rows own
        self._decode_region = decode_region(self._ragged_seqs,
                                            self._ragged_blk)
        # Packed batched prefill: a prefill batch laid out on ONE flat
        # token axis through the same ragged trunk (zero decode rows),
        # bucketed on T alone, instead of a (power-of-two batch x
        # power-of-two length) grid that dispatches about twice the
        # tokens it was given.  Taken where the ragged kernel gives the
        # (B, L) route's result at speed, from what the engine observes
        # (no option): not under a mesh (the kernel has no tp wrapper —
        # which also covers multi-host), not on the pipeline engine,
        # and only with pages in the model's own dtype — the (B, L)
        # route attends the FRESH K/V, and reading them back from int8 or
        # narrower pages is a different result, not a faster one.  (An
        # MLA model's packed prefill attends the latent pages it just
        # wrote in the absorbed form, its (B, L) route the decompressed
        # fresh K/V: the same numbers, and only the packed one has a
        # Pallas form.)  The descriptors have ONE fixed width for these
        # dispatches too.
        self._packed_prefill = (
            mesh is None and self._pp == 1 and jax.process_count() == 1
            and jnp.dtype(self.cache_cfg.dtype) == param_dtype(self.model_cfg))
        self._prefill_seqs = next_power_of_2(sched_cfg.max_prefill_seqs)
        # Mixed ragged steps: where a decode step is bound by its WEIGHTS,
        # every dispatch that carries prompt tokens carries the running
        # decode rows too, so the weights are read once for both.  The
        # route is observed from the model's shape and the pool just
        # sized (decode_route: latent pages count as K/V), no option and
        # no model's name; SchedulerConfig.mixed_batching
        # (--mixed-batching) still forces it, under the exclusions above.
        self._route = self._observe_route(sched_cfg.mixed_batching)
        if self._route["rides"] and not sched_cfg.mixed_batching:
            sched_cfg = dataclasses.replace(sched_cfg, mixed_batching=True)
        logger.info(
            "decode route: %s (%s): a decode step reads %s B of weights, "
            "its seats %s B of K/V from the pool", self._route["route"],
            self._route["why"], self._route["weight_bytes"],
            self._route["kv_bytes"])
        # Pallas-under-tp runs the phase-split kernels via shard_map
        # (ops/pallas_tp.py); the ragged kernel has no tp wrapper yet, so
        # mixed steps fall back to the reference ragged attention there
        # (GSPMD partitions the einsums on its own).
        self._ragged_attn = self.attn_impl
        if self._attn_mesh is not None:
            self._ragged_attn = "reference"
            if sched_cfg.mixed_batching:
                self._no_pallas("the ragged kernel has no tp wrapper; "
                                "mixed steps run reference attention "
                                "under a mesh", attr="_ragged_attn")
        if sched_cfg.mixed_batching:
            # the row budget must cover the full decode region PLUS at
            # least one aligned chunk, or a full decode batch would
            # starve admissions forever (mixed cycles returning None
            # schedule no prefill at all)
            blk = self._ragged_blk
            floor = self._decode_region + blk
            if sched_cfg.mixed_token_budget < floor:
                logger.warning(
                    "mixed_token_budget %d cannot cover max_num_seqs %d "
                    "decode rows plus one %d-row chunk; raising to %d",
                    sched_cfg.mixed_token_budget, sched_cfg.max_num_seqs,
                    blk, floor)
                sched_cfg = dataclasses.replace(sched_cfg,
                                                mixed_token_budget=floor)
        self.scheduler = Scheduler(sched_cfg, self.block_manager,
                                   max_model_len=self.cache_cfg.max_model_len,
                                   ragged_align=self._ragged_blk,
                                   decode_region=self._decode_region)
        self.scheduler.clock = self.clock
        # SLO class scheduling + brownout ladder (runtime/slo.py): the
        # controller is consulted at intake (shed / max_tokens clamp),
        # by the scheduler (class-ordered queue, budget reserve,
        # class-aware preemption victims), and per cycle (estimator
        # tick).  TPUSERVE_SLO_CLASSES=0 / EngineConfig.slo_classes=False
        # leaves it None — every consumer degrades to classless FIFO
        # byte-identically.
        slo_on = config.slo_classes
        if slo_on is None:
            slo_on = env_flag("TPUSERVE_SLO_CLASSES")
        self._slo = (SloController(config.slo or SloConfig(),
                                   sched_cfg.resolve_max_waiting(),
                                   clock=self.clock)
                     if slo_on else None)
        self.scheduler.slo = self._slo
        # Flight recorder (runtime/flight.py): always-on lifecycle ring
        # + per-cycle step records; single-writer from this engine's
        # loop thread, snapshot reads from serving threads.
        from tpuserve.runtime.flight import FlightRecorder
        self.flight = FlightRecorder(clock=self.clock)
        # engine-shape facts ride every bundle so the replay harness
        # (tpuserve/replay/) can build a comparably-sized engine — an
        # incident replayed against twice the seats/blocks diffs
        # meaninglessly
        self.flight.note_engine_facts(
            model=config.model,
            max_num_seqs=sched_cfg.max_num_seqs,
            num_blocks=self.cache_cfg.num_blocks,
            block_size=self.cache_cfg.block_size,
            max_model_len=self.cache_cfg.max_model_len,
            mixed_batching=sched_cfg.mixed_batching,
            multi_step=config.resolve_multi_step(),
            slo_classes=bool(self._slo is not None),
            ssm_state_seats=sched_cfg.max_num_seqs if recurrent else 0,
            decode_route=self._route)
        self.scheduler.flight = self.flight
        if self._slo is not None:
            self._slo.flight = self.flight
        # Device telemetry (runtime/devprof.py): device-time attribution
        # at the existing sync points, the executable-ladder registry,
        # HBM watermark accounting and profiler-capture bookkeeping.
        # Always on like the recorder; the recorder handle lets
        # note_step stamp per-step device-ms deltas and bundles carry
        # the ladder/HBM/capture sections.
        from tpuserve.runtime.devprof import DeviceProfiler
        self.devprof = DeviceProfiler()
        self.flight.devprof = self.devprof
        self._step_kind = "idle"
        self._step_ridden = 0        # a mixed step's decode rows (its record)
        self._step_kda = 0           # its Kimi-delta row-layers (its record)
        # terminal errors for QUEUED requests decided engine-side
        # (deadline expiry, queue-full class eviction): (rid, exc) pairs
        # the runner drains and routes to the waiting clients — the
        # engine's step() has no channel to a request's output queue
        self._error_outbox: list = []
        self.stats = EngineStats()
        # Chaos layer (runtime/faults.py): disabled unless EngineConfig
        # .faults or TPUSERVE_FAULTS arms it.  Every _exec_* hook plus the
        # KV-allocation and window-flush points run through
        # self.faults.check(site, rids); _dispatch_rids names the requests
        # in the dispatch being built, which is also what the runner's
        # salvage path charges fault budgets against.
        import os as _os
        from tpuserve.runtime.faults import FaultInjector
        spec = (config.faults if config.faults is not None
                else _os.environ.get("TPUSERVE_FAULTS"))
        self.faults = FaultInjector.from_spec(spec, seed=config.seed)
        # firing chaos rules land in the affected requests' timelines
        # (post-mortems and salvage sequences become self-explanatory)
        self.faults.on_fire = self.flight.fault_hook
        # Debug strict mode: cross-check block refcounts against live
        # requests after every successful step (block_manager.py
        # check_integrity) — the chaos/salvage tests run with it on, so
        # any recovery path that leaks or double-frees KV blocks fails
        # the cycle it happens, not a soak later.
        self._strict_blocks = bool(_os.environ.get("TPUSERVE_STRICT_BLOCKS"))
        # Host hot-path batching (TPUSERVE_HOST_BATCHED=0 restores the
        # pre-batching per-request/per-token path — the host-overhead A/B
        # lever, not measured on the current code): ON, each decode
        # cycle makes ONE block-manager crossing per operation kind
        # (shortfall probe / slot charge / table fill / window advance)
        # instead of 2-3 per row, and fused-window flushes detokenize +
        # emit once per row per window instead of once per token.
        self._host_batched = env_flag("TPUSERVE_HOST_BATCHED")
        self._dispatch_rids: tuple = ()
        # device outputs of warmup-only executables (samplers, token
        # select) whose producer chains the end-of-warmup sync must drain
        # individually — see warmup()
        self._warm_tails: list = []
        # serialises Engine.embed dispatches: the score budget is
        # per-request; concurrent HTTP handler threads must not multiply it
        import threading
        self._embed_lock = threading.Lock()
        # structured output (params.guided): per-request JSON acceptors +
        # the lazily-built structural fallback token set (runtime/guided.py)
        self._guided: dict[str, object] = {}
        self._guided_fallback_ids: Optional[list[int]] = None
        # grammar-FSM guided decoding (runtime/grammar/): rid -> [TokenFSM,
        # current state]; requests here are served by true logit masking
        # (per-step AND inside fused windows) and never consult the
        # substitution path.  _fsm_cache memoises compiles per grammar;
        # _fsm_device holds the per-grammar device tables (masks /
        # tok_class / class_next), padded to power-of-2 state/class
        # buckets so the windowed executable count stays bounded.
        self._guided_fsm: dict[str, list] = {}
        self._fsm_cache: dict[tuple, object] = {}
        # grammar compile-cache counters surfaced via compile_cache_stats
        # (/debug/engine "compile_caches"): misses count full compile
        # walks AND disk-cache loads; disk_hits is the subset the
        # fleet-wide PVC cache absorbed
        self._fsm_stats = {"hits": 0, "misses": 0, "disk_hits": 0}
        self._fsm_device: dict[int, tuple] = {}
        self._fsm_texts: Optional[dict] = None   # token -> text, lazy
        self._fsm_tok_fp: Optional[str] = None   # disk-cache key half, lazy
        # committed canonical completions: when char-level substitution
        # can't spell the next legal char in single tokens (non-ASCII
        # choices under a byte-fallback vocab), _guided_pick encodes a
        # viable suffix once and emits its token ids verbatim
        self._guided_plan: dict[str, list[int]] = {}
        self.requests: dict[str, Request] = {}   # all live + finished-unclaimed
        self._detok: dict[str, IncrementalDetokenizer] = {}
        self._greedy_cache: dict[int, tuple] = {}
        self._pending: Optional[PendingDecode] = None
        self._pending_window: Optional[PendingWindow] = None
        self._pending_first: Optional[PendingFirst] = None
        # (step seq, device (E + 1,) routing counts) of the dispatches of
        # a model with expert layers whose tokens the host has not read
        # yet: each is read with those tokens (_moe_due, _moe_note)
        self._moe_inflight: list = []
        self._pipeline_decode = config.resolve_pipeline_decode()
        self._multi_step = config.resolve_multi_step()
        self._min_multi_step = min(max(1, config.min_multi_step),
                                   self._multi_step)
        self._adaptive_window = (config.adaptive_multi_step
                                 and self._multi_step > self._min_multi_step)
        self._last_busy_arrival = float("-inf")
        # Speculation needs a single process: followers can't mirror the
        # data-dependent verify shapes (parallel/multihost broadcasts
        # fixed-shape step kinds only).
        self._spec = (config.speculative
                      if jax.process_count() == 1 else None)
        # adaptive-speculation governor state (SpecConfig.adaptive): a
        # rolling (proposed, accepted) window and the decode-step number
        # at which a paused spec path may probe again
        self._spec_window = [0, 0]
        self._spec_resume_step = 0
        # draft-model speculation: the draft's params live alongside the
        # target's; proposals run statelessly over a truncated window
        # (runtime/spec.py SpecConfig.draft_model rationale)
        self._draft_params = None
        self._draft_cfg = None
        if self._spec is not None and self._spec.draft_model:
            self._draft_cfg = get_model_config(self._spec.draft_model)
            if self._draft_cfg.vocab_size != self.model_cfg.vocab_size:
                raise ValueError(
                    f"draft model {self._spec.draft_model!r} vocab "
                    f"{self._draft_cfg.vocab_size} != target vocab "
                    f"{self.model_cfg.vocab_size} — draft tokens must be "
                    "target tokens")
            ddir = self._spec.draft_checkpoint_dir
            if ddir:
                import glob as _glob
                import os as _os
                if not _glob.glob(_os.path.join(ddir, "*.safetensors")):
                    # load_or_init would silently random-init — a garbage
                    # draft degrades to ~0 acceptance with NO error (the
                    # governor just pauses), invisible unlike a garbage
                    # TARGET model
                    raise ValueError(
                        f"draft checkpoint dir {ddir!r} has no "
                        "*.safetensors — a typo here would silently "
                        "serve a random-weights draft")
            self._draft_params = load_or_init(self._draft_cfg, ddir,
                                              config.seed)
            if mesh is not None:
                # replicate the (small) draft across the mesh so spec
                # steps run SPMD alongside the sharded target instead of
                # pinning one chip while the others idle
                from jax.sharding import (NamedSharding,
                                          PartitionSpec as _P)
                self._draft_params = jax.device_put(
                    self._draft_params, NamedSharding(mesh, _P()))
        self._req_counter = itertools.count()
        self._rng_key = jax.random.PRNGKey(config.seed)
        self._eos_ids = set(self.tokenizer.eos_token_ids)
        if self.model_cfg.eos_token_id is not None:
            self._eos_ids.add(self.model_cfg.eos_token_id)
        # Effective sequence limit: per-seq cache capacity, the model's
        # position range (learned position tables silently clamp out-of-range
        # gathers), and total cache size minus one block of headroom — a
        # sequence that can never be allocated must be rejected at intake,
        # not spin forever in the waiting queue.
        self.max_seq_len = min(
            self.cache_cfg.max_model_len,
            self.model_cfg.max_position_embeddings,
            (self.cache_cfg.num_blocks - 1) * self.cache_cfg.block_size)
        # seed the devprof HBM watermark once weights + cache exist
        self._note_hbm_budget()
        pools.__exit__(None, None, None)

    def swap_model(self, config: EngineConfig, *, params=None,
                   source_tier: str = "cold"):
        """Replace the served model in place — the model-pool hot-swap
        seam (tpuserve/modelpool/pool.py drives it).

        Preconditions: the engine is DRAINED (``has_work()`` False — the
        runner's idle branch guarantees the window boundary) and single-
        process/meshless (the lockstep and GSPMD paths don't re-broadcast
        weights).  The engine re-initialises against ``config`` —
        ``params`` carries tier-restored weights (warm swap; the module-
        level transformer jit entries and the persistent XLA cache make
        the rebuilt executable ladder compile-free for a model served
        before), None falls through to ``load_or_init`` (cold swap).

        Continuity across the swap: the flight recorder (one timeline
        per replica, SWAP event emitted here), the device profiler (HBM
        watermark re-reconciled for the new resident model via
        ``_note_hbm_budget``), cumulative ``EngineStats`` (metrics
        counters stay monotonic over the pool's lifetime), and the
        injected clock (replays swap too).  Returns
        ``(old_model_name, old_params)`` — the caller owns demoting the
        outgoing weights through the tiers."""
        if self.has_work():
            raise RuntimeError("swap_model needs a drained engine "
                               "(has_work() is True)")
        if self._pp > 1 or self.mesh is not None or jax.process_count() > 1:
            raise ValueError("model hot-swap is single-process, meshless "
                             "only (weights aren't re-broadcast/re-sharded)")
        t0 = self.clock.monotonic()
        old_model, old_params = self.config.model, self.params
        flight, devprof, stats = self.flight, self.devprof, self.stats
        self.params = None              # the pool owns the outgoing tree
        self.__init__(dataclasses.replace(config, clock=self.clock),
                      params=params)
        # re-attach the replica-lifetime observability objects the
        # re-init replaced with fresh ones
        self.flight = flight
        self.scheduler.flight = flight
        if self._slo is not None:
            self._slo.flight = flight
        self.devprof = devprof
        self.stats = stats
        flight.note_engine_facts(
            model=config.model,
            max_num_seqs=self.scheduler.cfg.max_num_seqs,
            num_blocks=self.cache_cfg.num_blocks,
            block_size=self.cache_cfg.block_size,
            max_model_len=self.cache_cfg.max_model_len,
            mixed_batching=self.scheduler.cfg.mixed_batching,
            multi_step=config.resolve_multi_step(),
            slo_classes=bool(self._slo is not None),
            ssm_state_seats=(self.scheduler.cfg.max_num_seqs
                             if self.ssm_state is not None else 0),
            decode_route=self._route)
        self._note_hbm_budget()         # HBM watermark per resident model
        dt = self.clock.monotonic() - t0
        stats.model_swaps += 1
        stats.model_swaps_by_outcome[source_tier] = (
            stats.model_swaps_by_outcome.get(source_tier, 0) + 1)
        stats.swap_latencies.append((source_tier, dt))
        del stats.swap_latencies[:-256]
        flight.req_event(f"swap:{old_model}->{config.model}", "SWAP",
                         source_tier=source_tier,
                         seconds=round(dt, 4))
        logger.info("model swap %s -> %s (%s, %.2fs)", old_model,
                    config.model, source_tier, dt)
        return old_model, old_params

    def _observe_route(self, forced: bool) -> dict:
        """Whether this engine's decode rows ride its prompt dispatches
        (mixed ragged steps), from what it can see: ``decode_route``'s
        verdict on the weights and the pool, after the engines no mixed
        step can serve (``route_excluded``).  ``forced``:
        SchedulerConfig.mixed_batching, as the exclusions in ``__init__``
        left it."""
        why = route_excluded(
            self.model_cfg, staged=self._pp > 1 or jax.process_count() > 1,
            mesh=self.mesh is not None, packed=self._packed_prefill)
        weights = kv = None
        if self.params is not None and self.mesh is None:
            weights, kv = decode_step_bytes(
                self.params, self.kv_cache, self.model_cfg, self.cache_cfg,
                self.config.scheduler.max_num_seqs)
        return decode_route(weights, kv, forced=forced, excluded=why)

    def _no_pallas(self, why: str, attr: str = "attn_impl") -> None:
        """This engine cannot run the Pallas kernels (``why``).  A caller
        that asked for ``attn_impl="pallas"`` by name gets an error — it
        is measuring or proving those kernels, and a quiet substitute
        would make its result a lie; under ``"auto"`` the engine serves
        on the reference path and says so."""
        if self.config.attn_impl == "pallas":
            raise ValueError(f"attn_impl='pallas' was requested but {why}")
        logger.warning("%s; using reference attention", why)
        setattr(self, attr, "reference")

    def _device_hbm_limit(self) -> int:
        """Per-device HBM budget in bytes, after ``hbm_share``.

        ``TPUSERVE_HBM_BYTES`` overrides detection, then jax
        ``memory_stats()`` (bytes_limit / bytes_reservable_limit).  A TPU
        that reports neither is an error — guessing its HBM would size the
        cache against a number nobody checked; off the TPU (CPU tests) a
        small fixed budget stands in.  Shared by cache auto-sizing
        (_auto_num_blocks) and the devprof HBM watermark so both report
        against the SAME budget."""
        import os

        limit = int(os.environ.get("TPUSERVE_HBM_BYTES") or 0)
        if not limit:
            stats = jax.local_devices()[0].memory_stats() or {}
            limit = (stats.get("bytes_limit")
                     or stats.get("bytes_reservable_limit"))
        if not limit:
            if jax.default_backend() == "tpu":
                raise RuntimeError(
                    "the TPU reports no memory_stats() limit; set "
                    "TPUSERVE_HBM_BYTES to the per-device HBM budget")
            limit = 1 << 30
        return int(limit * self.config.hbm_share)

    def _note_hbm_budget(self) -> None:
        """Seed the devprof HBM watermark: weights (target + draft + any
        pp-stage replication already inside self.params) from actual
        loaded array bytes, the KV reservation from the cache geometry,
        and live in-use bytes from device memory_stats when the backend
        reports them (TPU does; CPU tests fall back to the
        weights+kv floor, making "other" zero there)."""

        def _tree_bytes(tree) -> int:
            if tree is None:
                return 0
            return sum(int(getattr(x, "nbytes", 0))
                       for x in jax.tree_util.tree_leaves(tree))

        weights = _tree_bytes(self.params) + _tree_bytes(self._draft_params)
        kv = _tree_bytes(self.kv_cache)
        state = _tree_bytes(self.ssm_state)
        block_bytes = (kv // self.cache_cfg.num_blocks
                       if self.cache_cfg.num_blocks else 0)
        in_use = None
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
            in_use = stats.get("bytes_in_use")
        except Exception:
            pass
        self.devprof.set_hbm(weights=weights, kv_reserved=kv, state=state,
                             limit=self._device_hbm_limit(),
                             num_blocks=self.cache_cfg.num_blocks,
                             block_bytes=block_bytes, in_use=in_use)

    def _auto_num_blocks(self, mesh) -> int:
        """Size the paged KV cache to the device memory the weights left
        free (CacheConfig.num_blocks == 0) — the vLLM
        ``gpu_memory_utilization`` analog; the reference's deployed vLLM
        sizes its cache the same way rather than taking a block count.

        Uses the ACTUAL loaded parameter bytes (so int8-quantized weights
        buy a proportionally larger cache).  Under a mesh, params and
        cache both shard over the tp axis (replicated over dp), so the
        per-device budget arithmetic cancels to: total blocks =
        (limit*util - params/tp) * tp / bytes_per_block.

        ``TPUSERVE_HBM_BYTES`` overrides the detected per-device memory —
        for engines sharing a chip (the colocated disagg topology passes
        a halved value via hbm_share) and for tests."""
        from tpuserve.runtime.kv_cache import num_blocks_for_budget
        limit = self._device_hbm_limit()
        from tpuserve.models.weights import param_nbytes
        from tpuserve.runtime.kv_cache import ssm_state_bytes
        shards = tp_n = 1
        # the recurrent-state pool is as fixed a cost as the weights
        param_bytes = param_nbytes(self.params) + ssm_state_bytes(
            self.model_cfg, self.config.scheduler.max_num_seqs)
        if mesh is not None:
            # tp shards all weights and the cache, so the per-device
            # arithmetic cancels to the total-budget form.  pp shards the
            # LAYERS and the cache but replicates the head (embed /
            # final-norm / lm-head, pipeline.stack_pipeline_params) on
            # every stage — charge the head once per stage or the budget
            # converts (pp-1)×head_bytes of phantom headroom into KV
            # blocks and OOMs on vocab-heavy models.
            from tpuserve.parallel.mesh import AXIS_PP, AXIS_TP
            pp_n = mesh.shape.get(AXIS_PP, 1)
            tp_n = mesh.shape.get(AXIS_TP, 1)
            shards = tp_n * pp_n
            if pp_n > 1:
                head_bytes = param_nbytes(
                    {k: v for k, v in self.params.items() if k != "layers"})
                param_bytes += (pp_n - 1) * head_bytes
        blocks = num_blocks_for_budget(
            self.model_cfg, self.cache_cfg, limit * shards,
            weight_bytes=param_bytes, head_shards=tp_n)
        # cap at what the scheduler can ever address (+1 decode-headroom
        # block per sequence) — HBM past that is pure waste — and bound
        # host-side block-manager state on huge-HBM backends
        sched = self.config.scheduler
        addressable = sched.max_num_seqs * (self.cache_cfg.max_blocks_per_seq
                                            + 1)
        return min(blocks, addressable, 1 << 17)

    # ------------------------------------------------------------------
    # Request intake
    # ------------------------------------------------------------------

    def add_request(self, prompt: str | None = None,
                    prompt_token_ids: Optional[Sequence[int]] = None,
                    params: Optional[SamplingParams] = None,
                    request_id: Optional[str] = None,
                    adapter: Optional[str] = None,
                    deadline: Optional[float] = None) -> str:
        params = params or SamplingParams()
        # rid assigned FIRST so intake-policy events (SHED,
        # BROWNOUT_CLAMPED) land in the flight recorder under the id the
        # caller can actually look up at /debug/requests/{id}
        request_id = request_id or f"req-{next(self._req_counter)}"
        # SLO intake policy (runtime/slo.py) — BEFORE tokenization, so a
        # shed costs nothing: validate the class (400 at the API edge),
        # shed classes the brownout ladder has turned away (429 +
        # Retry-After, retryable by contract), and clamp batch
        # max_tokens at level 2+ (the graceful step before shedding).
        rank = class_rank(params.slo_class)
        if self._slo is not None:
            # the shed gate wants the LIVE queue depth, not last tick's
            self._slo._waiting = self.scheduler.num_waiting
            retry_after = self._slo.shed_retry_after(rank)
            if retry_after is not None:
                if not params.canary:
                    # synthetic canary probes (tpuserve/obs) must not
                    # feed the availability SLO's bad-event counter —
                    # a shed canary is the PROBER's signal (its own
                    # failures family), not a production shed
                    self.stats.requests_shed += 1
                self._slo.shed_total += 1
                self.flight.req_event(request_id, "SHED",
                                      slo_class=params.slo_class,
                                      level=self._slo.level,
                                      retry_after_s=retry_after)
                raise ShedError(
                    f"overloaded (brownout level {self._slo.level}): "
                    f"{params.slo_class} work is shed; retry in "
                    f"{retry_after:.0f}s", retry_after_s=retry_after)
            cap = self._slo.max_tokens_cap(rank)
            if cap is not None and params.max_tokens > cap:
                params = dataclasses.replace(params, max_tokens=cap)
                self.flight.req_event(request_id, "BROWNOUT_CLAMPED",
                                      max_tokens=cap,
                                      level=self._slo.level)
        caller_ids = prompt_token_ids is not None
        adapter_idx = None
        if adapter is not None:
            if not self._lora_names:
                raise ValueError(f"adapter {adapter!r} requested but no "
                                 "lora_modules are loaded")
            adapter_idx = self._lora_index.get(adapter)
            if adapter_idx is None:
                raise ValueError(f"unknown adapter {adapter!r}; loaded: "
                                 f"{self._lora_names}")
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("need prompt or prompt_token_ids")
            prompt_token_ids = self.tokenizer.encode(prompt)
        prompt_token_ids = list(prompt_token_ids)
        if caller_ids and prompt_token_ids and not all(
                isinstance(t, int) and 0 <= t < self.model_cfg.vocab_size
                for t in prompt_token_ids):
            # out-of-int32 ids crash the prefill buffers; out-of-vocab
            # ids would gather-clamp into silently wrong embeddings.
            # Only CALLER-supplied ids are scanned — the tokenizer's own
            # output is trusted, keeping string-prompt admission flat.
            raise ValueError(
                "prompt token ids must be integers in [0, "
                f"{self.model_cfg.vocab_size})")
        if params.truncate_prompt_tokens is not None:
            if params.truncate_prompt_tokens < 1:
                # a negative slice would keep all-but-the-FIRST-N tokens —
                # the opposite of the documented keep-last-N semantics
                raise ValueError("truncate_prompt_tokens must be >= 1")
            # vLLM semantics: keep the LAST N tokens
            prompt_token_ids = prompt_token_ids[
                -params.truncate_prompt_tokens:]
        if not prompt_token_ids:
            raise ValueError("empty prompt")
        if jax.process_count() > 1 and params.multihost_unsupported():
            # Penalty/bias/logprob ops are separate jits over the
            # mesh-global logits; the lockstep protocol mirrors
            # prefill/decode/sample only.  Rejected at intake rather than
            # deadlocking in SPMD (the API edge already 400s these; this
            # guards direct engine users).  See parallel/multihost.py
            # "Limitations".
            raise ValueError(
                f"{', '.join(params.multihost_unsupported())} not "
                "supported in multi-host serving mode")
        if len(prompt_token_ids) >= self.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} exceeds max sequence "
                f"length {self.max_seq_len} (min of cache capacity "
                f"{self.cache_cfg.max_model_len} and model position range "
                f"{self.model_cfg.max_position_embeddings})")
        if self._pp > 1:
            # chunked prefill is closed under pp, so prefill runs batched
            # REFERENCE attention whose (rows, Hq, L, L) f32 score tensor
            # is unbounded by chunk size — bound it here (same budget idea
            # as Engine.embed) instead of OOMing the stages mid-serving.
            # The worst case is not the prompt itself: a decode-OOM
            # preemption re-prefills prompt+generated at a bigger bucket,
            # and the scheduler can batch several prompts into one bucket
            # (admission charges cand*(picked+1) vs max_prefill_tokens,
            # with the first pick exempt) — so budget the largest
            # re-prefill this request can ever grow to, times the rows the
            # scheduler could co-admit at that bucket.
            worst = min(len(prompt_token_ids) + (params.max_tokens or 0),
                        self.max_seq_len)
            L = next_power_of_2(worst)
            scfg = self.scheduler.cfg
            rows = min(scfg.max_prefill_seqs,
                       max(1, scfg.max_prefill_tokens // L))
            score = rows * self.model_cfg.num_heads * L * L * 4
            if score > self.PP_PREFILL_SCORE_BUDGET_BYTES:
                raise ValueError(
                    f"prompt length {len(prompt_token_ids)} + max_tokens "
                    f"{params.max_tokens} exceeds the pipeline engine's "
                    f"prompt budget: chunked prefill is unavailable under "
                    f"pp and a (re-)prefill at bucket {L} would need "
                    f"{score / 2**30:.1f} GiB of attention scores "
                    f"(budget {self.PP_PREFILL_SCORE_BUDGET_BYTES / 2**30:.0f}"
                    " GiB); lower max_tokens or use tp instead of pp")
        if params.guided is not None:
            if params.guided not in ("json", "json_schema", "regex",
                                     "choice"):
                raise ValueError(f"unsupported guided mode {params.guided!r}"
                                 " (only 'json' / 'json_schema' / 'regex' /"
                                 " 'choice')")
            if params.logprobs is not None:
                # substitution happens after on-device logprob recording —
                # the reported tokens would not match the emitted ones
                raise ValueError(
                    "logprobs cannot be combined with response_format")
            # the char acceptor compiles FIRST so spec errors (bad
            # schema/pattern/choices) surface here as the documented
            # ValueError, whether or not the FSM compile then succeeds
            acceptor = self._make_guided(params)
            fsm = self._fsm_for(params)
            if fsm is not None:
                self._guided_fsm[request_id] = [fsm, fsm.start]
                self.stats.guided_fsm_requests += 1
            else:
                self._guided[request_id] = acceptor
        req = Request(request_id=request_id, prompt_token_ids=prompt_token_ids,
                      params=params, prompt=prompt, adapter_idx=adapter_idx,
                      deadline=deadline,
                      arrival_time=self.clock.monotonic())
        self._detok[request_id] = IncrementalDetokenizer(self.tokenizer)
        self.requests[request_id] = req
        try:
            try:
                self.scheduler.add(req)
            except MemoryError:
                # Queue full: shed the loosest-class waiting work first
                # (ShedError -> 429 to ITS client) to seat a stricter
                # arrival — overload costs batch before interactive.
                # No evictable victim (classless, or the queue is all
                # same-or-stricter): the MemoryError 503 stands.
                if not self._shed_queue_victim(rank):
                    raise
                self.scheduler.add(req)
        except MemoryError:
            # backpressure rejection must not leak the half-registered
            # request record
            self.requests.pop(request_id, None)
            self._detok.pop(request_id, None)
            self._guided.pop(request_id, None)
            self._guided_fsm.pop(request_id, None)
            self._guided_plan.pop(request_id, None)
            raise
        # max_tokens recorded so replay extraction can rebuild the
        # generation budget of requests the incident never finished
        self.flight.req_event(request_id, "QUEUED",
                              slo_class=params.slo_class,
                              prompt_tokens=len(prompt_token_ids),
                              max_tokens=params.max_tokens)
        if self._adaptive_window and (self.scheduler.running
                                      or self._pending_window is not None):
            # an arrival into a BUSY engine predicts more: shrink the next
            # windows so arrivals stop waiting out a full fused window.
            # Burst admission into an idle engine doesn't trip this —
            # and neither does a BACKPRESSURE-REJECTED arrival (stamped
            # only after scheduler.add succeeds): a retry flood against a
            # full queue must not pin running streams at min_multi_step
            # exactly when max throughput would drain the queue fastest.
            self._last_busy_arrival = self.clock.monotonic()
        self.stats.prompt_tokens += len(prompt_token_ids)
        return request_id

    def adopt_prefilled(self, request_id: str,
                        prompt_token_ids: Sequence[int], first_token: int,
                        params: SamplingParams, seq_kv: list,
                        guided_plan: Optional[Sequence[int]] = None) -> str:
        """Adopt a sequence prefilled on another pod (cross-pod
        disaggregation, parallel/disagg_net.py): allocate blocks, scatter
        the transferred KV pages into this cache, and drop the request
        straight into the running decode batch — no recompute.

        ``seq_kv``: per-layer {"k","v"} page arrays as produced by
        ``parallel.disagg.extract_seq_kv`` (power-of-two padded block
        count).  The first token's text was already emitted by the prefill
        pod; it seeds the detokenizer here but is not re-emitted.  Raises
        ``MemoryError`` when the pool lacks blocks or sequence slots (the
        caller maps it to backpressure, e.g. HTTP 503).
        """
        from tpuserve.parallel.disagg import insert_seq_kv
        prompt_token_ids = list(prompt_token_ids)
        if self.ssm_state is not None:
            raise ValueError("KV adoption (disaggregation) is not supported "
                             "for a model with recurrent state: the "
                             "transferred pages do not carry it")
        if self._pp > 1:
            raise ValueError("KV adoption (disaggregation) is not supported "
                             "on the pipeline engine — the transferred "
                             "per-layer pages don't match the stage-stacked "
                             "cache layout")
        if request_id in self.requests:
            raise ValueError(f"request {request_id} already exists")
        if len(prompt_token_ids) >= self.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} exceeds max "
                f"sequence length {self.max_seq_len}")
        need = self.block_manager.blocks_needed(len(prompt_token_ids)) + 1
        if (need > self.block_manager.num_free_blocks
                or self.scheduler.num_running
                >= self.config.scheduler.max_num_seqs):
            raise MemoryError("decode pool at capacity")
        req = Request(request_id=request_id,
                      prompt_token_ids=prompt_token_ids, params=params,
                      arrival_time=self.clock.monotonic())
        alloc = self.block_manager.allocate(request_id, prompt_token_ids)
        try:
            # Everything between the allocate and the self.requests
            # registration below is a leak window: a raise here (bad page
            # shapes from a remote pod, a failed scatter) exits with
            # blocks that neither abort_request nor salvage can find —
            # found by tpulint's kv-leak pass.
            self._drop_superseded_tier_entries(prompt_token_ids)
            seq_kv = [{kk: jnp.asarray(a) for kk, a in l.items()}
                      for l in seq_kv]
            # the allocate above may have evicted cached blocks that the
            # scatter below immediately overwrites — demote them first
            self._demote_evicted()
            self.kv_cache = insert_seq_kv(self.kv_cache, seq_kv,
                                          alloc.blocks)
            req.output_token_ids.append(first_token)
            req.state = RequestState.RUNNING
            req.first_token_time = self.clock.monotonic()
            detok = IncrementalDetokenizer(self.tokenizer)
            # seed; text streamed prefill-side
            first_text = detok.add(first_token)
            self._detok[request_id] = detok
            if params.guided is not None:
                # cross-pod migration: prefer the token-level FSM (advance
                # by the first TOKEN — exact); a prefill pod that already
                # left the FSM (suffix-plan bytes) falls back to the char
                # acceptor
                fsm = self._fsm_for(params)
                if fsm is not None and not guided_plan:
                    ns = fsm.advance(fsm.start, first_token)
                    if ns >= 0:
                        self._guided_fsm[request_id] = [fsm, ns]
                        self.stats.guided_fsm_requests += 1
            if params.guided is not None \
                    and request_id not in self._guided_fsm:
                # rebuild the acceptor and advance it by the first token's
                # text, mirroring what prefill emitted
                st = self._make_guided(params)
                try:
                    st.feed(first_text)
                    self._guided[request_id] = st
                    if guided_plan:
                        # the first token opened a committed
                        # canonical-suffix plan on the prefill pod
                        # (possibly a partial rune — first_text empty):
                        # keep emitting the same sequence, or the dangling
                        # bytes in ctx never complete and the constraint
                        # silently drops (round-4 review finding)
                        self._guided_plan[request_id] = list(guided_plan)
                except ValueError:
                    pass                 # already off-grammar: unconstrained
        except Exception:
            # the transferred KV never fully landed: blocks are suspect,
            # drop them from the prefix pool too
            self.block_manager.free(request_id, cache_blocks=False)
            self._detok.pop(request_id, None)
            self._guided.pop(request_id, None)
            self._guided_fsm.pop(request_id, None)
            self._guided_plan.pop(request_id, None)
            raise
        self.requests[request_id] = req
        # migrated sequences skip the waiting queue entirely: QUEUED and
        # ADMITTED collapse into the adoption instant
        self.flight.req_event(request_id, "QUEUED", migrated=True,
                              prompt_tokens=len(prompt_token_ids))
        self.flight.req_event(request_id, "ADMITTED", migrated=True)
        if self._adaptive_window and (self.scheduler.running
                                      or self._pending_window is not None):
            # cross-pod migration into a busy decode pod is an arrival
            # (bypasses add_request's busy-arrival stamp)
            self._last_busy_arrival = self.clock.monotonic()
        self.scheduler.running.append(req)
        self.stats.prompt_tokens += len(prompt_token_ids)
        return request_id

    def abort_request(self, request_id: str) -> bool:
        req = self.scheduler.abort(request_id)
        if req is None:
            # A request orphaned by a faulted prefill dispatch (popped from
            # waiting, never marked running) is in neither scheduler queue
            # but may still hold KV blocks; without this fallback every
            # fail-all/fail-request path leaks them permanently.  Their
            # contents are suspect, so never park them in the prefix cache.
            req = self.requests.get(request_id)
            if req is None or req.finished:
                return False
            req.state = RequestState.FINISHED
            req.finish_reason = FinishReason.ABORT
            self.block_manager.free(request_id, cache_blocks=False)
            self._detok.pop(request_id, None)
            self._guided.pop(request_id, None)
            self._guided_fsm.pop(request_id, None)
            self._guided_plan.pop(request_id, None)
            self.flight.req_event(request_id, "FINISHED", cause="abort")
            return True
        # A mid-prefill chunked request (holds blocks but isn't RUNNING yet)
        # has later blocks with no KV written: freeing them into the
        # prefix-cache pool would serve garbage to the next identical
        # prefix.  Once RUNNING, every prompt block is fully written.
        partial = req.state != RequestState.RUNNING and req.num_prefilled > 0
        req.state = RequestState.FINISHED
        req.finish_reason = FinishReason.ABORT
        self.block_manager.free(request_id, cache_blocks=not partial)
        self._detok.pop(request_id, None)
        self._guided.pop(request_id, None)
        self._guided_fsm.pop(request_id, None)
        self._guided_plan.pop(request_id, None)
        self.flight.req_event(request_id, "FINISHED", cause="abort")
        return True

    # ---- overload robustness (runtime/slo.py) -------------------------

    def _shed_queue_victim(self, rank: int) -> bool:
        """Queue-full class eviction: drop the TAIL-most waiting request
        of a class strictly looser than ``rank`` (never one with prefill
        progress or delivered tokens — that work is paid for) so a
        stricter arrival gets the seat.  The victim's client is answered
        through the error outbox with a retryable ShedError."""
        if self._slo is None:
            return False
        for victim in reversed(self.scheduler.waiting):
            if (class_rank(victim.params.slo_class) > rank
                    and victim.num_prefilled == 0
                    and not victim.output_token_ids
                    and victim.state == RequestState.WAITING):
                self.flight.req_event(victim.request_id, "SHED",
                                      cause="queue_full_eviction")
                self.abort_request(victim.request_id)
                if not victim.params.canary:
                    # canary probes don't count as production sheds
                    # (tpuserve/obs — same rule as the intake gate)
                    self.stats.requests_shed += 1
                self._slo.shed_total += 1
                ra = self._slo.cfg.shed_retry_after_s
                self._error_outbox.append((victim.request_id, ShedError(
                    "shed from a full queue for higher-priority "
                    f"admission; retry in {ra:.0f}s", retry_after_s=ra)))
                return True
        return False

    def _expire_queued_deadlines(self) -> None:
        """Abort WAITING requests whose admission deadline has passed —
        their client's request_timeout_s fails them anyway; expiring
        queue-side means the engine never spends prefill on a response
        nobody will read.  RESTORING requests are skipped for the one
        cycle their tier restore is in flight (it must commit)."""
        sched = self.scheduler
        if not sched.waiting:
            return
        now = self.clock.monotonic()
        # only requests with NO progress expire here: a preempted
        # mid-stream request (delivered tokens) or a mid-chunk prompt
        # (prefill spent) is paid-for work — aborting it queue-side
        # would discard that and 504 a stream that already produced
        # output; those stay under the handler's own timeout
        expired = [r for r in sched.waiting
                   if r.deadline is not None and now > r.deadline
                   and r.state == RequestState.WAITING
                   and r.num_prefilled == 0 and not r.output_token_ids]
        for r in expired:
            self.abort_request(r.request_id)
            self._error_outbox.append((r.request_id, TimeoutError(
                "request deadline expired before admission (engine "
                "overloaded); aborted queue-side")))

    def drain_request_errors(self) -> list:
        """(rid, exception) pairs for queued requests the engine
        terminated itself (deadline expiry, queue-full eviction);
        consumed by the runner loop, which fails the waiting clients."""
        out, self._error_outbox = self._error_outbox, []
        return out

    def _slo_preempt_for_admission(self) -> list[RequestOutput]:
        """Priority preemption: when the waiting head is stricter-class
        than running batch rows and cannot be admitted for seats or
        blocks, preempt the loosest-class most-recent running rows
        (bounded per cycle and by each victim's preemption budget)
        through the token-identical re-prefill replay path.  Flushes the
        pipelined window and a pending first token first — preempting a
        request with tokens still on the device would double-append them
        at replay."""
        slo, sched = self._slo, self.scheduler
        if slo is None or not sched.waiting or not sched.running:
            return []
        head = sched.waiting[0]
        if head.state == RequestState.RESTORING:
            return []
        rank = class_rank(head.params.slo_class)
        budget = slo.cfg.preempt_budget

        def victims():
            # loosest class first, most recent admission breaking ties
            # (index captured by enumerate — running.index() in a sort
            # key would be O(n^2) on the host hot path)
            return [r for _, _, r in sorted(
                (class_rank(r.params.slo_class), i, r)
                for i, r in enumerate(sched.running)
                if class_rank(r.params.slo_class) > rank
                and r.num_preemptions < budget)]

        def shortfall() -> bool:
            """Mirror of the head's OWN admission arithmetic: preempting
            when the scheduler would have admitted anyway burns a full
            re-prefill for nothing.  Only the mixed path charges
            per-decode-row headroom against the free pool; the
            phase-split prefill/chunk admissions check the raw free
            count."""
            seats = len(sched.running) >= sched.cfg.max_num_seqs
            need = self.block_manager.blocks_needed(head.num_tokens) + 1
            headroom = (len(sched.running)
                        if sched.cfg.mixed_batching else 0)
            blocks = need > (self.block_manager.num_free_blocks - headroom)
            return seats or blocks

        if not victims() or not shortfall():
            return []
        outputs = (self._flush_pending() + self._flush_window()
                   + self._flush_first())
        for _ in range(slo.cfg.max_preempt_per_cycle):
            cand = victims()
            if not cand or not shortfall():
                break
            victim = cand[-1]         # most recent loosest-class row
            sched.preempt_for_class(victim)
            self.stats.preemptions += 1
            self.stats.slo_preemptions += 1
        return outputs

    def salvage_requeue(self) -> list[str]:
        """Crash-only salvage after a faulted/stuck step (server/runner.py):
        drop every piece of in-flight device state and re-queue every live
        request through the existing preemption re-prefill path.  Requests
        carry prompt + generated tokens, so greedy/seeded replays continue
        token-identically; KV is recomputed from scratch — freed blocks are
        NOT parked in the prefix cache (``cache_blocks=False``), because a
        faulted dispatch leaves their contents suspect.

        Also rescues requests ORPHANED by the fault: a prefill batch's
        requests are popped from the waiting queue before the dispatch and
        only marked running after it, so a mid-prefill fault leaves them in
        neither queue (the old fail-all path leaked their blocks).

        Returns the re-queued request ids (queue-head first)."""
        self._pending = None
        self._pending_window = None
        self._pending_first = None
        self._moe_inflight.clear()    # a faulted dispatch's counts with it
        cohort = list(self.scheduler.running)
        self.scheduler.running.clear()
        seen = ({r.request_id for r in cohort}
                | {r.request_id for r in self.scheduler.waiting})
        cohort += [r for r in self.requests.values()
                   if not r.finished and r.request_id not in seen]
        for r in cohort:
            self.block_manager.free(r.request_id, cache_blocks=False)
            r.state = RequestState.PREEMPTED
            r.num_prefilled = 0
            self.flight.req_event(r.request_id, "SALVAGED",
                                  output_tokens=len(r.output_token_ids))
        for r in self.scheduler.waiting:
            if r.num_prefilled > 0:
                # mid-chunk prompts hold blocks whose KV is now suspect too
                self.block_manager.free(r.request_id, cache_blocks=False)
                r.num_prefilled = 0
        for r in reversed(cohort):
            self.scheduler.waiting.appendleft(r)
        return [r.request_id for r in cohort]

    def has_work(self) -> bool:
        # _restores counts as work: an in-flight tier restore must reach
        # its commit step even if every request was aborted meanwhile, or
        # its blocks would sit in the restore-in-flight set forever
        # so does a demotion in flight: the idle cycle lands it, which
        # keeps "no work" meaning "every demoted block is filed"
        return (self.scheduler.has_work() or self._pending is not None
                or self._pending_window is not None
                or self._pending_first is not None
                or bool(self._restores)
                or (self._kv_tiers is not None
                    and self._kv_tiers.in_flight_batches > 0))

    # ------------------------------------------------------------------
    # Step
    # ------------------------------------------------------------------

    def step(self) -> list[RequestOutput]:
        """Run one engine iteration (one prefill batch or one decode
        step).  Under ``TPUSERVE_STRICT_BLOCKS`` every successful cycle
        cross-checks block refcounts against the live request set — the
        runtime complement to tpulint's static kv-leak pass (faulted
        steps skip the check: their orphans are reconciled by the
        runner's salvage path, not mid-exception)."""
        with PROF.phase("engine.step", seq=self.flight.begin_step()):
            t_cycle = self.clock.monotonic()
            outputs = self._step_inner()
            with PROF.phase("step.close"):
                self._close_step(t_cycle)
        return outputs

    def _close_step(self, t_cycle: float) -> None:
        dispatched = bool(self._dispatch_rids)
        self.flight.note_step(
            self._step_kind, len(self._dispatch_rids),
            self.stats.step_actual_tokens if dispatched else 0,
            self.stats.step_padded_tokens if dispatched else 0,
            self.clock.monotonic() - t_cycle,
            ctx_tokens=self.stats.step_ctx_tokens if dispatched else 0,
            ridden_tokens=self._step_ridden, kda_row_layers=self._step_kda)
        if self._slo is not None:
            # estimator tick once per successful cycle (queue depth +
            # the EWMAs fed during scheduling) drives the brownout
            # ladder; the level is mirrored into stats for the
            # tpuserve_brownout_level gauge
            self._slo.tick(self.scheduler.num_waiting)
            self.stats.brownout_level = self._slo.level
        # control-plane scalars for /debug/engine, dump bundles and
        # the autoscaler's scrape: the level + per-class delay
        # EWMAs as plain numbers (ISSUE 12 — consumers must not
        # reconstruct these from histogram buckets).  waiting/
        # running are scheduler facts published even with SLO
        # classes off, so a pool observer is never blind to load.
        self.flight.note_control(
            **(self._slo.snapshot() if self._slo is not None
               else {"brownout_level": 0}),
            waiting=self.scheduler.num_waiting,
            running=len(self.scheduler.running))
        if self._strict_blocks:
            self._check_block_integrity()

    def _check_block_integrity(self) -> None:
        chk = getattr(self.block_manager, "check_integrity", None)
        if chk is None:              # native C++ manager: no introspection
            return
        holders = {r.request_id for r in self.scheduler.running}
        holders |= {r.request_id for r in self.scheduler.waiting
                    if r.num_prefilled > 0}
        # tiered mode: also verify the exactly-one-tier invariant (a hash
        # resolvable in HBM must not be in the tier store, and restore-
        # in-flight hashes must already have LEFT it)
        tier_hashes = (list(self._kv_tiers.hashes())
                       if self._kv_tiers is not None else None)
        chk(expected_seq_ids=holders, tier_hashes=tier_hashes)

    def _step_inner(self) -> list[RequestOutput]:
        self._dispatch_rids = ()
        self._step_kind = "idle"
        self._step_ridden = 0
        self._step_kda = 0
        PROF.bump_cycle()
        self.devprof.bump_cycle()
        # overload robustness, BEFORE scheduling: deadline-expired queued
        # requests leave without spending prefill, and a stricter-class
        # waiting head may preempt running batch rows for its seat/blocks
        # (runtime/slo.py; no-ops when SLO scheduling is off)
        with PROF.phase("slo.admission"):
            self._expire_queued_deadlines()
            pre = self._slo_preempt_for_admission()
        if self._kv_tiers is not None:
            # commit FIRST: last cycle's restored prefixes become HBM
            # prefix entries, so their requests admit THIS cycle with the
            # restored span as shared blocks; then start new restores,
            # whose copies overlap the batch dispatched below
            with PROF.phase("kv.restore"):
                self._commit_tier_restores()
                self._begin_tier_restores()
        with PROF.phase("schedule"):
            batch = self.scheduler.schedule()
        if batch is None:
            # nothing schedulable but a decode result may still be in flight
            outputs = (pre + self._flush_pending() + self._flush_window()
                       + self._flush_first())
            # nor anything left for a demotion's copy to hide behind
            self._land_demotions(wait=True)
            return outputs
        t0 = self.clock.monotonic()
        if batch.kind == "prefill":
            outputs = self._run_prefill(batch)
        elif batch.kind == "prefill_chunk":
            outputs = self._run_prefill_chunk(batch)
        elif batch.kind == "mixed":
            outputs = self._run_mixed(batch)
        elif (self._spec is not None
              and self.stats.num_decode_steps >= self._spec_resume_step
              and not (self._slo is not None
                       and self._slo.spec_paused_for(batch.requests))
              and all(not r.params.needs_penalties
                      and not r.params.needs_logit_bias
                      and not (r.params.needs_min_tokens
                               and r.params.min_tokens_active(
                                   len(r.output_token_ids)))
                      and r.params.logprobs is None
                      and r.params.guided is None
                      for r in batch.requests)):
            # sampled batches speculate too: the verify pass runs
            # rejection-sampling acceptance on device
            # (decode_verify_sampled), so temperature/top-k/top-p keep
            # the spec speedup instead of forcing per-token decode
            outputs = self._run_decode_spec(batch)
        else:
            outputs = None
            if self._multi_step > 1:
                outputs = self._run_decode_multi(batch)  # None = ineligible
            if outputs is None:
                outputs = self._run_decode(batch)
        self.stats.last_step_time = self.clock.monotonic() - t0
        self._release_window_blocks()
        return pre + outputs

    def _release_window_blocks(self) -> None:
        """Sliding-window rolling buffer: blocks whose every position fell
        behind the attention window go back to the pool, so a windowed
        model's cache footprint scales with the WINDOW, not the context
        (vLLM's rolling-buffer cache for Mistral).  Safe against in-flight
        device work: TPU executes dispatches in order, so any reuse of a
        released block is ordered after the steps that attended it."""
        W = self.model_cfg.sliding_window
        if not W or not self.config.window_release:
            return
        if not self.model_cfg.uniform_window:
            # mixed-layer models (Qwen2 max_window_layers, Gemma2
            # alternating) keep full-attention layers that need every
            # position's KV forever — nothing is releasable
            return
        bm = self.block_manager
        for r in self.scheduler.running:
            self.stats.released_blocks += bm.release_out_of_window(
                r.request_id, max(0, r.num_tokens - W))
        for r in self.scheduler.waiting:
            # mid-chunk long prompts free their tail-window backlog too
            if r.num_prefilled > 0:
                self.stats.released_blocks += bm.release_out_of_window(
                    r.request_id, max(0, r.num_prefilled - W))

    def window_dead_tokens(self) -> int:
        """Token-layers of KV held for windowed layers at positions more
        than the window plus one block behind their sequence's end — no
        step will read them again, and layers of two kinds release
        nothing (above): what an allocator by layer kind would give back
        (ROADMAP M3).  Host integers the scheduler already holds; zero
        where every layer is windowed and released, or none is."""
        cfg, bs = self.model_cfg, self.cache_cfg.block_size
        W = cfg.sliding_window
        if not W or (cfg.uniform_window and self.config.window_release):
            return 0
        windowed = sum(cfg.layer_window(i) is not None
                       for i in range(cfg.num_layers))
        dead = sum(max(0, r.num_tokens - W - bs)
                   for r in self.scheduler.running)
        dead += sum(max(0, r.num_prefilled - W - bs)
                    for r in self.scheduler.waiting if r.num_prefilled > 0)
        return dead * windowed

    # ---- tiered KV cache (runtime/kv_tiers.py) ------------------------
    # HBM -> host-DRAM -> PVC prefix offload: evictions of a prefix that
    # has left HBM before demote instead of destroying KV, lower-tier
    # hits restore asynchronously ahead of admission.
    # TPUSERVE_KV_TIERS=0 (or kv_tiers=False) removes all of it —
    # self._kv_tiers is None and no path below runs.

    def _demote_evicted(self) -> None:
        """Drain the block manager's eviction log and demote the evicted
        blocks' device pages into the tier store.  MUST run before any
        dispatch that could overwrite those pages (every _run_* path
        calls this right before its _exec_*; adopt_prefilled before its
        KV scatter): until that dispatch executes, the pages still hold
        the evicted prefix's KV, so one fused gather enqueued here reads
        the whole cycle's evictions.  Only blocks the store admits are
        gathered (kv_tiers.py: a hash that has left HBM before); the rest
        die in place.  Dispatch-only, like the restore:
        the gather's output is a fresh buffer, the store's copier thread
        copies it to the host while the chip does what the caller
        dispatches next, and the loop files the pages later
        (_land_demotions); the hashes resolve in the store from here
        on."""
        store = self._kv_tiers
        if store is None:
            return
        with PROF.phase("kv.demote"):
            # filter out hashes that became HBM-resolvable again since
            # their eviction (a later allocation in the SAME cycle
            # recomputed and re-registered the prefix — two requests
            # sharing it in one batch): HBM holds the canonical copy,
            # demoting the stale block would put the hash in two tiers
            # at once
            ev = [(b, h) for b, h in self.block_manager.take_evictions()
                  if not self.block_manager.prefix_resolvable(h)]
            # admission: a hash leaving HBM for the first time is declined
            # (no request has yet come back for it), and a cycle of such
            # evictions touches neither the device nor the copier
            cold = len(ev)
            ev = [(b, h) for b, h in ev if store.admit(h)]
            self.stats.kv_demote_declined_blocks += cold - len(ev)
            if not ev:
                return
            from tpuserve.runtime.kv_cache import (
                enqueue_block_pages_gather, fetch_block_pages)
            if not self._demote_budget_read:
                self._read_demote_budget()
            # older batches give way BEFORE the gather takes its buffer
            nbytes = next_power_of_2(len(ev)) * self._kv_block_bytes
            store.reserve(nbytes)
            gathered = enqueue_block_pages_gather(self.kv_cache,
                                                  [b for b, _ in ev])
            store.put_async([h for _, h in ev],
                            lambda: fetch_block_pages(gathered), nbytes)
            self.stats.kv_demoted_blocks += len(ev)
            self._mirror_tier_counters()

    def _land_demotions(self, wait: bool = False) -> None:
        """File the demotions whose copy has landed (all of them, waiting,
        with ``wait``).  Called where the chip has work queued behind the
        gather — before each blocking sync (_sync) — so the host's filing
        hides behind it, and when the loop goes idle or stops."""
        store = self._kv_tiers
        if store is None or not store.in_flight_batches:
            return
        with PROF.phase("kv.demote"):
            store.land(wait)
            self._mirror_tier_counters()

    def _mirror_tier_counters(self) -> None:
        store = self._kv_tiers
        self.stats.kv_spilled_blocks = store.spilled_blocks
        self.stats.kv_tier_dropped_blocks = store.dropped_blocks
        self.stats.kv_demote_waited_blocks = store.waited_blocks

    def _read_demote_budget(self) -> None:
        """Device memory the gathered demotion batches may hold while a
        dispatch runs: what the allocator has never handed out
        (``bytes_limit - peak_bytes_in_use``), so a batch in flight plus
        the largest workspace seen so far still fit.  Read when warm-up
        ends (every dispatch shape has then run) or, in an engine never
        warmed, at the first demotion; no limit where the backend keeps
        no memory statistics (the CPU).  A batch over it is copied out
        before the next dispatch (store.put_async): the in-flight bound
        gives way, never the cache size."""
        stats = jax.local_devices()[0].memory_stats() or {}
        if self._kv_tiers is not None and "peak_bytes_in_use" in stats:
            self._kv_tiers.device_budget_bytes = max(
                0, stats["bytes_limit"] - stats["peak_bytes_in_use"])
            logger.info("KV demotion batches in flight may hold %d B of "
                        "device memory (limit %d, peak so far %d)",
                        self._kv_tiers.device_budget_bytes,
                        stats["bytes_limit"], stats["peak_bytes_in_use"])
        self._demote_budget_read = True

    def _sync(self, kind: str):
        """The span for one designated blocking ``device_get``.  What the
        host is about to wait for has this cycle's dispatch queued with
        or behind it, so landed demotions are filed first."""
        self._land_demotions()
        return self.devprof.sync(kind)

    def _drop_superseded_tier_entries(self, ids: list[int]) -> None:
        """Called right after a first allocate: the request's prefill is
        about to (re)compute and re-register every full block of ``ids``
        that wasn't served from HBM — any tier-store copies of those
        hashes are now superseded and must leave the store, or the
        exactly-one-tier invariant breaks the moment the recompute
        publishes the hash in HBM (and the stale host/PVC copies squat
        on budget forever).  The common case costs one chain walk per
        admission, which admission already pays twice (lookup +
        register)."""
        store = self._kv_tiers
        if store is None or len(store) == 0:
            return
        # registration hashes len//block_size full blocks, ONE more than
        # prefix_chain's lookup bound when the length is an exact block
        # multiple (lookup leaves a token uncached; registration doesn't)
        # — the appended dummy token raises the bound to the registered
        # chain without changing any hash
        for h in self.block_manager.prefix_chain(list(ids) + [0]):
            store.drop(h)

    def _begin_tier_restores(self) -> None:
        """Restore lower-tier prefix hits for head-of-queue requests: claim
        blocks (restore-in-flight: in no pool, un-evictable), take the
        pages out of the tier store, and dispatch the host->HBM scatter
        WITHOUT waiting on it — the copy overlaps whatever this cycle
        dispatches, and the request (held in RESTORING for the cycle)
        admits next cycle with the restored span as a prefix-cache hit,
        prefilling only the uncached suffix."""
        store = self._kv_tiers
        if not store or len(store) == 0 or not self.scheduler.waiting:
            return
        from tpuserve.runtime.kv_cache import scatter_block_pages
        bm = self.block_manager
        seats = self.config.scheduler.max_prefill_seqs
        for req in list(self.scheduler.waiting)[:seats]:
            if (req.state == RequestState.RESTORING
                    or req.num_prefilled > 0):
                continue
            ids = self._prefill_tokens(req)
            hashes = bm.prefix_chain(ids)
            if not hashes:
                continue
            shared, _ = bm.lookup_prefix(ids, count_stats=False)
            k = len(shared)
            span: list[int] = []
            while (k + len(span) < len(hashes)
                   and store.has(hashes[k + len(span)])):
                span.append(hashes[k + len(span)])
            if not span:
                continue
            # the request's total fresh-block demand is independent of how
            # much we restore (restored blocks are revived as shared at
            # allocate): everything past the HBM hit plus decode headroom
            # must fit, or the restore would just thrash the cached pool
            if bm.blocks_needed(len(ids)) - k + 1 > bm.num_free_blocks:
                continue
            blocks = bm.begin_restore(span)
            if blocks is None:
                continue
            pages = []
            for h in span:
                p = store.take(h)
                if p is None:       # unreadable spill entry mid-chain:
                    break           # restore only the intact prefix
                pages.append(p)
            if len(pages) < len(span):
                bm.abort_restore(blocks[len(pages):])
                blocks, span = blocks[:len(pages)], span[:len(pages)]
                # the unreadable entry was dropped as LOST KV — surface
                # the store's counter without waiting for the next demote
                self._mirror_tier_counters()
            if not blocks:
                continue
            # claiming restore blocks can itself evict cold cached blocks
            # — demote THEM before the scatter below overwrites the pages
            self._demote_evicted()
            self.kv_cache = scatter_block_pages(self.kv_cache, blocks,
                                                pages)
            req.state = RequestState.RESTORING
            self.flight.req_event(req.request_id, "RESTORING",
                                  blocks=len(blocks))
            self._restores[req.request_id] = (span, blocks,
                                              self.clock.monotonic())
            self.stats.kv_restores += 1
            self.stats.kv_restored_blocks += len(blocks)

    def _commit_tier_restores(self) -> None:
        """Publish last cycle's restored blocks as HBM prefix entries and
        release their requests back to WAITING.  Safe without a sync: the
        scatter was dispatched a cycle ago, and any prefill that reads
        the restored pages is dispatched after this — device execution
        order does the rest."""
        if not self._restores:
            return
        now = self.clock.monotonic()
        for rid, (span, blocks, t0) in self._restores.items():
            self.block_manager.commit_restore(span, blocks)
            req = self.requests.get(rid)
            if req is not None and req.state == RequestState.RESTORING:
                req.state = RequestState.WAITING
            if len(self.stats.restore_latencies) < 512:
                self.stats.restore_latencies.append(now - t0)
        self._restores.clear()

    def _note_step_tokens(self, actual: int, padded: int,
                          ctx_tokens: int, prefill: bool = False,
                          kv_by_page: bool = False) -> None:
        """Record one dispatch's real vs padded token counts (the
        padding-waste observability behind the
        ``tpuserve_step_padded/actual_tokens`` gauges) — ONE home so the
        phase-split and mixed paths count identically.  ``ctx_tokens`` is
        the work at the attention kernel's boundary: the context the
        dispatch's real rows attend, summed (``seq_lens`` for decode,
        window — at its first step — and verify; context + chunk length
        for prefill, chunk and mixed), from host-known integers.
        ``prefill``: a batched-prefill or prefill-chunk dispatch, counted
        in the prefill-only pair as well; ``kv_by_page``: its trunk wrote
        the tokens' K and V a page at a time."""
        if prefill:
            self.stats.prefill_tokens_total += actual
            self.stats.prefill_padded_tokens_total += padded
            self.stats.prefill_kv_tokens_paged_total += actual * kv_by_page
        elif self.model_cfg.is_mla:
            self.stats.kv_latent_tokens_attended_total += ctx_tokens
        if not prefill and self.model_cfg.lin_gate == "channel":
            served = actual * len(self.model_cfg.state_layers)
            self.stats.kda_state_row_layers += served
            self._step_kda += served
        self.stats.step_actual_tokens = actual
        self.stats.step_padded_tokens = padded
        self.stats.step_ctx_tokens = ctx_tokens
        self.stats.actual_tokens_total += actual
        self.stats.padded_tokens_total += padded
        if self._slo is not None:
            # padding-waste EWMA feeds the overload estimator: waste
            # derates delivered capacity, so pressure rises sooner on a
            # badly-bucketed workload (runtime/slo.py)
            self._slo.note_step(actual, padded)

    def _next_key(self) -> jax.Array:
        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    def _row_key(self, req: Request, extra_step: int = 0) -> tuple:
        """Per-row sampling key (salt, step): deterministic for seeded
        requests no matter which batches/windows the request lands in.
        Single source of truth — the fused-window and single-step paths
        must derive keys identically or seeded streams diverge between
        multi_step settings."""
        salt = (req.params.seed if req.params.seed is not None
                else self.config.seed ^ (hash(req.request_id) & 0x7FFFFFFF))
        step = len(req.output_token_ids) + extra_step
        return (np.uint32(salt & 0xFFFFFFFF), np.uint32(step))

    def _window_steps(self) -> int:
        """Fused-window size for the next dispatch: full multi_step in
        steady state, min_multi_step while arrivals are landing into a
        busy engine (EngineConfig.adaptive_multi_step) — a new request's
        admission wait is bounded by one window, so this is the p50-TTFT
        lever under load."""
        if self._adaptive_window and (
                self.clock.monotonic() - self._last_busy_arrival
                < self.config.adaptive_window_hold_s):
            return self._min_multi_step
        return self._multi_step

    def _try_reserve_window(self, reqs: list[Request], window: int) -> bool:
        """Reserve ``window`` KV slots past each request's written tokens
        (fused decode windows, speculative draft windows).  On failure the
        over-reserved blocks of earlier requests stay attached — they're
        used as the sequence grows or freed with it."""
        cap = self.cache_cfg.max_blocks_per_seq * self.cache_cfg.block_size
        if any(r.num_tokens - 1 + window > cap for r in reqs):
            return False
        with PROF.phase("block"):
            if self._host_batched:
                return self.block_manager.reserve_batch(
                    [r.request_id for r in reqs],
                    [r.num_tokens - 1 + window for r in reqs])
            try:
                for r in reqs:
                    self.block_manager.reserve(r.request_id,
                                               r.num_tokens - 1 + window)
            except MemoryError:
                return False
            return True

    # ---- batched block-manager boundary -------------------------------
    # ONE manager crossing per operation kind per cycle (the native
    # manager makes each a single C++ call; the Python manager loops
    # internally) — TPUSERVE_HOST_BATCHED=0 keeps the historical
    # per-request call pattern.

    def _bm_decode_shortfall(self, reqs: list[Request]) -> int:
        with PROF.phase("block"):
            if self._host_batched:
                return self.block_manager.decode_shortfall(
                    [r.request_id for r in reqs])
            bm = self.block_manager
            need = sum(bm.needs_new_block(r.request_id) for r in reqs)
            return max(need - bm.num_free_blocks, 0)

    def _bm_charge_decode(self, reqs: list[Request],
                          slots_out: np.ndarray) -> None:
        """Append one KV slot per row into ``slots_out[:len(reqs)]``.
        Capacity was already established by the shortfall probe; a miss
        here raises MemoryError like the historical append_slot loop."""
        with PROF.phase("block"):
            if self._host_batched:
                if self.block_manager.charge_decode(
                        [r.request_id for r in reqs], slots_out):
                    raise MemoryError("out of KV blocks on append")
                return
            for i, r in enumerate(reqs):
                slots_out[i] = self.block_manager.append_slot(r.request_id)

    def _bm_fill_tables(self, reqs: list[Request],
                        out: np.ndarray) -> None:
        """Write every row's block table into the zeroed (B, mb) dispatch
        buffer in one crossing."""
        with PROF.phase("block"):
            if self._host_batched:
                self.block_manager.fill_block_tables(
                    [r.request_id for r in reqs], out)
                return
            for i, r in enumerate(reqs):
                bt = self.block_manager.block_table(r.request_id)
                out[i, :len(bt)] = bt

    def _bm_advance(self, reqs: list[Request], steps: int) -> None:
        with PROF.phase("block"):
            if self._host_batched:
                self.block_manager.advance_batch(
                    [r.request_id for r in reqs], steps)
                return
            for r in reqs:
                self.block_manager.advance(r.request_id, steps)

    # ---- execution hooks (multi-host coordinators wrap these to broadcast
    # each step to follower processes before running it — parallel/multihost).
    # EVERY transformer.* / sample_tokens call in this class goes through a
    # hook; tests/test_multihost.py asserts that by AST so a new call site
    # can't silently bypass the lockstep protocol (the round-1 deadlock).

    def _lora_ad(self, reqs: list, B: int) -> "Optional[jnp.ndarray]":
        """Per-row one-hot adapter weights (B, n) for a batch — None when
        no adapter stack is loaded (the transformer then compiles without
        the lora contraction at all).  Padding/base rows are all-zero."""
        if not self._lora_names:
            return None
        ad = np.zeros((B, len(self._lora_names)), np.float32)
        for i, r in enumerate(reqs):
            if r.adapter_idx is not None:
                ad[i, r.adapter_idx] = 1.0
        return jnp.asarray(ad)

    def _row_kw(self, reqs: list, B: int) -> dict:
        """Conditional per-row kwargs for the exec hooks — ``ad=`` with an
        adapter stack, ``seats=`` with recurrent state (never both: the
        engine refuses that pair): an EMPTY dict otherwise, so multihost
        wrappers (whose hook signatures predate the args) are never passed
        them.  One home for the dance instead of six call sites."""
        if self.ssm_state is not None:
            return {"seats": self._seat_ids(reqs, B)}
        if not self._lora_names:
            return {}
        return {"ad": self._lora_ad(reqs, B)}

    def _seat_ids(self, reqs: list, B: int) -> jnp.ndarray:
        """Each row's seat in the recurrent-state pool, in row order;
        rows past ``reqs`` (padding, warm-up) share the trash seat."""
        seats = self.block_manager.seats
        ids = np.full((B,), seats.trash, np.int32)
        with PROF.phase("block"):
            for i, r in enumerate(reqs):
                ids[i] = seats.of(r.request_id)
        return jnp.asarray(ids)

    def _pool_kw(self, seats, rows: int) -> dict:
        """What a cache trunk of models/transformer.py takes beside the KV
        cache for a model with recurrent state: the seat pool (donated,
        like the cache) and each row's seat; nothing for any other model.
        ``seats`` None: a warm-up dispatch, every one of its ``rows`` on
        the trash seat.  (Keywords for a call the hook makes itself, not
        a wrapper around it: one more Python frame under every traced
        operation cost the warm-up of the dense cells 28 % on the chip,
        PERF.md, PR 32.)"""
        if self.ssm_state is None:
            return self._moe_kw
        if seats is None:
            seats = self._seat_ids([], rows)
        return {"ssm": self.ssm_state, "seats": seats, **self._moe_kw}

    def _keep_pool(self, res: tuple, tokens: int) -> tuple:
        """The trunk's result as every caller reads it: the updated seat
        pool, which a model with recurrent state returns after them, is
        kept here, and so is the routing that a model with expert layers
        returns last (still on the device: the counts are read with the
        dispatch's tokens, the logits rows' picks with their logprobs
        where a request asked for those).  ``tokens``: the rows of the
        trunk's flat token axis, from which the expert layers chose how
        to move their rows."""
        if self.ssm_state is None and not self._moe_counted:
            return res
        res = list(res)
        if self._moe_counted:
            counts, self._moe_picks, self._moe_prompt_picks = res.pop()
            # (the dense form a mesh takes gathers nothing)
            moves = 0 if self._moe_kw else sum(
                moe_plain_moves(self.model_cfg, tokens))
            self._moe_inflight.append(((self.flight.seq, moves), counts))
        if self.ssm_state is not None:
            self.ssm_state = res.pop()
        return tuple(res)

    def _moe_due(self, seq: int) -> tuple[list, list]:
        """``(step seqs, each with the plain moves of a row its dispatch
        moved; device arrays)`` of the routing counts of the
        dispatches up to step ``seq``, taken off the in-flight list: the
        device runs dispatches in order, so they are ready when that
        step's tokens are, and the read that fetches those tokens fetches
        them too (no sync of their own)."""
        n = 0
        while n < len(self._moe_inflight) \
                and self._moe_inflight[n][0][0] <= seq:
            n += 1
        due, self._moe_inflight = (self._moe_inflight[:n],
                                   self._moe_inflight[n:])
        return [s for s, _ in due], [a for _, a in due]

    def _moe_note(self, seqs: list, counts: list) -> None:
        """File routing counts the host has read: the totals behind
        ``tpuserve_moe_*`` and each dispatch's step record."""
        E = self.model_cfg.num_experts
        for (seq, moves), c in zip(seqs, counts):
            c = np.asarray(c, np.int64)
            rows, hits = int(c[:E].sum()), int(c[E])
            self.stats.moe_routed_rows += rows
            self.stats.moe_expert_hits += hits
            by_expert = self.stats.moe_expert_rows
            if by_expert is None or len(by_expert) != E:
                # (a swapped-in model with another number of experts
                # starts its own row)
                by_expert = np.zeros(E, np.int64)
            self.stats.moe_expert_rows = by_expert + c[:E]
            held = tuple(int(n) for n in c[E + 1:])   # a share's four
            if held:
                self.stats.moe_held_rows += held[0]
                self.stats.moe_held_hits += held[1]
                self.stats.moe_buffer_rows += held[2]
                self.stats.moe_held_pieces += held[3]
                self.stats.moe_group_rows += sum(held[4:])
            # (a share moves its buffer's rows, a whole layer every row)
            plain = (held[2] if held else rows) * moves
            self.stats.moe_row_moves_plain += plain
            self.flight.note_moe(seq, rows, hits, plain, *held)

    def _note_prompt_picks(self, req: Request, row: int, done: int,
                           take: int) -> None:
        """The prefill just enqueued computed positions ``done`` to
        ``done + take`` of ``req``'s prompt in rows ``row`` on of its flat
        token axis.  Where the request asked for logprobs (and the model
        has expert layers) those rows' picks are kept, on the device,
        for its first token's entry (:meth:`_file_prompt_picks`)."""
        if self._moe_prompt_picks is not None \
                and req.params.logprobs is not None:
            req.prompt_picks.append(
                (self._moe_prompt_picks, row, done, take))

    @staticmethod
    def _file_prompt_picks(r: Request) -> None:
        """Beside ``r``'s first token's logprob entry, just appended: the
        experts each expert layer picked for each position of the prompt,
        ``(prompt tokens, expert layers, k)``, -1 where no prefill
        computed a position (a prefix the cache already held).  With the
        tokens' own ``routed_experts`` that is every pick the tokens'
        logits went through.  A re-prefill after pre-emption files
        nothing: its first entry has the prompt's."""
        kept, r.prompt_picks = r.prompt_picks, []
        if not kept or len(r.logprobs) != 1:
            return
        first = np.asarray(kept[0][0])
        out = np.full((len(r.prompt_token_ids),) + first.shape[1:], -1,
                      first.dtype)
        for picks, row, done, take in kept:
            out[done:done + take] = np.asarray(picks)[row:row + take]
        r.logprobs[0]["prompt_routed_experts"] = out.tolist()

    def _note_seat_start(self, req: Request, n_tokens: int) -> None:
        """A sequence of a model with recurrent state starts (or starts
        AGAIN: nothing snapshots a seat, so a pre-empted or salvaged
        sequence rebuilds its state from a zeroed slot by prefilling its
        prompt and everything generated so far)."""
        if self.ssm_state is None:
            return
        self.stats.ssm_state_resets += 1
        if req.output_token_ids:
            self.stats.ssm_rebuilt_tokens += n_tokens

    def _exec_prefill(self, tokens, prompt_lens, slot_ids, ad=None,
                      seats=None):
        self.faults.check("prefill_dispatch", self._dispatch_rids)
        with self.devprof.dispatch("prefill", (tuple(tokens.shape),)):
            if self._pp > 1:
                from tpuserve.parallel.pipeline import pp_prefill
                return pp_prefill(self._pp_head, self._pp_stages,
                                  self.model_cfg, tokens, prompt_lens,
                                  slot_ids, self.kv_cache, mesh=self.mesh)
            return self._keep_pool(transformer.prefill(
                self.params, self.model_cfg, tokens, prompt_lens, slot_ids,
                self.kv_cache, ad, attn_impl=self.attn_impl,
                mesh=self._attn_mesh,
                **self._pool_kw(seats, tokens.shape[0])), tokens.size)

    def _exec_decode(self, tokens, positions, slot_ids, block_tables,
                     seq_lens, ad=None, seats=None):
        self.faults.check("decode_dispatch", self._dispatch_rids)
        with self.devprof.dispatch("decode", (tuple(tokens.shape),)):
            if self._pp > 1:
                from tpuserve.parallel.pipeline import pp_decode_step
                return pp_decode_step(self._pp_head, self._pp_stages,
                                      self.model_cfg, tokens, positions,
                                      slot_ids, block_tables, seq_lens,
                                      self.kv_cache, mesh=self.mesh)
            return self._keep_pool(transformer.decode_step(
                self.params, self.model_cfg, tokens, positions, slot_ids,
                block_tables, seq_lens, self.kv_cache, ad,
                attn_impl=self.attn_impl, mesh=self._attn_mesh,
                **self._pool_kw(seats, tokens.shape[0])), tokens.size)

    def _exec_prefill_chunk(self, tokens, ctx_lens, chunk_lens, slot_ids,
                            block_tables, ad=None, seats=None):
        self.faults.check("prefill_dispatch", self._dispatch_rids)
        if self._pp > 1:            # unreachable: gated at add_request
            raise RuntimeError("chunked prefill is not supported on the "
                               "pipeline engine")
        with self.devprof.dispatch("prefill_chunk", (tuple(tokens.shape),)):
            return self._keep_pool(transformer.prefill_chunk(
                self.params, self.model_cfg, tokens, ctx_lens, chunk_lens,
                slot_ids, block_tables, self.kv_cache, ad,
                attn_impl=self.attn_impl, mesh=self._attn_mesh,
                **self._pool_kw(seats, tokens.shape[0])), tokens.size)

    def _exec_decode_verify(self, tokens, ctx_lens, chunk_lens, slot_ids,
                            block_tables):
        self.faults.check("decode_dispatch", self._dispatch_rids)
        # Speculative decoding is single-process only (gated in __init__),
        # so no coordinator wraps this hook; it exists so the AST coverage
        # test can hold the "no direct transformer calls" line everywhere.
        # Verify windows are a handful of rows — below the Pallas kernel's
        # tiling minima and cheap for the segmented einsum — so this stays
        # on the reference attention regardless of attn_impl.
        with self.devprof.dispatch("verify", (tuple(tokens.shape),)):
            return transformer.decode_verify(
                self.params, self.model_cfg, tokens, ctx_lens, chunk_lens,
                slot_ids, block_tables, self.kv_cache)

    def _exec_decode_verify_sampled(self, tokens, ctx_lens, chunk_lens,
                                    slot_ids, block_tables, keys,
                                    temperature, top_k, top_p, min_p):
        self.faults.check("decode_dispatch", self._dispatch_rids)
        # sampled-batch twin of _exec_decode_verify: rejection-sampling
        # acceptance runs on device against the full verify logits
        with self.devprof.dispatch("verify_sampled", (tuple(tokens.shape),)):
            return transformer.decode_verify_sampled(
                self.params, self.model_cfg, tokens, ctx_lens, chunk_lens,
                slot_ids, block_tables, self.kv_cache, keys, temperature,
                top_k, top_p, min_p)

    def _exec_draft_propose(self, tokens, lens, *, k):
        self.faults.check("decode_dispatch", self._dispatch_rids)
        # Draft-model speculation is single-process only (gated with the
        # rest of speculation in __init__); the hook exists so the AST
        # coverage test can hold the "no direct transformer calls" line
        # everywhere (see _exec_decode_verify).
        with self.devprof.dispatch("draft", (tuple(tokens.shape), k)):
            return transformer.draft_propose(self._draft_params,
                                             self._draft_cfg, tokens, lens,
                                             k=k)

    def _exec_decode_multi(self, tokens, positions, block_tables, seq_lens,
                           active, keys, temperature, *, steps, mode,
                           top_k=None, top_p=None, min_p=None,
                           logprobs_n=0, counts=None, presence=None,
                           frequency=None, repetition=None, bias=None,
                           floor_bias=None, floor_remaining=None,
                           gstate=None, gmasks=None, gclass=None,
                           gnext=None, ad=None, seats=None):
        self.faults.check("decode_dispatch", self._dispatch_rids)
        with self.devprof.dispatch(
                "decode_multi", (tuple(tokens.shape), steps, mode,
                                 logprobs_n, gmasks is not None)):
            if self._pp > 1:
                from tpuserve.parallel.pipeline import pp_decode_multi
                return pp_decode_multi(
                    self._pp_head, self._pp_stages, self.model_cfg, tokens,
                    positions, block_tables, seq_lens, active, keys,
                    temperature, self.kv_cache, mesh=self.mesh, steps=steps,
                    mode=mode, top_k=top_k, top_p=top_p, min_p=min_p,
                    logprobs_n=logprobs_n, counts=counts, presence=presence,
                    frequency=frequency, repetition=repetition, bias=bias,
                    floor_bias=floor_bias, floor_remaining=floor_remaining)
            return self._keep_pool(transformer.decode_multi(
                self.params, self.model_cfg, tokens, positions, block_tables,
                seq_lens, active, keys, temperature, self.kv_cache, ad,
                steps=steps, mode=mode, top_k=top_k, top_p=top_p,
                min_p=min_p, logprobs_n=logprobs_n, counts=counts,
                presence=presence, frequency=frequency,
                repetition=repetition, bias=bias, floor_bias=floor_bias,
                floor_remaining=floor_remaining, gstate=gstate,
                gmasks=gmasks, gclass=gclass, gnext=gnext,
                attn_impl=self.attn_impl,
                mesh=self._attn_mesh, out_mesh=self.mesh,
                **self._pool_kw(seats, tokens.shape[0])), tokens.size)

    def _exec_forward_ragged(self, tokens, positions, slot_ids, row_seq,
                             block_tables, kv_lens, q_starts, q_lens,
                             meta, blk_seq, last_rows, ad=None, seats=None,
                             *, kind="mixed"):
        # ``kind``: "mixed", or "prefill" for a packed batched prefill
        # (_run_prefill) — its fault site and dispatch span stay prefill's,
        # and its program is built without the decode rows' part
        self.faults.check(kind + "_dispatch", self._dispatch_rids)
        # mixed batching and packed prefill are gated single-process/
        # non-pp in __init__, so no coordinator wraps this hook; it exists
        # for the AST coverage test's "no direct transformer calls" line
        # (_exec_decode_verify precedent).  No mesh arg: under tp
        # _ragged_attn is forced to "reference" (the ragged kernel has no
        # shard_map wrapper yet) and GSPMD partitions the reference
        # einsums on its own.
        with self.devprof.dispatch(kind, (tuple(tokens.shape),)):
            return self._keep_pool(transformer.forward_ragged(
                self.params, self.model_cfg, tokens, positions, slot_ids,
                row_seq, block_tables, kv_lens, q_starts, q_lens, meta,
                blk_seq, last_rows, self.kv_cache, ad,
                ragged_blk=self._ragged_blk, attn_impl=self._ragged_attn,
                decode_rows=kind != "prefill",
                **self._pool_kw(seats, q_lens.shape[0])), tokens.size)

    def _exec_sample(self, logits, keys, temperature, top_k, top_p, *,
                     min_p=None, mode):
        # sampling executables ride the decode site: they are part of the
        # same device round-trip a dispatch failure would take down
        self.faults.check("decode_dispatch", self._dispatch_rids)
        with self.devprof.dispatch("sample", (tuple(logits.shape), mode)):
            return sampling_ops.sample_tokens(
                logits, keys, temperature, top_k, top_p, min_p=min_p,
                mode=mode)

    # ---- prefill ------------------------------------------------------

    def _run_prefill(self, batch: ScheduledBatch) -> list[RequestOutput]:
        """One batched prefill.  The batch is what ``_schedule_prefill``
        admitted either way; the LAYOUT is one of two.  On the packed
        route (``self._packed_prefill``) the prompts lie on one flat token
        axis, each starting on a ragged-block boundary, and run through
        the ragged trunk with zero decode rows at the next rung of a fine
        ladder of token counts (scheduler.packed_prefill_bucket); a
        prefix-cache hit starts at its cached offset, as in _run_mixed.
        Elsewhere they run as a (power-of-two batch) x (power-of-two
        length of the longest) grid over the fresh K/V."""
        reqs = batch.requests
        self._dispatch_rids = tuple(r.request_id for r in reqs)
        self._step_kind = "prefill"
        packed = self._packed_prefill
        chunks = []                       # packed: (req, ids, done, take)
        if not packed:
            L = batch.padded_len
            B = next_power_of_2(len(reqs))
            tokens = np.zeros((B, L), np.int32)
            slot_ids = np.full((B, L), PAD_SLOT, np.int32)
            prompt_lens = np.ones((B,), np.int32)
        for i, req in enumerate(reqs):
            ids = self._prefill_tokens(req)
            self.faults.check("kv_alloc", (req.request_id,))
            shared, cached = self.block_manager.lookup_prefix(ids)
            self.block_manager.allocate(req.request_id, ids, shared_blocks=shared)
            self._note_seat_start(req, len(ids))
            self._drop_superseded_tier_entries(ids)
            if packed:
                # the shared blocks hold the cached tokens' KV already —
                # or, for a prefix first seen in THIS batch, get it from
                # this same dispatch: every layer writes all rows' K/V
                # before its attention reads a page
                chunks.append((req, ids, cached, len(ids) - cached))
            else:
                tokens[i, :len(ids)] = ids
                prompt_lens[i] = len(ids)
                slot_ids[i, :len(ids)] = self._token_slots(
                    req.request_id, 0, len(ids))
            self.flight.req_event(req.request_id, "PREFILL",
                                  tokens=len(ids),
                                  replay=bool(req.output_token_ids))
        if packed:
            B = self._prefill_seqs
            blk = self._ragged_blk
            arrays, kw = self._pack_ragged(
                [], None, chunks, lambda rows: packed_prefill_bucket(rows, blk), B)
            n_tok = sum(c[3] for c in chunks)
            padded = len(arrays[0])
            ctx_tok = sum(len(c[1]) for c in chunks)
        else:
            kw = self._row_kw(reqs, B)
            n_tok = ctx_tok = int(prompt_lens[:len(reqs)].sum())
            padded = B * L
        self._demote_evicted()
        with PROF.phase("dispatch"):
            if packed:
                logits, self.kv_cache = self._exec_forward_ragged(
                    *map(jnp.asarray, arrays), **kw, kind="prefill")
            else:
                logits, self.kv_cache = self._exec_prefill(
                    jnp.asarray(tokens), jnp.asarray(prompt_lens),
                    jnp.asarray(slot_ids), **kw)
        for i, req in enumerate(reqs):
            if packed:
                self._note_prompt_picks(req, int(arrays[6][i]),
                                        *chunks[i][2:])
            else:
                self._note_prompt_picks(req, i * L, 0, int(prompt_lens[i]))
        self.scheduler.mark_running(reqs)
        self.stats.num_prefill_steps += 1
        self.stats.prefill_packed_steps += packed
        # the trunk's own static test (forward_ragged, decode_rows=False)
        self._note_step_tokens(
            n_tok, padded, ctx_tok, prefill=True,
            kv_by_page=packed and kv_stream_by_page(
                self.kv_cache[0], self._ragged_blk, self._ragged_attn))
        return self._defer_first(logits, reqs, B)

    def _prefill_tokens(self, req: Request) -> list[int]:
        """Tokens to prefill — prompt plus, after a preemption, everything
        generated so far (the cache was dropped and must be rebuilt)."""
        return req.prompt_token_ids + req.output_token_ids

    def _token_slots(self, request_id: str, start: int, n: int,
                     block_table=None) -> np.ndarray:
        """Flat cache slots for token indices [start, start+n) — the
        vectorized form of ``block_manager.slot_for_token`` (a per-token
        Python loop costs ~10 ms of host time per batch-64 prefill, which
        is pure TTFT).  Pass ``block_table`` when the caller already
        fetched it to skip a second manager round-trip."""
        bs = self.cache_cfg.block_size
        if block_table is None:
            block_table = self.block_manager.block_table(request_id)
        bt = np.asarray(block_table, np.int64)
        t = np.arange(start, start + n)
        return (bt[t // bs] * bs + t % bs).astype(np.int32)

    def _run_prefill_chunk(self, batch: ScheduledBatch) -> list[RequestOutput]:
        """One fixed-size chunk of a long prompt (vLLM chunked-prefill
        analog): bounded activation memory and a single compiled shape for
        any prompt length.  The request re-enters the waiting queue until
        its last chunk, which samples the first token."""
        req = batch.requests[0]
        self._dispatch_rids = (req.request_id,)
        self._step_kind = "prefill_chunk"
        C = batch.padded_len
        ids = self._prefill_tokens(req)
        if req.num_prefilled == 0:
            self.faults.check("kv_alloc", (req.request_id,))
            shared, cached = self.block_manager.lookup_prefix(ids)
            self.block_manager.allocate(req.request_id, ids,
                                        shared_blocks=shared)
            self._note_seat_start(req, len(ids))
            self._drop_superseded_tier_entries(ids)
            # Compute skip: the shared blocks already hold valid KV for the
            # cached tokens, so prefill starts at the cached offset instead
            # of recomputing them (lookup always leaves >= 1 token to
            # compute, so the last chunk exists and samples the first
            # token).
            req.num_prefilled = cached
        done = req.num_prefilled
        chunk = ids[done:done + C]
        n = len(chunk)
        self.flight.req_event(req.request_id, "PREFILL_CHUNK",
                              done=done, tokens=n, total=len(ids))
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :n] = chunk
        slot_ids = np.full((1, C), PAD_SLOT, np.int32)
        bt = self.block_manager.block_table(req.request_id)
        slot_ids[0, :n] = self._token_slots(req.request_id, done, n,
                                            block_table=bt)
        block_tables = np.zeros((1, self.cache_cfg.max_blocks_per_seq),
                                np.int32)
        block_tables[0, :len(bt)] = bt
        kw = self._row_kw([req], 1)
        # the trunk's own static test (prefill_chunk): a chunk of whole
        # pages is written by page, on the word that it STARTS on one —
        # lookup_prefix returns whole blocks and every chunk before the
        # last is a full one
        by_page = kv_stream_by_page(self.kv_cache[0], C, self.attn_impl,
                                    self._attn_mesh)
        assert not by_page or done % self.cache_cfg.block_size == 0, done
        self._demote_evicted()
        with PROF.phase("dispatch"):
            logits, self.kv_cache = self._exec_prefill_chunk(
                jnp.asarray(tokens),
                jnp.asarray(np.asarray([done], np.int32)),
                jnp.asarray(np.asarray([n], np.int32)),
                jnp.asarray(slot_ids), jnp.asarray(block_tables), **kw)
        self._note_prompt_picks(req, 0, done, n)
        req.num_prefilled = done + n
        self.stats.num_prefill_steps += 1
        self._note_step_tokens(n, C, done + n, prefill=True,
                               kv_by_page=by_page)
        if req.num_prefilled < len(ids):
            # more chunks to go: back to the head of the queue (an earlier
            # prefill's first tokens are read behind this chunk)
            self.scheduler.waiting.appendleft(req)
            return self._flush_first(deferred=True)
        self.scheduler.mark_running([req])
        return self._defer_first(logits, [req], 1)

    # ---- mixed ragged prefill+decode ----------------------------------

    def _pack_ragged(self, decode_reqs: list, ahead, chunks: list, bucket,
                     B: int) -> tuple:
        """Lay one ragged dispatch out as ONE flat token stream — the host
        side of the Pallas kernel's layout contract
        (ops/pallas_ragged_attention.py), shared by mixed steps and packed
        batched prefills: ``decode_reqs``' rows first, densely packed
        (flat row == sequence index), the decode region padded to the
        ragged block, then each of ``chunks`` ((req, ids, done, take):
        ``take`` prompt tokens from offset ``done``) starting
        block-aligned, in order.  ``ahead``: rid -> a decode row's tokens
        still on the device (:meth:`_unread_rows`): the row stands that
        many positions past its host-known length, in the slot its block
        table (reserved that far by the caller) gives it, and its token
        is left 0 for the caller to take from the device; None for a
        packed prefill, whose stream has no decode region.  ``bucket``
        maps the rows used to the dispatched T; ``B`` is the fixed
        descriptor width.  Returns ``(arrays, kw)``: _exec_forward_ragged's
        positional arguments as numpy arrays, and its ``ad`` keyword when
        an adapter stack is loaded."""
        blk = self._ragged_blk
        n_dec = len(decode_reqs)
        n_dec_blocks = -(-n_dec // blk)
        # a mixed step's chunks start behind the decode region, whatever
        # rows it holds (transformer.decode_region: static in the program)
        cursor = self._decode_region if ahead is not None else 0
        starts = []
        for _, _, _, take in chunks:
            starts.append(cursor)
            cursor += -(-take // blk) * blk
        T = bucket(max(cursor, 1))
        mb = self.cache_cfg.max_blocks_per_seq
        tokens = np.zeros((T,), np.int32)
        positions = np.zeros((T,), np.int32)
        slot_ids = np.full((T,), PAD_SLOT, np.int32)
        row_seq = np.zeros((T,), np.int32)
        kv_lens = np.zeros((B,), np.int32)
        q_starts = np.full((B,), T, np.int32)
        q_lens = np.zeros((B,), np.int32)
        last_rows = np.zeros((B,), np.int32)
        block_tables = np.zeros((B, mb), np.int32)
        if decode_reqs:
            self.flight.req_event_many(
                tuple(r.request_id for r in decode_reqs), "WINDOW",
                steps=1, mixed=True)
            self._bm_fill_tables(decode_reqs, block_tables)
            n = np.arange(n_dec)
            extra = np.asarray([ahead.get(r.request_id, 0)
                                for r in decode_reqs])
            at = np.asarray([r.num_tokens for r in decode_reqs]) + extra - 1
            tokens[:n_dec] = [0 if e else r.output_token_ids[-1]
                              for r, e in zip(decode_reqs, extra)]
            positions[:n_dec] = at
            bs = self.cache_cfg.block_size
            slot_ids[:n_dec] = block_tables[n, at // bs] * bs + at % bs
            row_seq[:n_dec] = q_starts[:n_dec] = last_rows[:n_dec] = n
            kv_lens[:n_dec] = at + 1
            q_lens[:n_dec] = 1
        blk_seq = np.full((T // blk,), -1, np.int32)
        for si, ((req, ids, done, take), start) in enumerate(
                zip(chunks, starts), start=n_dec):
            rows = slice(start, start + take)
            tokens[rows] = ids[done:done + take]
            positions[rows] = done + np.arange(take)
            bt = self.block_manager.block_table(req.request_id)
            slot_ids[rows] = self._token_slots(req.request_id, done, take,
                                               block_table=bt)
            row_seq[rows] = si
            kv_lens[si] = done + take
            q_starts[si] = start
            q_lens[si] = take
            last_rows[si] = start + take - 1
            block_tables[si, :len(bt)] = bt
            blk_seq[start // blk:(start + -(-take // blk) * blk) // blk] = si
        meta = np.asarray([n_dec, n_dec_blocks], np.int32)
        kw = {}
        if self.ssm_state is not None:
            # one seat a descriptor row (a packed prefill: chunks only)
            kw["seats"] = self._seat_ids([c[0] for c in chunks], B)
        if self._lora_names:
            # per-ROW one-hot adapter weights: the ragged trunk applies
            # LoRA on the flat (T, H) stream, so each VALID row carries
            # its sequence's adapter; padding rows are filled explicitly
            # all-zero (= base model) rather than gathered through
            # row_seq, whose padding value of 0 would hand them sequence
            # 0's adapter
            ad_rows = np.zeros((T, len(self._lora_names)), np.float32)
            for i, r in enumerate(decode_reqs):
                if r.adapter_idx is not None:
                    ad_rows[i, r.adapter_idx] = 1.0
            for (req, _, _, take), start in zip(chunks, starts):
                if req.adapter_idx is not None:
                    ad_rows[start:start + take, req.adapter_idx] = 1.0
            kw["ad"] = jnp.asarray(ad_rows)
        return (tokens, positions, slot_ids, row_seq, block_tables, kv_lens,
                q_starts, q_lens, meta, blk_seq, last_rows), kw

    def _run_mixed(self, batch: ScheduledBatch) -> list[RequestOutput]:
        """One ragged mixed step (scheduler mixed mode): every running
        stream's decode row plus the scheduled prefill-chunk tokens run
        as ONE flat token batch through the ragged trunk
        (models/transformer.forward_ragged) — the weights are read once
        for both, decode streams get a token on every cycle even while
        prompts are being admitted, and the executable set is bucketed on
        the single flat-token dimension.

        Pipelined like the fused windows it stands between: a decode
        row's input token is taken ON THE DEVICE from the record in
        flight (the last window's tail, an earlier mixed step's tokens, a
        completed prompt's first token: ``_unread_rows``), the step's own
        tokens stay on the device as a window of one step
        (``PendingWindow``) and its completing prompts' as
        ``PendingFirst``, and the records in flight are read BEHIND this
        dispatch, so the chip's queue does not drain around a mixed step.
        Rows whose token the host shapes from history it holds
        (penalties, logprobs, guided, an active min_tokens floor), an
        engine that does not pipeline, and a pool too full to reserve
        ahead read everything first and sample at once, as the
        single-step path does.

        Row layout (the Pallas kernel's host contract,
        ops/pallas_ragged_attention.py): decode rows first, densely
        packed (flat row == sequence index), the decode region padded to
        the ragged block, each prefill chunk starting block-aligned;
        sequences are ordered decode -> completing prefills -> continuing
        prefills so the rows that sample a token this step are a prefix.
        """
        outputs = self._flush_pending()
        p = self._pending_window
        slack = p.steps if p is not None else 1
        defer = self._pipeline_decode and not any(
            _host_shapes_token(r, slack)
            for r in itertools.chain(batch.requests,
                                     (c[0] for c in batch.prefill_chunks)))
        if not defer:
            outputs += self._flush_window() + self._flush_first()
        p, pf = self._pending_window, self._pending_first
        decode_reqs, pend_idx, first_idx, ahead = self._unread_rows(
            batch.requests, p, pf)
        self._dispatch_rids = tuple(r.request_id for r in decode_reqs)
        self._step_kind = "mixed"
        # each decode row writes one KV slot past what is in flight: the
        # window discipline (reserve now, commit when the record is read)
        if not self._try_reserve_window(
                decode_reqs, 1 + max(ahead.values(), default=0)):
            # the pool is short: read what is in flight (a finish frees
            # blocks), then evict as _run_decode does
            outputs += self._flush_window() + self._flush_first()
            p = pf = None
            pend_idx = first_idx = ahead = {}
            decode_reqs = [r for r in decode_reqs if not r.finished]
            while self._bm_decode_shortfall(decode_reqs) > 0:
                victim = self.scheduler.preempt_last()
                self.stats.preemptions += 1
                if victim is None:
                    raise MemoryError("KV cache exhausted with a single "
                                      "sequence")
                decode_reqs = [r for r in decode_reqs if r is not victim]
            if not self._try_reserve_window(decode_reqs, 1):
                raise MemoryError("out of KV blocks on append")
        self.faults.check("kv_alloc", self._dispatch_rids)
        # prefill chunks: first chunk allocates (with prefix-cache
        # compute skip — prefill_chunk semantics); a request whose blocks
        # no longer fit (decode appends ate them) goes back to the head
        chunks = []                       # (req, ids, done, take)
        for req, n in batch.prefill_chunks:
            ids = self._prefill_tokens(req)
            if req.num_prefilled == 0:
                self.faults.check("kv_alloc", (req.request_id,))
                try:
                    shared, cached = self.block_manager.lookup_prefix(ids)
                    self.block_manager.allocate(req.request_id, ids,
                                                shared_blocks=shared)
                except MemoryError:
                    self.scheduler.waiting.appendleft(req)
                    continue
                self._drop_superseded_tier_entries(ids)
                req.num_prefilled = cached
            done = req.num_prefilled
            take = min(n, len(ids) - done)
            chunks.append((req, ids, done, take))
            self.flight.req_event(req.request_id, "PREFILL_CHUNK",
                                  done=done, tokens=take,
                                  total=len(ids), mixed=True)
        if not decode_reqs and not chunks:
            return outputs + self._flush_window() + self._flush_first()
        self._dispatch_rids = tuple(
            [r.request_id for r in decode_reqs]
            + [c[0].request_id for c in chunks])
        # completing chunks sample this step; order them before
        # continuing ones so the sampled rows form a prefix
        comp = [c for c in chunks if c[2] + c[3] == len(c[1])]
        cont = [c for c in chunks if c[2] + c[3] < len(c[1])]
        n_dec = len(decode_reqs)
        B = self._ragged_seqs
        blk = self._ragged_blk
        arrays, kw = self._pack_ragged(
            decode_reqs, ahead, comp + cont,
            lambda rows: packed_prefill_bucket(rows, blk), B)
        self._demote_evicted()
        with PROF.phase("dispatch"):
            tokens = jnp.asarray(arrays[0])
            for rec, idx in ((p, pend_idx), (pf, first_idx)):
                # rows whose last token is still on the device take it
                # there: no host round-trip
                at = [(i, idx[r.request_id])
                      for i, r in enumerate(decode_reqs)
                      if r.request_id in idx]
                if at:
                    gather = np.zeros(tokens.shape, np.int32)
                    use_host = np.ones(tokens.shape, bool)
                    rows, src = zip(*at)
                    gather[list(rows)] = src
                    use_host[list(rows)] = False
                    tokens = _select_tokens(rec.tail, jnp.asarray(gather),
                                            tokens, jnp.asarray(use_host))
            logits, self.kv_cache = self._exec_forward_ragged(
                tokens, *map(jnp.asarray, arrays[1:]), **kw)
        self.stats.num_mixed_steps += 1
        if decode_reqs:
            self.stats.num_decode_steps += 1
        if chunks:
            self.stats.num_prefill_steps += 1
            # the decode rows rode a dispatch that carried prompt tokens
            self.stats.decode_tokens_ridden += n_dec
            self._step_ridden = n_dec
        actual = n_dec + sum(c[3] for c in chunks)
        self._note_step_tokens(
            actual, len(arrays[0]),
            int(arrays[5][:n_dec].sum())
            + sum(done + take for _, _, done, take in chunks))
        # bookkeeping: chunk progress, requeue continuations, promote
        # completions to running BEFORE sampling/emit (finish() removes
        # from running; same order as _run_prefill_chunk)
        for ci, (req, _, done, take) in enumerate(chunks):
            self._note_prompt_picks(req, int(arrays[6][n_dec + ci]), done,
                                    take)
            req.num_prefilled = done + take
        for req, _, _, _ in reversed(cont):
            self.scheduler.waiting.appendleft(req)
        comp_reqs = [c[0] for c in comp]
        if comp_reqs:
            self.scheduler.mark_running(comp_reqs)
        emit_reqs = decode_reqs + comp_reqs
        if not emit_reqs:
            # continuing chunks alone: nothing to sample; what is in
            # flight is read behind this dispatch
            return outputs + self._flush_window() + self._flush_first(
                deferred=True)
        if defer:
            with PROF.phase("sample"):
                _, toks = self._sample_enqueue(logits, emit_reqs, B, ahead)
            # read the records this step chained off while it runs; a row
            # that turns out to have finished in them is baked into this
            # dispatch and dropped at its own flush (the window invariant)
            outputs += self._flush_window() + self._flush_first(deferred=True)
            seq = self.flight.seq
            if decode_reqs:
                self._pending_window = PendingWindow(
                    reqs=decode_reqs, toks=toks, steps=1, seq=seq)
            if comp_reqs:
                self._pending_first = PendingFirst(
                    reqs=comp_reqs, toks=toks, seq=seq, offset=n_dec)
            return outputs
        new_tokens = self._sample(logits, emit_reqs, B)
        now = self.clock.monotonic()
        for req in comp_reqs:
            if req.first_token_time is None:
                req.first_token_time = now
                self.stats.ttft_sum += now - req.arrival_time
                self.stats.ttft_count += 1
        # commit the written KV before emitting (a finish frees blocks)
        self._bm_advance(decode_reqs, 1)
        outputs += self._append_and_emit(decode_reqs, new_tokens[:n_dec])
        outputs += self._append_and_emit(comp_reqs, new_tokens[n_dec:],
                                         from_prefill=True)
        return outputs

    # ---- decode -------------------------------------------------------

    def _unread_rows(self, requests: list, p: Optional[PendingWindow],
                     pf: Optional[PendingFirst]) -> tuple:
        """``(rows, pend_idx, first_idx, ahead)`` for a dispatch chained
        off the records in flight: rid -> its row in the in-flight window
        ``p`` / the pending first tokens ``pf`` (a row is in one record at
        most), how many of its tokens are sampled on the device but not
        read yet, and of ``requests`` the live rows that may take another
        token: a request whose unread tokens reach max_tokens /
        max_model_len (host-known) finishes when its record is flushed."""
        first_idx = pf.rows() if pf is not None else {}
        ahead = dict.fromkeys(first_idx, 1)
        pend_idx = p.rows() if p is not None else {}
        if p is not None:
            ahead.update(dict.fromkeys(pend_idx, p.steps))
        rows = [r for r in requests if not r.finished
                and (r.request_id not in ahead
                     or (len(r.output_token_ids) + ahead[r.request_id]
                         < r.params.max_tokens
                         and r.num_tokens + ahead[r.request_id]
                         < self.max_seq_len))]
        return rows, pend_idx, first_idx, ahead

    def _run_decode_multi(self, batch: ScheduledBatch
                          ) -> Optional[list[RequestOutput]]:
        """Run a ``multi_step``-token decode window in one dispatch
        (transformer.decode_multi): sampled tokens feed the next iteration
        on device, the host reads the whole (B, S) window once.  Tokens a
        request cannot use (EOS / max_tokens / stop string mid-window) are
        dropped at emit — bounded overrun, the vLLM-TPU/JetStream tradeoff.

        Returns None — before any side effect — only when the batch
        needs per-step host guided validation: a guided request whose
        grammar didn't FSM-compile (candidate substitution), a
        mixed-grammar batch, or guided rows on the pp / multi-host
        trunks.  Everything else — top-k/top-p/min-p truncation,
        sampled-token logprobs, presence/frequency/repetition penalties,
        logit_bias, the min_tokens floor (lifted mid-window by
        floor_remaining), and grammar-FSM guided masking (state carried
        on device across iterations, runtime/grammar/) — runs INSIDE
        the window.  Falls back to the single-step path internally when
        cache capacity can't cover the window.
        """
        S = self._window_steps()
        # Truncated sampling, logprobs, penalties (on-device count
        # carry), logit_bias (dense per-row add), the min_tokens floor
        # (per-step lift via floor_remaining) and grammar-FSM guided
        # masking (runtime/grammar/ state carry) all run INSIDE the
        # window.  Only guided requests WITHOUT a compiled FSM — specs
        # the compiler couldn't bound — still need per-step host
        # validation (candidate substitution).
        if any(r.request_id in self._guided for r in batch.requests):
            # substitution-path guided rows (spec didn't FSM-compile)
            # need per-step host validation; a guided request in NEITHER
            # dict dropped its constraint mid-stream and no longer gates
            return None
        gset = [r for r in batch.requests
                if r.request_id in self._guided_fsm]
        if gset:
            if self._pp > 1 or jax.process_count() > 1:
                # the staged-trunk and lockstep-broadcast hook signatures
                # don't carry the FSM tables yet — per-step fallback
                return None
            if len({id(self._guided_fsm[r.request_id][0])
                    for r in gset}) > 1:
                # one grammar table set per dispatch; mixed-grammar
                # batches fall back to per-step (rare co-batching case)
                return None
        outputs = self._flush_pending()
        if (self._pending_window is not None
                and self._pending_window.gstate is None
                and any(r.request_id in self._guided_fsm
                        for r in self._pending_window.reqs)):
            # a guided row chained from a window that carried no FSM
            # states (possible only across an adoption/config edge):
            # resolve it first so this dispatch reads fresh host states
            outputs += self._flush_window()
        # logit_bias is static per request — safe under pipelining; the
        # COUNT-dependent penalties and the LENGTH-dependent min_tokens
        # floor need the staleness flush below (host history/length lag
        # the in-flight window)
        if (self._pending_window is not None
                and any(r.params.needs_penalties
                        or (r.params.needs_min_tokens
                            and r.params.min_tokens_active(
                                len(r.output_token_ids),
                                slack=self._pending_window.steps))
                        for r in batch.requests)):
            # penalty counts come from HOST token history; under pipelined
            # decode the in-flight window's tokens aren't in it yet, so a
            # penalized window chained off the pending one would sample a
            # whole window blind to its own previous tokens.  Resolve the
            # window first — the same staleness rule the per-step path
            # enforces (pipeline_ok in _run_decode).
            outputs += self._flush_window()
        pf = self._pending_first
        if pf is not None and any(
                r.params.needs_penalties or r.params.guided is not None
                or (r.params.needs_min_tokens
                    and r.params.min_tokens_active(
                        len(r.output_token_ids), slack=1))
                for r in pf.reqs):
            # the same staleness rule for a prefill's first token still on
            # the device: penalty counts and the min_tokens floor read host
            # history, a guided row's FSM mirror advances by the token
            outputs += self._flush_first()
            pf = None
        p = self._pending_window
        reqs, pend_idx, first_idx, ahead = self._unread_rows(
            batch.requests, p, pf)
        if not reqs:
            return outputs + self._flush_window() + self._flush_first()
        self._dispatch_rids = tuple(r.request_id for r in reqs)
        self._step_kind = "window"
        self.faults.check("kv_alloc", self._dispatch_rids)
        # Rows continuing from the in-flight window need p.steps extra KV
        # slots (its advance hasn't run yet), rows of a pending prefill
        # one; reserving the conservative bound for every row
        # over-reserves the others by that many slots, which stay
        # attached and get used as the sequence grows.
        window_need = S + max(p.steps if p is not None else 0,
                              1 if pf is not None else 0)
        if not self._try_reserve_window(reqs, window_need):
            # _run_decode flushes the in-flight window before preempting
            return outputs + self._run_decode(batch)
        B = self.scheduler.decode_bucket(len(reqs))
        host_tokens = np.zeros((B,), np.int32)
        use_host = np.ones((B,), bool)
        gather = np.zeros((B,), np.int32)
        not_first = np.ones((B,), bool)
        gather_first = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        seq_lens = np.ones((B,), np.int32)
        active = np.zeros((B,), bool)
        keys = np.zeros((B, 2), np.uint32)
        temperature = np.zeros((B,), np.float32)
        gstate_host = np.full((B,), -1, np.int32)
        block_tables = np.zeros((B, self.cache_cfg.max_blocks_per_seq),
                                np.int32)
        for i, r in enumerate(reqs):
            pi = pend_idx.get(r.request_id)
            extra = ahead.get(r.request_id, 0)
            nt = r.num_tokens + extra
            if pi is not None:
                # input token = last column of the in-flight window,
                # gathered on device — no host round-trip
                use_host[i] = False
                gather[i] = pi
            elif extra:
                # input token = the pending prefill's sampled token
                not_first[i] = False
                gather_first[i] = first_idx[r.request_id]
            else:
                host_tokens[i] = r.output_token_ids[-1]
            positions[i] = nt - 1
            seq_lens[i] = nt
            active[i] = True
            keys[i] = self._row_key(r, extra_step=extra)
            temperature[i] = r.params.temperature
            gent = self._guided_fsm.get(r.request_id)
            if gent is not None:
                # chained rows overwrite this with the device gstate via
                # the same use_host/gather select as their input tokens
                gstate_host[i] = gent[1]
        # recorded at DISPATCH (entered a fused window), so a fault
        # at the flush still shows the window in the timeline;
        # consumed tokens land in FINISHED.  One batched ring entry
        # for the whole dispatch, so the cost does not grow with
        # the batch.
        self.flight.req_event_many(self._dispatch_rids, "WINDOW",
                                   steps=S)
        self._bm_fill_tables(reqs, block_tables)
        mode = ("greedy" if all(r.params.greedy for r in reqs)
                else "temperature"
                if not any(r.params.needs_truncation for r in reqs)
                else "full")
        kw = self._row_kw(reqs, B)
        if mode == "full":
            top_k, top_p, min_p = self._truncation_arrays(reqs, B)
            kw.update(top_k=jnp.asarray(top_k), top_p=jnp.asarray(top_p),
                      min_p=jnp.asarray(min_p))
        lp_n = 0
        if any(r.params.logprobs is not None for r in reqs):
            # FIXED at MAX_LOGPROBS, not the batch's max: logprobs_n is a
            # static jit arg, so a per-batch value would compile a fresh
            # window trunk per distinct N mid-serving (the 47 s stall
            # class warmup exists to prevent); one variant per
            # (mode, steps) instead, pre-warmed, sliced per request at
            # flush
            lp_n = self.MAX_LOGPROBS
            kw["logprobs_n"] = lp_n
        if any(r.params.needs_penalties or r.params.needs_logit_bias
               or (r.params.needs_min_tokens
                   and r.params.min_tokens_active(len(r.output_token_ids)))
               for r in reqs):
            # ONE executable family serves penalties AND logit_bias:
            # counts/bias are derived in SMALL bucketed executables
            # (token_counts / the bias scatter) so the fixed-shape window
            # trunk never recompiles per history- or bias-width bucket;
            # whichever of the two isn't in play rides along as zeros.
            from tpuserve.ops.sampling import token_counts
            V = self.model_cfg.vocab_size
            out_tokens, mask, presence, frequency, repetition = \
                self._penalty_arrays(reqs, B)
            bias_ids, bias_vals = self._logit_bias_arrays(reqs, B, V)
            kw.update(
                counts=token_counts(jnp.asarray(out_tokens),
                                    jnp.asarray(mask), V),
                presence=jnp.asarray(presence),
                frequency=jnp.asarray(frequency),
                repetition=jnp.asarray(repetition),
                bias=sampling_ops.apply_logit_bias(
                    jnp.zeros((B, V), jnp.float32),
                    jnp.asarray(bias_ids), jnp.asarray(bias_vals)))
            f_ids, f_vals, f_rem = self._min_tokens_arrays(reqs, B, V)
            kw.update(
                floor_bias=sampling_ops.apply_logit_bias(
                    jnp.zeros((B, V), jnp.float32),
                    jnp.asarray(f_ids), jnp.asarray(f_vals)),
                floor_remaining=jnp.asarray(f_rem))
        gfsm = next((self._guided_fsm[r.request_id][0] for r in reqs
                     if r.request_id in self._guided_fsm), None)
        if gfsm is not None:
            gm, gc, gn = self._fsm_device_tables(gfsm)
            if p is not None and p.gstate is not None:
                # chained rows' FSM states live on device (the in-flight
                # window's final carry) — select them exactly like the
                # input tokens; fresh rows take the host mirror
                gstate_in = _select_tokens(p.gstate, jnp.asarray(gather),
                                           jnp.asarray(gstate_host),
                                           jnp.asarray(use_host))
            else:
                gstate_in = jnp.asarray(gstate_host)
            kw.update(gstate=gstate_in, gmasks=gm, gclass=gc, gnext=gn)
            self.stats.guided_fsm_windows += 1
        self._demote_evicted()
        with PROF.phase("dispatch"):
            tokens = jnp.asarray(host_tokens)
            if p is not None:
                tokens = _select_tokens(p.tail, jnp.asarray(gather),
                                        tokens, jnp.asarray(use_host))
            if pf is not None:
                # the second device source, over the first select's result
                tokens = _select_tokens(pf.toks, jnp.asarray(gather_first),
                                        tokens, jnp.asarray(not_first))
            res = self._exec_decode_multi(
                tokens, jnp.asarray(positions),
                jnp.asarray(block_tables), jnp.asarray(seq_lens),
                jnp.asarray(active), jnp.asarray(keys),
                jnp.asarray(temperature), steps=S, mode=mode, **kw)
        toks, self.kv_cache = res[0], res[1]
        ri = 2
        window_lp = None
        if lp_n:
            window_lp = res[ri]
            ri += 1
        gstate_out = res[ri] if gfsm is not None else None
        self.stats.num_decode_steps += S
        self._note_step_tokens(len(reqs) * S, B * S,
                               int(seq_lens[:len(reqs)].sum()))
        if S < self._multi_step:
            # counted at the dispatch, not in _window_steps(): eligibility
            # bailouts above return before any window actually shrinks
            self.stats.latency_windows += 1
        if self._pipeline_decode:
            # resolve the PREVIOUS window while this one runs on device.
            # A request that turns out to have finished inside ``p`` (EOS /
            # stop string) is already baked into this dispatch: its rows
            # compute into blocks freed at the flush — safe because device
            # executions run in dispatch order through the donated cache,
            # so any later owner of those blocks overwrites the stale slots
            # (same invariant the single-step pipeline established for its
            # one-slot overrun) — and its tokens are dropped at the next
            # flush.  A row that ends on its prefill's first token (read
            # here, after the window's) is the same case.
            outputs += self._flush_window() + self._flush_first(deferred=True)
            self._pending_window = PendingWindow(reqs=list(reqs), toks=toks,
                                                 steps=S, lp=window_lp,
                                                 gstate=gstate_out,
                                                 seq=self.flight.seq)
            return outputs
        # synchronous: flush the just-dispatched window immediately (one
        # code path for the KV-commit-before-emit and overrun invariants)
        self._pending_window = PendingWindow(reqs=list(reqs), toks=toks,
                                             steps=S, lp=window_lp,
                                             gstate=gstate_out,
                                             seq=self.flight.seq)
        return outputs + self._flush_window()

    def _flush_window(self) -> list[RequestOutput]:
        """Read the in-flight fused window's tokens and run the deferred
        host-side bookkeeping (KV commit, append, detokenize, stop checks,
        emission).  Rows whose request finished while the window was in
        flight (EOS in the previous window, abort) are dropped whole — all
        their tokens are overrun."""
        p, self._pending_window = self._pending_window, None
        if p is None:
            return []
        # fault site: the device->host sync that resolves a window is its
        # own failure point (a wedged transfer).  The window is
        # already detached above, so a fault here drops it orphaned —
        # exactly what the salvage path expects to find.
        self.faults.check("window_flush",
                          tuple(r.request_id for r in p.reqs))
        due, moe = self._moe_due(p.seq)
        with self._sync("window"):
            # tpulint: sync-ok(THE designated sync: one device_get per S-token window is the whole fused-window design)
            toks_h, moe = jax.device_get((p.toks, moe))
        toks_h = np.asarray(toks_h).reshape(len(toks_h), p.steps)
        self._moe_note(due, moe)
        lp_h = None
        if p.lp is not None:
            with self._sync("window"):
                # tpulint: sync-ok(rides the same window-flush sync point; logprob arrays resolve with the tokens)
                lp_h = tuple(np.asarray(x) for x in jax.device_get(p.lp))
        outputs: list[RequestOutput] = []
        # Commit written KV BEFORE emitting (finish frees blocks mid-loop);
        # zombie rows' blocks were already freed at the previous flush.
        self._bm_advance([r for r in p.reqs if not r.finished], p.steps)
        with PROF.phase("detokenize"):
            for i, r in enumerate(p.reqs):
                if r.finished:
                    self.stats.window_overrun_tokens += p.steps
                    continue
                if (self._host_batched and not r.params.stop
                        and r.request_id not in self._guided):
                    # window-batched detokenize-and-emit: ONE delta and
                    # ONE RequestOutput per row per window (token- and
                    # text-identical to the per-token path — pinned by
                    # tests/test_host_hotpath.py).  Rows with stop
                    # strings keep the per-token path: a stop match must
                    # truncate at its exact TOKEN position.
                    outputs.append(self._emit_window_row(
                        r, toks_h[i], p.steps, lp_h, i))
                    continue
                for s in range(p.steps):
                    if lp_h is not None and r.params.logprobs is not None:
                        # recorded BEFORE emit (same order as the per-step
                        # path: _record_logprobs then _append_and_emit), and
                        # only for CONSUMED tokens — overrun rows break out
                        # below before recording theirs
                        self._append_logprob_entry(
                            r, int(toks_h[i, s]), *(a[i, s] for a in lp_h))
                    out = self._emit_one(r, int(toks_h[i, s]))
                    outputs.append(out)
                    if out.finished:
                        self.stats.window_overrun_tokens += p.steps - 1 - s
                        break
        return outputs

    def _emit_window_row(self, req: Request, row, steps: int,
                         lp_h, li: int) -> RequestOutput:
        """Window-batched twin of the per-token ``_emit_one`` loop for one
        row: decide the consumed token count by scanning ints (EOS /
        stop_token_ids / max_tokens / max_model_len / grammar-FSM
        completion — the same rules ``check_stop`` and the FSM advance
        apply per token, in the same order), then detokenize the consumed
        tokens in ONE ``add_many`` call and build ONE RequestOutput.
        Content is identical to per-token flushing: same tokens appended,
        same concatenated text, same finish reason — only the chunk
        granularity changes (one multi-token chunk per window).  Callers
        guarantee no stop strings and no substitution-path guided state on
        this row."""
        prm = req.params
        n0 = len(req.output_token_ids)
        fsm_ent = (self._guided_fsm.get(req.request_id)
                   if prm.guided is not None else None)
        # output-length cap this window can reach (>= 1: rows already at
        # their cap never get another window — dispatch-gated)
        cap = min(prm.max_tokens, self.max_seq_len - req.num_prompt_tokens)
        limit = min(steps, cap - n0)
        reason = None
        if fsm_ent is None and not prm.min_tokens_active(n0 + 1):
            # fast scan (the common case): membership against the
            # precomputed stop set over a C-converted token list — no
            # per-token Python method calls.  min_tokens_active is
            # monotone in n, so inactive at n0+1 means inactive for the
            # whole window.
            # tpulint: sync-ok(row is a host numpy slice of the already-flushed window; .tolist() is a C list build, not a device sync)
            toks_list = row[:limit].tolist()
            if prm.stop_token_ids:
                stopset = (set(prm.stop_token_ids) if prm.ignore_eos
                           else self._eos_ids | set(prm.stop_token_ids))
            else:
                stopset = None if prm.ignore_eos else self._eos_ids
            if stopset is not None:
                for s, tok in enumerate(toks_list):
                    if tok in stopset:
                        reason = FinishReason.STOP
                        toks_list = toks_list[:s + 1]
                        break
            if reason is None and limit >= cap - n0:
                reason = FinishReason.LENGTH
            consumed = len(toks_list)
        else:
            # grammar-FSM / min-tokens rows: per-token rule order exactly
            # as _emit_one applies it (FSM advance, then check_stop)
            consumed = 0
            for s in range(limit):
                tok = int(row[s])
                n = n0 + s + 1
                consumed = s + 1
                if fsm_ent is not None:
                    fsm = fsm_ent[0]
                    ns = fsm.advance(fsm_ent[1], tok)
                    if ns < 0:
                        # off-grammar token (masking bypassed): drop the
                        # constraint rather than track a corrupt state
                        self._guided_fsm.pop(req.request_id, None)
                        fsm_ent = None
                    else:
                        fsm_ent[1] = ns
                        if fsm.complete[ns] and tok not in self._eos_ids:
                            reason = FinishReason.STOP
                if reason is None:
                    # check_stop over host counters (request.check_stop
                    # semantics at output length n)
                    if (not prm.min_tokens_active(n)
                            and ((not prm.ignore_eos
                                  and tok in self._eos_ids)
                                 or tok in prm.stop_token_ids)):
                        reason = FinishReason.STOP
                    elif n >= cap:
                        reason = FinishReason.LENGTH
                if reason is not None:
                    break
            toks_list = [int(t) for t in row[:consumed]]
        if lp_h is not None and prm.logprobs is not None:
            # consumed tokens only, appended before the emit bookkeeping —
            # the per-token path's entry order
            for s in range(consumed):
                self._append_logprob_entry(req, toks_list[s],
                                           *(a[li, s] for a in lp_h))
        req.output_token_ids.extend(toks_list)
        # progress resets the salvage budget, exactly like _emit_one
        req.num_salvages = 0
        self.stats.generated_tokens += consumed
        delta = self._detok[req.request_id].add_many(toks_list)
        req.output_text += delta
        finished = reason is not None
        if finished:
            if req.stop_held:
                # unreachable on this path (no stop strings) but kept in
                # lockstep with _emit_one: held text is real output
                req.output_text += req.stop_held
                delta += req.stop_held
                req.stop_held = ""
            req.finish_reason = reason
            req.finish_time = self.clock.monotonic()
            self.scheduler.finish(req)
            self.stats.requests_finished += 1
            self.stats.window_overrun_tokens += steps - consumed
            self.flight.req_event(req.request_id, "FINISHED",
                                  cause=reason.value,
                                  output_tokens=len(req.output_token_ids))
            self._detok.pop(req.request_id, None)
            self._guided.pop(req.request_id, None)
            self._guided_fsm.pop(req.request_id, None)
            self._guided_plan.pop(req.request_id, None)
        return RequestOutput(
            request_id=req.request_id, new_token_ids=toks_list,
            new_text=delta, finished=finished, finish_reason=reason,
            num_prompt_tokens=req.num_prompt_tokens,
            num_output_tokens=len(req.output_token_ids))

    def _run_decode(self, batch: ScheduledBatch) -> list[RequestOutput]:
        outputs: list[RequestOutput] = []
        # resolve any in-flight fused window first: this path mutates
        # request/block state (append_slot, preemption) that must see the
        # window's finishes, and a row's first token as its input
        outputs += self._flush_window() + self._flush_first()
        reqs = [r for r in batch.requests if not r.finished]
        pending = self._pending
        # Penalties/logprobs read host-side token history, which is one step
        # stale under the pipeline — those batches run synchronously.
        pipeline_ok = self._pipeline_decode and not any(
            _host_shapes_token(r, 1) for r in reqs)
        if pending is not None and not pipeline_ok:
            outputs += self._flush_pending()
            pending = None
            reqs = [r for r in reqs if not r.finished]
        pend_idx: dict[str, int] = {}
        if pending is not None:
            pend_idx = {r.request_id: i for i, r in enumerate(pending.reqs)}
            # host-known length rules: a request whose in-flight token
            # completes max_tokens / max_model_len must not run another step
            reqs = [r for r in reqs
                    if r.request_id not in pend_idx
                    or (len(r.output_token_ids) + 1 < r.params.max_tokens
                        and r.num_tokens + 1 < self.max_seq_len)]
        if not reqs:
            return outputs + self._flush_pending()
        self._dispatch_rids = tuple(r.request_id for r in reqs)
        # Reserve capacity up front (preempting if needed), THEN append —
        # the slot charge mutates per-seq state, so it must not fail
        # mid-batch.  Probe + charge + table fill are each ONE manager
        # crossing per cycle (_bm_* helpers), not 2-3 per row.
        while self._bm_decode_shortfall(reqs) > 0:
            if self._pending is not None:
                # resolve in-flight results before evicting anyone — some of
                # these requests may already be finished
                outputs += self._flush_pending()
                pending = None
                pend_idx = {}
                reqs = [r for r in reqs if not r.finished]
                if not reqs:
                    return outputs
                continue
            victim = self.scheduler.preempt_last()
            self.stats.preemptions += 1
            if victim is None:
                raise MemoryError("KV cache exhausted with a single sequence")
            reqs = [r for r in reqs if r is not victim]
            if not reqs:
                return outputs
        self._dispatch_rids = tuple(r.request_id for r in reqs)
        self._step_kind = "decode"
        self.faults.check("kv_alloc", self._dispatch_rids)
        B = self.scheduler.decode_bucket(len(reqs))
        host_tokens = np.zeros((B,), np.int32)
        use_host = np.ones((B,), bool)
        gather = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        slot_arr = np.full((B,), PAD_SLOT, np.int32)
        seq_lens = np.ones((B,), np.int32)
        block_tables = np.zeros((B, self.cache_cfg.max_blocks_per_seq), np.int32)
        self._bm_charge_decode(reqs, slot_arr)
        self._bm_fill_tables(reqs, block_tables)
        in_flight = {}
        for i, req in enumerate(reqs):
            pend = pend_idx.get(req.request_id)
            nt = req.num_tokens + (0 if pend is None else 1)
            if pend is None:
                host_tokens[i] = req.output_token_ids[-1]
            else:
                use_host[i] = False
                gather[i] = pend
                in_flight[req.request_id] = 1
            positions[i] = nt - 1
            seq_lens[i] = nt
        self.flight.req_event_many(self._dispatch_rids, "WINDOW",
                                   steps=1)
        kw = self._row_kw(reqs, B)
        self._demote_evicted()
        with PROF.phase("dispatch"):
            if pending is not None:
                tokens = _select_tokens(pending.toks, jnp.asarray(gather),
                                        jnp.asarray(host_tokens),
                                        jnp.asarray(use_host))
            else:
                tokens = jnp.asarray(host_tokens)
            logits, self.kv_cache = self._exec_decode(
                tokens, jnp.asarray(positions), jnp.asarray(slot_arr),
                jnp.asarray(block_tables), jnp.asarray(seq_lens), **kw)
        self.stats.num_decode_steps += 1
        self._note_step_tokens(len(reqs), B,
                               int(seq_lens[:len(reqs)].sum()))
        if pipeline_ok:
            if any(r.params.needs_logit_bias for r in reqs):
                # static per request (no host token history), so safe on
                # the pipelined path — unlike penalties
                logits = self._apply_logit_bias(logits, reqs, B)
            with PROF.phase("sample"):
                toks = self._sample_modes(logits, reqs, B, in_flight)
            # resolve the PREVIOUS step while this one runs on device
            outputs += self._flush_pending()
            self._pending = PendingDecode(reqs=list(reqs), toks=toks,
                                          seq=self.flight.seq)
            return outputs
        new_tokens = self._sample(logits, reqs, B)
        return outputs + self._append_and_emit(reqs, new_tokens)

    def _run_decode_spec(self, batch: ScheduledBatch) -> list[RequestOutput]:
        """Speculative decode step: n-gram drafts verified in one pass
        (runtime/spec.py).  Emits 1..k+1 tokens per sequence per weight
        pass; falls back to the normal decode path when nothing can be
        proposed or the draft window doesn't fit."""
        from tpuserve.runtime import spec as spec_mod
        outputs: list[RequestOutput] = []
        if self._pending is not None:           # spec steps are synchronous
            outputs += self._flush_pending()
        outputs += self._flush_window() + self._flush_first()
        reqs = [r for r in batch.requests if not r.finished]
        if not reqs:
            return outputs
        self._dispatch_rids = tuple(r.request_id for r in reqs)
        self._step_kind = "spec"
        k = self._spec.num_draft_tokens
        K = k + 1
        if self._draft_params is not None:
            drafts = self._draft_propose(reqs, k)
        else:
            drafts = [spec_mod.ngram_propose(
                r.prompt_token_ids + r.output_token_ids, k,
                self._spec.max_ngram, self._spec.min_ngram,
                self._spec.max_lookback) for r in reqs]
        # The verify pass costs every row ~(k+1)x a decode step; it only
        # pays when enough of the batch actually has drafts to accept.
        coverage = sum(1 for d in drafts if d) / len(drafts)
        if (coverage < self._spec.min_batch_coverage
                or not self._try_reserve_window(reqs, K)):
            return outputs + self._run_decode(batch)
        base = [r.num_tokens - 1 for r in reqs]  # input-token positions
        B = self.scheduler.decode_bucket(len(reqs))
        tokens = np.zeros((B, K), np.int32)
        slot_ids = np.full((B, K), PAD_SLOT, np.int32)
        ctx_lens = np.zeros((B,), np.int32)
        chunk_lens = np.ones((B,), np.int32)
        block_tables = np.zeros((B, self.cache_cfg.max_blocks_per_seq),
                                np.int32)
        self._bm_fill_tables(reqs, block_tables)
        for i, r in enumerate(reqs):
            d = drafts[i]
            tokens[i, 0] = r.output_token_ids[-1]
            tokens[i, 1:1 + len(d)] = d
            ctx_lens[i] = base[i]
            chunk_lens[i] = 1 + len(d)
            # the padded table row is index-safe: every token in the
            # verify window sits inside the reserved table
            slot_ids[i] = self._token_slots(r.request_id, base[i], K,
                                            block_table=block_tables[i])
        # spec verify window: K is the max per-row window; accepted
        # counts surface in FINISHED/output deltas
        self.flight.req_event_many(self._dispatch_rids, "WINDOW",
                                   steps=K, spec=True)
        sampled = not all(r.params.greedy for r in reqs)
        self._demote_evicted()
        accept_h = None
        if sampled:
            keys = np.zeros((B, 2), np.uint32)
            temperature = np.zeros((B,), np.float32)
            for i, r in enumerate(reqs):
                keys[i] = self._row_key(r)
                temperature[i] = r.params.temperature
            top_k, top_p, min_p = self._truncation_arrays(reqs, B)
            with PROF.phase("dispatch"):
                accept, pred, self.kv_cache = \
                    self._exec_decode_verify_sampled(
                        jnp.asarray(tokens), jnp.asarray(ctx_lens),
                        jnp.asarray(chunk_lens), jnp.asarray(slot_ids),
                        jnp.asarray(block_tables), jnp.asarray(keys),
                        jnp.asarray(temperature), jnp.asarray(top_k),
                        jnp.asarray(top_p), jnp.asarray(min_p))
            # ONE round trip for both arrays
            with self._sync("verify"):
                accept_h, pred_h = (
                    np.asarray(x) for x in
                    # tpulint: sync-ok(spec verify is synchronous by design: accept/pred decide host-side emission this step)
                    jax.device_get((accept, pred)))
        else:
            with PROF.phase("dispatch"):
                pred, self.kv_cache = self._exec_decode_verify(
                    jnp.asarray(tokens), jnp.asarray(ctx_lens),
                    jnp.asarray(chunk_lens), jnp.asarray(slot_ids),
                    jnp.asarray(block_tables))
            with self._sync("verify"):
                # tpulint: sync-ok(greedy spec verify twin of the sampled sync above)
                pred_h = np.asarray(jax.device_get(pred))
        self.stats.num_decode_steps += 1
        self.stats.spec_steps += 1
        n_tok = int(chunk_lens[:len(reqs)].sum())
        self._note_step_tokens(
            n_tok, B * K, int(ctx_lens[:len(reqs)].sum()) + n_tok)
        step_proposed = step_accepted = 0
        for i, r in enumerate(reqs):
            emitted = (spec_mod.accept_greedy(drafts[i], pred_h[i])
                       if accept_h is None else
                       spec_mod.accept_sampled(drafts[i], accept_h[i],
                                               pred_h[i]))
            step_proposed += len(drafts[i])
            step_accepted += len(emitted) - 1
            self.block_manager.advance(r.request_id, len(emitted))
            for tok in emitted:
                out = self._emit_one(r, tok)
                outputs.append(out)
                if out.finished:
                    break
        self.stats.spec_proposed += step_proposed
        self.stats.spec_accepted += step_accepted
        self._spec_govern(step_proposed, step_accepted)
        return outputs

    def _draft_propose(self, reqs: list, k: int) -> list:
        """Batched stateless draft proposals: each row's window is its
        last ``draft_window`` tokens; the draft model extends every row
        by k greedy tokens in one jitted call
        (models/transformer.draft_propose).  Window and batch are padded
        to fixed buckets so repeat spec steps share one executable."""
        W = self._spec.draft_window
        B = next_power_of_2(len(reqs))
        T = W + k
        tokens = np.zeros((B, T), np.int32)
        lens = np.ones((B,), np.int32)
        for i, r in enumerate(reqs):
            ids = (r.prompt_token_ids + r.output_token_ids)[-W:]
            tokens[i, :len(ids)] = ids
            lens[i] = len(ids)
        with PROF.phase("dispatch"):
            out_d = self._exec_draft_propose(jnp.asarray(tokens),
                                             jnp.asarray(lens), k=k)
        # designated sync: draft proposals feed the verify batch built
        # host-side this same step (the spec path is synchronous)
        with self._sync("draft"):
            out = np.asarray(out_d)
        return [[int(t) for t in out[i]] for i in range(len(reqs))]

    def _spec_govern(self, proposed: int, accepted: int) -> None:
        """Adaptive speculation (SpecConfig.adaptive): accumulate a rolling
        acceptance window; once it holds enough evidence, pause the spec
        path when acceptance is below break-even and re-probe after
        ``adaptive_pause_steps`` decode steps.  The acceptance rate — not a
        config guess — decides whether speculation runs on this workload."""
        cfg = self._spec
        if cfg is None or not cfg.adaptive:
            return
        self._spec_window[0] += proposed
        self._spec_window[1] += accepted
        if self._spec_window[0] < cfg.adaptive_window_proposed:
            return
        acc = self._spec_window[1] / self._spec_window[0]
        self._spec_window = [0, 0]
        floor = cfg.effective_min_acceptance   # draft mode pays k extra
        if acc < floor:                        # device passes per step
            self._spec_resume_step = (self.stats.num_decode_steps
                                      + cfg.adaptive_pause_steps)
            self.stats.spec_pauses += 1
            logger.info(
                "speculation paused: rolling acceptance %.3f < %.3f; "
                "re-probing after %d decode steps", acc, floor,
                cfg.adaptive_pause_steps)

    def _flush_pending(self) -> list[RequestOutput]:
        """Read the in-flight decode step's tokens and run the host-side
        bookkeeping (append, detokenize, stop checks, emission)."""
        p, self._pending = self._pending, None
        if p is None:
            return []
        due, moe = self._moe_due(p.seq)
        with self._sync("decode"):
            # tpulint: sync-ok(the single-step pipeline's designated sync: resolves the PREVIOUS step while the next runs)
            toks, moe = jax.device_get((p.toks, moe))
        toks = np.asarray(toks)
        self._moe_note(due, moe)
        reqs, vals = [], []
        for i, r in enumerate(p.reqs):
            if r.finished:                      # aborted while in flight
                continue
            reqs.append(r)
            vals.append(toks[i])
        if not reqs:
            return []
        return self._append_and_emit(reqs, np.asarray(vals, np.int32))

    # ---- sampling -----------------------------------------------------

    MAX_LOGPROBS = 20

    def _sample(self, logits: jnp.ndarray, reqs: list[Request], B: int) -> np.ndarray:
        with PROF.phase("sample"):
            return self._sample_sync(logits, reqs, B)

    def _sample_enqueue(self, logits: jnp.ndarray, reqs: list[Request],
                        B: int, ahead: dict | None = None) -> tuple:
        """The per-step sampling rules and the sampler, enqueued: returns
        (the logits sampled from, DEVICE tokens (B,)).  ``ahead``: as
        :meth:`_sample_modes` takes it."""
        if any(r.params.needs_penalties for r in reqs):
            logits = self._apply_penalties(logits, reqs, B)
        if any(r.params.needs_logit_bias for r in reqs):
            # applied before logprobs, like penalties: reported logprobs
            # describe the distribution actually sampled from
            logits = self._apply_logit_bias(logits, reqs, B)
        if any(r.params.needs_min_tokens
               and r.params.min_tokens_active(len(r.output_token_ids))
               for r in reqs):
            logits = self._apply_min_tokens(logits, reqs, B)
        if any(r.request_id in self._guided_fsm for r in reqs):
            # grammar-FSM rows: TRUE logit masking before sampling — the
            # sampled token is legal by construction, no substitution
            logits = self._apply_fsm_mask(logits, reqs, B)
        return logits, self._sample_modes(logits, reqs, B, ahead or {})

    def _sample_sync(self, logits: jnp.ndarray, reqs: list[Request],
                     B: int) -> np.ndarray:
        n = len(reqs)
        logits, toks = self._sample_enqueue(logits, reqs, B)
        if any(r.params.logprobs is not None for r in reqs):
            self._record_logprobs(logits, toks, reqs)
        due, moe = self._moe_due(self.flight.seq)
        with self._sync("sample"):
            # tpulint: sync-ok(the synchronous per-step path's one sync; the pipelined paths never call _sample)
            toks_np, moe = jax.device_get((toks, moe))
        toks_np = np.asarray(toks_np)[:n].copy()
        self._moe_note(due, moe)
        if any(r.request_id in self._guided for r in reqs):
            # legacy substitution path: only rows WITHOUT a compiled FSM
            toks_np = self._apply_guided(logits, toks_np, reqs)
        return toks_np

    def _defer_first(self, logits: jnp.ndarray, reqs: list[Request],
                     B: int) -> list[RequestOutput]:
        """A prefill's tail: enqueue the sampler (and the logprobs a row
        asks for) behind the trunk and keep the results on the device, so
        the host reads them behind the NEXT dispatch and the chip's queue
        does not drain after every prefill.  The record of an earlier
        prefill is read here, behind this one.  An engine that does not
        pipeline, or a row whose token the host picks (guided
        substitution), reads its own record at once: one read-and-emit
        path either way."""
        with PROF.phase("sample"):
            logits, toks = self._sample_enqueue(logits, reqs, B)
            lp = None
            if any(r.params.logprobs is not None for r in reqs):
                lp = self._logprobs_enqueue(logits, toks, reqs)
        outputs = self._flush_first(deferred=True)
        substituted = any(r.request_id in self._guided for r in reqs)
        self._pending_first = PendingFirst(
            reqs=list(reqs), toks=toks, lp=lp,
            logits=logits if substituted else None, seq=self.flight.seq)
        if substituted or not self._pipeline_decode:
            outputs += self._flush_first()
        return outputs

    def _flush_first(self, deferred: bool = False) -> list[RequestOutput]:
        """Read the pending prefill's first tokens and emit them.
        ``deferred``: a dispatch was enqueued since the record was made,
        so the chip has work while the host waits here.  A request aborted
        meanwhile is dropped, as a window's row is."""
        p, self._pending_first = self._pending_first, None
        if p is None:
            return []
        if deferred:
            self.stats.prefill_first_token_deferred += len(p.reqs)
        else:
            self.stats.prefill_first_token_flushed_early += len(p.reqs)
        due, moe = self._moe_due(p.seq)
        with self._sync("sample"):
            # (the prompts' picks of rows that asked for logprobs come
            # to the host in the same read: _file_prompt_picks below finds
            # the arrays' host copies made)
            # tpulint: sync-ok(THE designated sync for a prefill's first tokens: one device_get a prefill, behind the next dispatch wherever the host can wait)
            toks, lp, moe, _ = jax.device_get(
                (p.toks, p.lp, moe,
                 [a for r in p.reqs for a, *_ in r.prompt_picks]))
        self._moe_note(due, moe)
        # (writable: guided picks in place)
        toks = np.array(toks[p.offset:p.offset + len(p.reqs)])
        live = [i for i, r in enumerate(p.reqs) if not r.finished]
        now = self.clock.monotonic()
        for i in live:
            r = p.reqs[i]
            if r.first_token_time is None:   # not a re-prefill after preemption
                r.first_token_time = now
                self.stats.ttft_sum += now - r.arrival_time
                self.stats.ttft_count += 1
            if lp is not None and r.params.logprobs is not None:
                self._append_logprob_entry(r, int(toks[i]),
                                           *(a[i] for a in lp))
                self._file_prompt_picks(r)
        if p.logits is not None:
            # legacy substitution path: only rows WITHOUT a compiled FSM
            toks = self._apply_guided(p.logits, toks, p.reqs)
        return self._append_and_emit([p.reqs[i] for i in live], toks[live],
                                     from_prefill=True)

    GUIDED_TOP_K = 32

    @staticmethod
    def _make_guided(params):
        """Acceptor for the request's response_format: plain JSON-object
        grammar, or the schema-constrained subclass (compiled schema
        carried as canonical JSON text in params.guided_schema)."""
        from tpuserve.runtime.guided import (JsonStateMachine,
                                             SchemaJsonStateMachine,
                                             compile_schema)
        if params.guided == "json_schema":
            import json as _json
            return SchemaJsonStateMachine(
                compile_schema(_json.loads(params.guided_schema)))
        if params.guided == "regex":
            from tpuserve.runtime.guided_regex import (RegexStateMachine,
                                                       compile_regex)
            return RegexStateMachine(compile_regex(params.guided_schema))
        if params.guided == "choice":
            import json as _json
            from tpuserve.runtime.guided_choice import (ChoiceStateMachine,
                                                        compile_choices)
            return ChoiceStateMachine(
                compile_choices(_json.loads(params.guided_schema)))
        return JsonStateMachine()

    MAX_FSM_CACHE = 64

    def _fsm_for(self, params):
        """Token-level FSM for the request's grammar, compiled once per
        (mode, spec) and memoised — None when disabled or the spec can't
        be bounded (the request then runs the per-step substitution
        path).  Compile failures memoise as None too, so a hard spec
        doesn't pay the failed walk on every admission.  The memo evicts
        FIFO one entry at a time (with its device tables), so a
        grammar-heavy workload never wipes every hot grammar at once."""
        if not self.config.guided_fsm:
            return None
        key = (params.guided, params.guided_schema)
        if key in self._fsm_cache:
            self._fsm_stats["hits"] += 1
            return self._fsm_cache[key]
        self._fsm_stats["misses"] += 1
        from tpuserve.runtime.grammar import (FsmCompileError, fsm_for_spec,
                                              load_fsm, resolve_cache_dir,
                                              save_fsm, token_text_table,
                                              tokenizer_fingerprint)
        # Persistent disk cache keyed by (spec hash, tokenizer hash) —
        # the model-PVC path in production (runtime/grammar/cache.py), so
        # a production-vocab grammar compiles ONCE per fleet, not once
        # per pod per grammar.  A hit skips both the determinizing walk
        # AND the token-text-table build below.
        disk_dir = resolve_cache_dir(self.config.checkpoint_dir)
        tok_fp = None
        if disk_dir is not None:
            if self._fsm_tok_fp is None:
                self._fsm_tok_fp = tokenizer_fingerprint(
                    self.tokenizer, self.model_cfg.vocab_size,
                    self._eos_ids)
            tok_fp = self._fsm_tok_fp
            fsm = load_fsm(disk_dir, params.guided, params.guided_schema,
                           tok_fp)
            if fsm is not None:
                self._fsm_stats["disk_hits"] += 1
                self._memoise_fsm(key, fsm)
                return fsm
        if self._fsm_texts is None:
            # token id -> standalone text depends only on the tokenizer:
            # computed ONCE per engine, not per grammar (a production
            # vocab makes this loop the dominant fixed compile cost)
            self._fsm_texts = token_text_table(self.tokenizer,
                                               self.model_cfg.vocab_size)
        try:
            fsm = fsm_for_spec(params.guided, params.guided_schema,
                               self.tokenizer, self.model_cfg.vocab_size,
                               self._eos_ids, texts=self._fsm_texts)
        except (FsmCompileError, ValueError) as e:
            logger.info("guided spec not FSM-compilable (%s); using the "
                        "per-step substitution path", e)
            fsm = None
        if fsm is not None and disk_dir is not None:
            # failures are NOT persisted: they depend on the walk/state
            # budgets, which are env-tunable per deployment
            save_fsm(disk_dir, params.guided, params.guided_schema,
                     tok_fp, fsm)
        self._memoise_fsm(key, fsm)
        return fsm

    def _memoise_fsm(self, key, fsm) -> None:
        """FIFO-bounded in-memory memo (with its device tables) — shared
        by the compile and disk-hit paths so eviction policy can't
        drift."""
        if len(self._fsm_cache) >= self.MAX_FSM_CACHE:
            old = self._fsm_cache.pop(next(iter(self._fsm_cache)))
            if old is not None:
                self._fsm_device.pop(id(old), None)
        self._fsm_cache[key] = fsm

    def compile_cache_stats(self) -> dict:
        """Hit/miss/size for the engine's two compile caches — the
        grammar-FSM memo and the bucketed-executable ladder — surfaced at
        /debug/engine ("compile_caches") so compile churn is visible
        without log archaeology.  FSM misses count full determinizing
        walks AND disk-cache loads (disk_hits is the subset the
        fleet-wide PVC cache absorbed); ladder ``misses`` are an
        executable's FIRST dispatches as devprof brackets them, each a
        compile only where the persistent cache missed: ``cache_misses``
        of them XLA compiled, ``cache_hits`` were read back from the
        cache (the rest did not ask it; runtime/devprof.py)."""
        dp = self.devprof
        return {
            "fsm": {"hits": self._fsm_stats["hits"],
                    "misses": self._fsm_stats["misses"],
                    "disk_hits": self._fsm_stats["disk_hits"],
                    "size": len(self._fsm_cache)},
            "ladder": {"hits": max(0, sum(dp.dispatch_counts.values())
                                   - dp.compiles),
                       "misses": dp.compiles,
                       "size": len(dp.ladder),
                       "compile_ms": round(dp.compile_s * 1000.0, 3),
                       **dp.cache_answers()},
        }

    def _fsm_device_tables(self, fsm):
        """Device-resident (masks, tok_class, class_next) for ``fsm``,
        uploaded once per grammar and padded to power-of-2 state/class
        buckets so repeat window dispatches over same-sized grammars
        share one executable.  Each entry keeps a STRONG reference to
        its fsm: while the entry lives, ``id(fsm)`` cannot be recycled
        onto a new grammar and served these tables by accident.  The
        table cache is FIFO-bounded like the compile memo; an in-flight
        request whose entry gets evicted just re-uploads next window."""
        ent = self._fsm_device.get(id(fsm))
        if ent is None:
            n, vw = fsm.masks.shape
            c = fsm.class_next.shape[1]
            np_, cp = next_power_of_2(n), next_power_of_2(c)
            masks = np.zeros((np_, vw), np.uint32)
            masks[:n] = fsm.masks
            nxt = np.full((np_, cp), -1, np.int32)
            nxt[:n, :c] = fsm.class_next
            if len(self._fsm_device) >= self.MAX_FSM_CACHE:
                self._fsm_device.pop(next(iter(self._fsm_device)))
            ent = (fsm, jnp.asarray(masks), jnp.asarray(fsm.tok_class),
                   jnp.asarray(nxt))
            self._fsm_device[id(fsm)] = ent
        return ent[1:]

    def _apply_fsm_mask(self, logits: jnp.ndarray, reqs: list[Request],
                        B: int) -> jnp.ndarray:
        """Per-step grammar-FSM logit masking: gather each FSM row's
        packed allow bitmask by its host-tracked state and drop illegal
        tokens before sampling.  This is the S=1 reference semantics the
        fused window reproduces on device — applied after penalties /
        bias / min_tokens, like window_guided_mask in the scan."""
        vw = (self.model_cfg.vocab_size + 31) // 32
        packed = np.zeros((B, vw), np.uint32)
        enabled = np.zeros((B,), bool)
        for i, r in enumerate(reqs):
            ent = self._guided_fsm.get(r.request_id)
            if ent is not None:
                packed[i] = ent[0].mask_row(ent[1])
                enabled[i] = True
        return sampling_ops.apply_token_mask(
            logits, jnp.asarray(packed), jnp.asarray(enabled))

    def _apply_guided(self, logits: jnp.ndarray, toks_np: np.ndarray,
                      reqs: list[Request]) -> np.ndarray:
        """Structured output: keep the sampled token when its text keeps
        the document valid; otherwise substitute the most-probable valid
        candidate from the top-K (then from a structural fallback set).
        Token substitution is safe on the single-step path: the next
        step's input token comes from the host, and KV for this position
        is written by the NEXT dispatch."""
        k = min(self.GUIDED_TOP_K, self.model_cfg.vocab_size)
        _, top_ids = jax.lax.top_k(logits, k)
        with self._sync("guided"):
            # tpulint: sync-ok(legacy guided substitution is host-side by design; FSM-compilable grammars stay on device)
            ids_h = np.asarray(jax.device_get(top_ids))
        for i, r in enumerate(reqs):
            st = self._guided.get(r.request_id)
            if r.params.guided is None or st is None:
                continue
            toks_np[i] = self._guided_pick(
                r, st, int(toks_np[i]), [int(t) for t in ids_h[i]])
        return toks_np

    @staticmethod
    def _guided_text_of(tokenizer, ctx: list, base: str, tok: int) -> str:
        """Text a candidate token would contribute, via decode-diff over a
        short context window — exact for any tokenizer (BPE merges,
        SentencePiece markers) without a vocabulary table.  ``ctx``/``base``
        are computed once per step by the caller (30-50 candidates share
        them)."""
        full = tokenizer.decode(ctx + [tok])
        d = full[len(base):] if full.startswith(base) else \
            tokenizer.decode([tok])
        # trailing replacement char = partial UTF-8 rune still pending —
        # its bytes aren't text yet
        return d.rstrip("�")

    def _guided_pick(self, r: Request, st, sampled: int,
                     candidates: list[int]) -> int:
        plan = self._guided_plan.get(r.request_id)
        if plan:
            # mid-plan: emit the committed canonical encoding verbatim —
            # mixing sampled tokens back in would break the byte
            # alignment the plan was committed to preserve
            tok = plan.pop(0)
            if not plan:
                self._guided_plan.pop(r.request_id, None)
            return tok
        ctx = (r.prompt_token_ids + r.output_token_ids)[-8:]
        base = self.tokenizer.decode(ctx)
        for tok in [sampled] + candidates:
            if tok in self._eos_ids:
                if st.can_finish:      # JSON: root closed; regex: accepting
                    return tok
                continue
            txt = self._guided_text_of(self.tokenizer, ctx, base, tok)
            if txt:
                if st.allows(txt):
                    return tok
            elif st.in_string:
                # no decoded text yet (partial rune / special token):
                # neutral ONLY where arbitrary text is legal — accepting
                # it elsewhere lets multibyte garbage assemble outside
                # strings
                return tok
        for tok in self._guided_fallback():
            txt = self._guided_text_of(self.tokenizer, ctx, base, tok)
            if txt and st.allows(txt):
                self.stats.guided_fallbacks += 1
                return tok
        # Last resort before dropping the constraint: acceptors that can
        # enumerate their legal continuations (guided_choice) let us
        # commit to the tokenizer's OWN encoding of one — correct even
        # when no single token spells the next char (non-ASCII choices:
        # the first byte token decodes to no text yet, so every
        # char-level candidate above was rejected).
        suffixes = getattr(st, "viable_suffixes", None)
        if suffixes is not None:
            anchor = None
            for s in suffixes():
                # strict IN-CONTEXT round-trip gate: the plan's tokens are
                # emitted after ctx, so validate what they decode to THERE
                # — a standalone decode(encode(s)) == s check would pass a
                # tokenizer whose sequence-initial marker then surfaces as
                # a stray leading space in context, failing the acceptor
                # mid-plan.  Skip rather than corrupt.
                def _gated(ids):
                    return (ids
                            and self.tokenizer.decode(ctx + ids) == base + s)

                ids = self.tokenizer.encode(s)
                if not _gated(ids):
                    # The wrapper's encode() is already special-token-free
                    # (models/tokenizer.py), but a SentencePiece-style
                    # tokenizer still prepends a sequence-initial space
                    # marker the gate just rejected — retry with the
                    # MID-TEXT tokenization of s (anchor trick) instead of
                    # silently dropping the constraint (ADVICE r4).
                    if anchor is None:
                        anchor = self.tokenizer.encode("x")
                    mid = self.tokenizer.encode("x" + s)
                    ids = (mid[len(anchor):]
                           if anchor and mid[:len(anchor)] == anchor
                           else [])
                    if not _gated(ids):
                        continue
                if len(ids) > 1:
                    self._guided_plan[r.request_id] = ids[1:]
                self.stats.guided_plans += 1
                return ids[0]
        # nothing valid exists (pathological tokenizer): give up on the
        # constraint for this step rather than deadlock
        self.stats.guided_fallbacks += 1
        return sampled

    def _guided_fallback(self) -> list[int]:
        """Single-token encodings of candidate strings — the escape hatch
        when the whole top-K is grammatically invalid (common early on
        with small/random models).  Tier 1: JSON structural strings (the
        json/json_schema fast path).  Tier 2: every printable-ASCII
        single char — a regex can demand ANY next char ('!', '@', ...),
        and a fallback that can't produce it silently drops the whole
        constraint (found by a live guided_regex drive emitting garbage
        after the pattern's '!')."""
        if self._guided_fallback_ids is None:
            import string
            ids, seen = [], set()
            tier1 = ('"', "}", "]", ":", ",", "{", "[", " ", "0", "1",
                     "2", "7", "a", "k", "true", "false", "null", "-",
                     ".", "e")
            for s in tier1 + tuple(string.printable):
                enc = self.tokenizer.encode(s)
                if len(enc) == 1 and enc[0] not in seen:
                    seen.add(enc[0])
                    ids.append(enc[0])
            self._guided_fallback_ids = ids
        return self._guided_fallback_ids

    def _logit_bias_arrays(self, reqs: list[Request], B: int, V: int):
        """Per-row (ids, vals) scatter arrays for logit_bias — shared by
        the per-step path and the fused-window dense-bias build."""
        K = next_power_of_2(max(len(r.params.logit_bias or {})
                                for r in reqs) or 1)
        ids = np.full((B, K), V, np.int32)          # V = dropped by scatter
        vals = np.zeros((B, K), np.float32)
        for i, r in enumerate(reqs):
            for j, (tid, b) in enumerate(r.params.logit_bias_items()):
                ids[i, j] = int(tid)
                vals[i, j] = float(b)
        return ids, vals

    def _apply_logit_bias(self, logits: jnp.ndarray, reqs: list[Request],
                          B: int) -> jnp.ndarray:
        ids, vals = self._logit_bias_arrays(reqs, B, logits.shape[1])
        return sampling_ops.apply_logit_bias(
            logits, jnp.asarray(ids), jnp.asarray(vals))

    def _min_tokens_arrays(self, reqs: list[Request], B: int, V: int):
        """vLLM min_tokens scatter inputs: per-row masked ids (every EOS
        id and per-request stop_token_ids at -1e9 — not -inf, a
        fully-masked row under temperature softmax must not produce NaN)
        for rows still below their floor, plus each row's REMAINING
        token count (the fused window lifts the mask on the scan step
        where the row crosses its floor).  Shared by the per-step mask
        and the window dispatch."""
        eos = sorted(self._eos_ids)
        rows = {}
        remaining = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            if (r.params.needs_min_tokens
                    and r.params.min_tokens_active(len(r.output_token_ids))):
                rows[i] = (([] if r.params.ignore_eos else eos)
                           + list(r.params.stop_token_ids))
                remaining[i] = (r.params.min_tokens
                                - len(r.output_token_ids))
        # width over MASKED rows only — a past-floor row with many
        # stop_token_ids must not inflate the scatter bucket
        K = next_power_of_2(max((len(v) for v in rows.values()), default=1)
                            or 1)
        ids = np.full((B, K), V, np.int32)
        vals = np.zeros((B, K), np.float32)
        for i, row in rows.items():
            ids[i, :len(row)] = row
            vals[i, :len(row)] = -1e9
        return ids, vals, remaining

    def _apply_min_tokens(self, logits: jnp.ndarray, reqs: list[Request],
                          B: int) -> jnp.ndarray:
        ids, vals, _ = self._min_tokens_arrays(reqs, B, logits.shape[1])
        return sampling_ops.apply_logit_bias(
            logits, jnp.asarray(ids), jnp.asarray(vals))

    def _sample_modes(self, logits: jnp.ndarray, reqs: list[Request], B: int,
                      ahead) -> jnp.ndarray:
        """Pick the cheapest sampler covering this batch; returns DEVICE
        tokens (B,).  ``ahead``: request id -> its tokens still on the
        device (pipelined decode) — their sampling-key step index is that
        many ahead of the host-visible output length."""
        if all(r.params.greedy for r in reqs):
            return self._exec_sample(
                logits, *self._greedy_dummies(B), mode="greedy")
        mode = ("temperature"
                if not any(r.params.needs_truncation for r in reqs) else "full")
        temperature = np.zeros((B,), np.float32)
        top_k, top_p, min_p = self._truncation_arrays(reqs, B)
        keys = np.zeros((B, 2), np.uint32)
        for i, r in enumerate(reqs):
            temperature[i] = r.params.temperature
            keys[i] = self._row_key(r, extra_step=ahead.get(r.request_id, 0))
        kw = {}
        if mode == "full" and (min_p > 0).any():
            kw["min_p"] = jnp.asarray(min_p)
        return self._exec_sample(
            logits, jnp.asarray(keys), jnp.asarray(temperature),
            jnp.asarray(top_k), jnp.asarray(top_p), mode=mode, **kw)

    def _truncation_arrays(self, reqs: list[Request], B: int):
        """Per-row top_k/top_p/min_p for the "full" sampler — ONE home for
        the clamps, shared by the per-step sampler and the fused-window
        dispatch so the two paths cannot drift (their token-identical
        parity is regression-tested)."""
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        min_p = np.zeros((B,), np.float32)
        for i, r in enumerate(reqs):
            # clamp: vocab_size bounds the meaningful range and keeps
            # direct-caller values inside the int32 array (a 2**40 here
            # crashed the whole co-batched step — found by fuzzing)
            top_k[i] = max(min(r.params.top_k,
                               self.model_cfg.vocab_size), -1)
            top_p[i] = r.params.top_p
            min_p[i] = r.params.min_p
        return top_k, top_p, min_p

    def _greedy_dummies(self, B: int):
        """Per-bucket constant sampling inputs, created once.  Building these
        eagerly every step costs ~4 dispatches/step for arrays whose
        values never change."""
        d = self._greedy_cache.get(B)
        if d is None:
            d = (jnp.zeros((B, 2), jnp.uint32), jnp.zeros((B,)),
                 jnp.zeros((B,), jnp.int32), jnp.ones((B,)))
            self._greedy_cache[B] = d
        return d

    def _penalty_arrays(self, reqs: list[Request], B: int):
        """Per-row token history (T-bucketed) + penalty coefficient
        arrays — shared by the per-step penalizer and the fused-window
        dispatch so the two paths' inputs cannot drift."""
        from tpuserve.utils import next_power_of_2 as np2
        T = max(np2(max(len(r.output_token_ids) for r in reqs)), 8)
        out_tokens = np.zeros((B, T), np.int32)
        mask = np.zeros((B, T), bool)
        presence = np.zeros((B,), np.float32)
        frequency = np.zeros((B,), np.float32)
        repetition = np.ones((B,), np.float32)
        for i, r in enumerate(reqs):
            ids = r.output_token_ids[-T:]
            out_tokens[i, :len(ids)] = ids
            mask[i, :len(ids)] = True
            presence[i] = r.params.presence_penalty
            frequency[i] = r.params.frequency_penalty
            repetition[i] = r.params.repetition_penalty
        return out_tokens, mask, presence, frequency, repetition

    def _apply_penalties(self, logits: jnp.ndarray, reqs: list[Request], B: int) -> jnp.ndarray:
        out_tokens, mask, presence, frequency, repetition = \
            self._penalty_arrays(reqs, B)
        return sampling_ops.apply_logit_penalties(
            logits, jnp.asarray(out_tokens), jnp.asarray(mask),
            jnp.asarray(presence), jnp.asarray(frequency), jnp.asarray(repetition))

    def _logprobs_enqueue(self, logits: jnp.ndarray, toks: jnp.ndarray,
                          reqs: list[Request]) -> tuple:
        """``(chosen logprob, top ids, top logprobs)`` of the rows of
        ``logits``, which the dispatch just enqueued returned; for a model
        with expert layers the rows' picks ride fourth."""
        top_n = min(max(r.params.logprobs or 0 for r in reqs) or 1, self.MAX_LOGPROBS)
        lp = sampling_ops.compute_logprobs(logits, toks, top_n)
        picks, self._moe_picks = self._moe_picks, None
        return lp if picks is None else lp + (picks,)

    def _record_logprobs(self, logits: jnp.ndarray, toks: jnp.ndarray,
                         reqs: list[Request]) -> None:
        lp = [np.asarray(a) for a in self._logprobs_enqueue(logits, toks,
                                                            reqs)]
        for i, r in enumerate(reqs):
            if r.params.logprobs is None:
                continue
            self._append_logprob_entry(r, int(toks[i]), *(a[i] for a in lp))
            self._file_prompt_picks(r)

    @staticmethod
    def _append_logprob_entry(r: Request, tok: int, chosen_lp,
                              top_ids, top_lps, experts=None) -> None:
        """ONE home for the per-token logprob record shape — shared by
        the per-step recorder and the fused-window flush so the two
        paths' response formats cannot drift.  ``top_ids``/``top_lps``
        are 1-D, possibly wider than the request asked for.  ``experts``
        (expert layers, k), a model with expert layers: the experts each
        layer routed the position to whose logits the token was sampled
        from — what an evaluation in another precision has to replay,
        because a top-k pick at a near-tie is not a rounding error."""
        k = min(r.params.logprobs, len(top_ids))
        entry = {
            "token_id": tok,
            "logprob": float(chosen_lp),
            "top": [(int(t), float(l)) for t, l in
                    zip(top_ids[:k], top_lps[:k])],
        }
        if experts is not None:
            entry["routed_experts"] = experts.tolist()
        r.logprobs.append(entry)

    # ---- bookkeeping --------------------------------------------------

    def _append_and_emit(self, reqs: list[Request], new_tokens: np.ndarray,
                         from_prefill: bool = False) -> list[RequestOutput]:
        with PROF.phase("detokenize"):
            return [self._emit_one(req, int(tok), from_prefill)
                    for req, tok in zip(reqs, new_tokens)]

    def _emit_one(self, req: Request, tok: int,
                  from_prefill: bool = False) -> RequestOutput:
        req.output_token_ids.append(tok)
        # progress resets the salvage budget: the budget bounds CONSECUTIVE
        # faulted attempts, not total faults a long stream lives through
        req.num_salvages = 0
        self.stats.generated_tokens += 1
        raw_delta = self._detok[req.request_id].add(tok)
        delta = raw_delta
        reason = None
        if req.params.stop and not req.params.min_tokens_active(
                len(req.output_token_ids)):
            # vLLM min_tokens semantics: stop strings are suppressed (text
            # still streams) until the floor is reached
            delta, stopped = self._match_stop(req, delta)   # mutates output_text on stop
            if stopped:
                reason = FinishReason.STOP
        else:
            req.output_text += delta
        if req.params.guided is not None:
            ent = self._guided_fsm.get(req.request_id)
            if ent is not None:
                # grammar-FSM path: advance the host mirror state by the
                # TOKEN through the same table the device window used —
                # host and device cannot drift.  EOS finishes via
                # check_stop below, keeping the legacy finish_reason.
                fsm, gs = ent
                ns = fsm.advance(gs, tok)
                if ns < 0:
                    # off-grammar token (only possible if masking was
                    # bypassed): drop the constraint rather than keep
                    # validating against a corrupt state
                    self._guided_fsm.pop(req.request_id, None)
                else:
                    ent[1] = ns
                    if (fsm.complete[ns] and reason is None
                            and tok not in self._eos_ids):
                        # grammar closed (JSON root / inextensible match):
                        # stop like OpenAI json mode does
                        reason = FinishReason.STOP
            st = self._guided.get(req.request_id)
            if st is not None:
                if raw_delta:
                    try:
                        # the RAW delta: guided state must track what was
                        # SAMPLED, not what stop hold-back emitted — a
                        # held stop-prefix would leave the acceptor
                        # lagging ctx and validating against stale state
                        st.feed(raw_delta)   # authoritative state advance
                    except ValueError:
                        # gave-up step: DEREGISTER so later steps don't
                        # validate candidates against a corrupted state
                        self._guided.pop(req.request_id, None)
                        self._guided_plan.pop(req.request_id, None)
                        st = None
                if st is not None and st.complete and reason is None:
                    # root object closed: stop like OpenAI json mode does
                    reason = FinishReason.STOP
        if reason is None:
            reason = check_stop(req, self._eos_ids, self.max_seq_len)
        finished = reason is not None
        if finished and req.stop_held:
            # the held stop-prefix never completed a match: it is real
            # output and must not be swallowed
            req.output_text += req.stop_held
            delta += req.stop_held
            req.stop_held = ""
        if finished:
            req.finish_reason = reason
            req.finish_time = self.clock.monotonic()
            self.scheduler.finish(req)
            self.stats.requests_finished += 1
            self.flight.req_event(req.request_id, "FINISHED",
                                  cause=reason.value,
                                  output_tokens=len(req.output_token_ids))
            self._detok.pop(req.request_id, None)
            self._guided.pop(req.request_id, None)
            self._guided_fsm.pop(req.request_id, None)
            self._guided_plan.pop(req.request_id, None)
        return RequestOutput(
            request_id=req.request_id, new_token_ids=[tok], new_text=delta,
            finished=finished, finish_reason=reason,
            num_prompt_tokens=req.num_prompt_tokens,
            num_output_tokens=len(req.output_token_ids),
            from_prefill=from_prefill)

    def _match_stop(self, req: Request, delta: str) -> tuple[str, bool]:
        """Stop-string search with PREFIX HOLD-BACK.  A stop string can
        span deltas; emitting eagerly would stream its prefix before the
        match completes (a client sees 'A' of a matched 'AA' it was never
        supposed to get — the stored text truncates but the stream cannot
        retract).  Scanning runs over held + delta; a tail that is a
        proper prefix of any stop string is WITHHELD (req.stop_held) and
        either consumed by a later match, or flushed when the request
        finishes for another reason.  On a match the stop string is
        dropped (OpenAI semantics) or kept
        (include_stop_str_in_output, the vLLM extension).
        Returns (emitted_delta, stopped)."""
        stops = req.params.stop
        if any(not s for s in stops):
            # the empty stop string matches everywhere: stop NOW, emit
            # nothing new (pre-hold-back behaviour)
            req.stop_held = ""
            return "", True
        max_stop = max(len(s) for s in stops)
        # Scan over: emitted tail + held + delta.  The emitted tail exists
        # so matches SPANNING already-emitted text are still found — in
        # particular across the min_tokens boundary, where suppressed text
        # bypassed this function entirely — but a candidate must consume
        # at least one unemitted char (ending at most at `base` would
        # mean an earlier scan already decided it).
        prev_tail = req.output_text[-(max_stop - 1):] if max_stop > 1 else ""
        base = len(prev_tail)
        text = prev_tail + req.stop_held + delta
        best = None
        for s in stops:
            start = 0
            while True:
                pos = text.find(s, start)
                if pos == -1:
                    break
                if pos + len(s) > base:
                    if best is None or pos < best[0]:
                        best = (pos, s)
                    break
                start = pos + 1
        if best is not None:
            keep_until = best[0]
            if req.params.include_stop_str_in_output:
                keep_until += len(best[1])
            req.stop_held = ""
            if keep_until >= base:
                emit = text[base:keep_until]
                req.output_text += emit
                return emit, True
            # cut inside already-emitted text (min_tokens spanning edge):
            # the stream cannot retract, but the STORED text honours the
            # stop semantics like the pre-hold-back implementation did
            req.output_text = req.output_text[
                :len(req.output_text) - (base - keep_until)]
            return "", True
        # no match: hold the longest UNEMITTED tail that could still
        # become one (an emitted prefix is covered by prev_tail above)
        held = 0
        for k in range(min(len(text) - base, max_stop - 1), 0, -1):
            if any(s.startswith(text[-k:]) for s in stops):
                held = k
                break
        emit = text[base:len(text) - held]
        req.stop_held = text[len(text) - held:] if held else ""
        req.output_text += emit
        return emit, False

    def generate(self, prompts: Sequence[str] | Sequence[Sequence[int]],
                 params: SamplingParams | Sequence[SamplingParams] | None = None,
                 ) -> list[Request]:
        if params is None:
            params = SamplingParams()
        if isinstance(params, SamplingParams):
            params = [params] * len(prompts)
        if len(params) != len(prompts):
            raise ValueError(f"got {len(prompts)} prompts but {len(params)} "
                             "sampling params")
        rids = []
        for prompt, p in zip(prompts, params):
            if isinstance(prompt, str):
                rids.append(self.add_request(prompt=prompt, params=p))
            else:
                rids.append(self.add_request(prompt_token_ids=prompt, params=p))
        while self.has_work():
            self.step()
        return [self.requests.pop(rid) for rid in rids]

    # ------------------------------------------------------------------
    # Embeddings: pooled hidden states, no KV cache involvement
    # ------------------------------------------------------------------

    MAX_EMBED_BATCH = 128
    # embed_forward materialises a (B, H, T, T) f32 score tensor (it runs
    # the reference prefill attention, cache-less).  Bound that to ~1 GiB
    # so one embeddings request can't OOM a device that is also serving
    # decode traffic: the batch is auto-chunked down, and a single input
    # too long for the budget alone is rejected with a 400-able error.
    EMBED_SCORE_BUDGET_BYTES = 1 << 30
    # pp intake guard (add_request): max f32 attention-score bytes one
    # batched reference prefill may materialise on the staged trunk
    PP_PREFILL_SCORE_BUDGET_BYTES = 1 << 30

    def _embed_max_rows(self, T: int) -> int:
        per_row = self.model_cfg.num_heads * T * T * 4
        return max(int(self.EMBED_SCORE_BUDGET_BYTES // max(per_row, 1)), 0)

    def score_prompts(self, ids_list: Sequence[Sequence[int]],
                      top_n: int = 0) -> list:
        """Prompt logprobs (OpenAI ``echo``+``logprobs``; vLLM
        ``prompt_logprobs``): per-token log p(t_i | t_<i) with optional
        top alternatives, via the cache-less scoring trunk
        (models/transformer.score_prompt — unembed in vocab slices, so a
        page of text never materialises (T, V) float32 logits).

        Returns one entry list per prompt, shaped like Request.logprobs
        entries; the FIRST token's logprob is None (no conditional), as
        OpenAI reports it.  Shares the embed lock and attention-score
        budget — both paths run the quadratic reference attention."""
        if jax.process_count() > 1:
            raise ValueError("prompt scoring not supported by this "
                             "multi-host deployment")
        if self._pp > 1:
            raise ValueError("prompt scoring not supported on the pipeline "
                             "engine; route to a non-pp replica")
        top_n = min(max(int(top_n), 0), self.MAX_LOGPROBS)
        prepared = []
        for ids in ids_list:
            ids = [int(t) for t in ids]
            if not ids:
                raise ValueError("prompts must be non-empty")
            limit = self.model_cfg.max_position_embeddings
            if len(ids) > limit:
                raise ValueError(f"prompt length {len(ids)} exceeds model "
                                 f"position range {limit}")
            if self._embed_max_rows(max(next_power_of_2(len(ids)), 16)) < 1:
                raise ValueError(
                    f"prompt length {len(ids)} exceeds the scoring "
                    "attention budget for this model; shorten the input")
            prepared.append(ids)
        with self._embed_lock:
            return self._score_locked(prepared, top_n)

    def _trunk_batches(self, ids_list, min_t: int):
        """Greedy (B, T) batching shared by the cache-less trunk callers
        (embed, prompt scoring): largest prefix whose padded shape fits
        the attention-score budget, power-of-2 buckets to bound
        recompiles.  Yields (group, tokens (B, T), lens (B,))."""
        i = 0
        while i < len(ids_list):
            T = max(next_power_of_2(len(ids_list[i])), min_t)
            j = i + 1
            while j < len(ids_list):
                T2 = max(T, next_power_of_2(len(ids_list[j])), min_t)
                if j + 1 - i > min(self._embed_max_rows(T2),
                                   self.MAX_EMBED_BATCH):
                    break
                T = T2
                j += 1
            group = ids_list[i:j]
            B = next_power_of_2(len(group))
            if B > self._embed_max_rows(T):     # padding rows count too
                B = max(len(group), 1)
            tokens = np.zeros((B, T), dtype=np.int32)
            lens = np.ones((B,), dtype=np.int32)   # pad rows: avoid 0-len
            for k, ids in enumerate(group):
                tokens[k, :len(ids)] = ids
                lens[k] = len(ids)
            yield group, tokens, lens
            i = j

    def _score_locked(self, ids_list, top_n):
        from tpuserve.models.transformer import score_prompt
        results = []
        for group, tokens, lens in self._trunk_batches(ids_list, 16):
            chosen, ranks, top_ids, top_lps = score_prompt(
                self.params, self.model_cfg, tokens, lens, top_n=top_n)
            chosen = np.asarray(chosen)
            ranks = np.asarray(ranks)
            top_ids = np.asarray(top_ids)
            top_lps = np.asarray(top_lps)
            for k, ids in enumerate(group):
                entries = [{"token_id": ids[0], "logprob": None,
                            "rank": None, "top": []}]
                for p in range(1, len(ids)):
                    # position p-1's distribution scores token p
                    entries.append({
                        "token_id": ids[p],
                        "logprob": float(chosen[k, p - 1]),
                        "rank": int(ranks[k, p - 1]),
                        "top": [(int(t), float(l)) for t, l in
                                zip(top_ids[k, p - 1], top_lps[k, p - 1])],
                    })
                results.append(entries)
        return results

    def embed(self, inputs: Sequence[str] | Sequence[Sequence[int]],
              pooling: str = "mean"):
        """Sentence embeddings for /v1/embeddings (vLLM-surface parity).

        Tokenises, pads to power-of-2 (B, T) buckets to bound recompiles,
        and runs the cache-less trunk (models/transformer.py
        embed_forward) in batch chunks sized to the attention-score memory
        budget.  Returns (float32 ndarray (n, H), token counts).
        Multi-host lockstep mirrors prefill/decode only, so embeddings are
        rejected there like the other out-of-protocol ops."""
        import jax
        if jax.process_count() > 1:
            raise ValueError("embeddings not supported by this multi-host "
                             "deployment; route to a single-host replica")
        if self._pp > 1:
            raise ValueError("embeddings not supported on the pipeline "
                             "engine; route to a non-pp replica")
        if pooling not in ("mean", "last"):
            raise ValueError("pooling must be 'mean' or 'last'")
        if not inputs:
            raise ValueError("input must be non-empty")
        if len(inputs) > self.MAX_EMBED_BATCH:
            raise ValueError(f"at most {self.MAX_EMBED_BATCH} inputs per "
                             "request")
        ids_list = []
        for x in inputs:
            ids = self.tokenizer.encode(x) if isinstance(x, str) else \
                [int(t) for t in x]
            if not ids:
                raise ValueError("input texts must be non-empty")
            limit = self.model_cfg.max_position_embeddings
            if len(ids) > limit:
                raise ValueError(f"input length {len(ids)} exceeds model "
                                 f"position range {limit}")
            T1 = max(next_power_of_2(len(ids)), 8)
            if self._embed_max_rows(T1) < 1:
                raise ValueError(
                    f"input length {len(ids)} exceeds the embeddings "
                    "attention budget for this model; shorten the input")
            ids_list.append(ids)
        with self._embed_lock:
            return self._embed_locked(ids_list, pooling)

    def _embed_locked(self, ids_list, pooling):
        from tpuserve.models.transformer import embed_forward
        outs = []
        for group, tokens, lens in self._trunk_batches(ids_list, 8):
            out = embed_forward(self.params, self.model_cfg, tokens, lens,
                                pooling=pooling)
            outs.append(np.asarray(out)[:len(group)])
        return np.concatenate(outs, axis=0), [len(x) for x in ids_list]

    # ------------------------------------------------------------------
    # Warmup: pre-compile the bucketed executables (TTFT depends on this —
    # SURVEY.md §7 "TTFT ≤150 ms requires compile-cache warmup at startup")
    # ------------------------------------------------------------------

    def warmup(self, *args, **kwargs) -> None:
        """Fault-suspended wrapper over :meth:`_warmup`: warmup runs the
        same ``_exec_*`` hooks as serving, and an armed chaos spec firing
        during startup compiles would fail the pod before it ever served —
        not the failure mode the injector exists to test."""
        with self.faults.suspended(), STARTUP.phase("startup.warmup"):
            try:
                return self._warmup(*args, **kwargs)
            finally:
                # warm-up dispatches route dummy rows: not traffic
                self._moe_inflight.clear()

    def _warmup(self, prefill_buckets: Sequence[int | tuple[int, int]] | None
                = None,
                decode_buckets: Sequence[int] = (),
                sample_modes: Sequence[str] = ("greedy", "temperature",
                                               "full", "logprobs",
                                               "penalties", "bias",
                                               "min_tokens"),
                chunk_buckets: Sequence[int] = (),
                embed_buckets: Sequence[tuple[int, int]] = (),
                mixed_buckets: Sequence[int] | None = None,
                ) -> None:
        """Pre-compile executables.  ``prefill_buckets`` entries are either a
        padded prompt length L (compiled at batch 1) or a ``(batch, L)`` pair
        — _run_prefill pads the batch to a power of two, so warming only
        batch 1 leaves the multi-sequence prefill shapes cold.  An engine
        on the packed route (``_packed_prefill``) dispatches none of them:
        it warms, in their place, every rung of its flat-token ladder that
        batches of those shapes can reach.  An EMPTY
        ``prefill_buckets`` list means "warm no batched prefill" (workloads
        routed entirely through chunked prefill); None means "not
        specified" and warms the minimum bucket.  ``chunk_buckets`` are
        extra chunked-prefill padded lengths to warm beyond the full chunk
        size (the padded TAIL chunk of a prompt that isn't an exact
        multiple)."""
        if prefill_buckets is None:
            prefill_buckets = [self.config.scheduler.min_prefill_bucket]
        else:
            prefill_buckets = list(prefill_buckets)
        decode_buckets = list(decode_buckets)
        scfg = self.scheduler.cfg
        if scfg.mixed_batching:
            # Mixed mode's executable family is derivable from config, so
            # the engine warms it itself (callers were duplicating — and
            # drifting — this ladder logic).  mixed_buckets=None = auto:
            # the flat-token ladder up to the budget (the row-charged
            # scheduler guarantees no dispatch ever exceeds it: its rows
            # are whole blocks, so a rung is never past the budget's own);
            # cold, a bucket compiles inside a measured/served ITL.  And because
            # budget-staggered admission staggers FINISHES, the decode
            # tail shrinks through partial buckets even on a burst
            # workload — warm the whole decode ladder unless the caller
            # pinned one.
            if mixed_buckets is None:
                # the packed prefill's ladder from the decode region and
                # one block of prompt up to the budget
                blk = self._ragged_blk
                mixed_buckets = sorted(
                    {packed_prefill_bucket(r, blk) for r in range(
                        self._decode_region + blk,
                        scfg.mixed_token_budget + 1, blk)})
            if not decode_buckets:
                decode_buckets = sorted(
                    {self.scheduler.decode_bucket(n)
                     for n in range(1, scfg.max_num_seqs + 1)})
            # the mixed scheduler only ever dispatches "mixed"/"decode":
            # batched-prefill and prefill_chunk executables are
            # unreachable dead weight (seconds of XLA compile each at
            # production size)
            prefill_buckets = []
            chunk_buckets = ()
        # ragged executables to warm: (flat tokens, descriptor width, kind)
        ragged_warm = [(Tm, self._ragged_seqs, "mixed")
                       for Tm in sorted(set(mixed_buckets or ()))]
        if self._packed_prefill and prefill_buckets:
            # Packed route: no (B, L) program is ever dispatched.  What
            # replaces the caller's list is derivable from config, so the
            # engine warms it itself: every rung of the flat-token ladder
            # that a batch of those shapes can reach (B prompts of up to L
            # tokens, each aligned to the ragged block), at the one
            # descriptor width prefill dispatches use.
            blk = self._ragged_blk
            top = max(b * -(-n // blk) * blk for b, n in (
                bk if isinstance(bk, tuple) else (1, bk)
                for bk in prefill_buckets))
            ragged_warm += [(t, self._prefill_seqs, "prefill") for t in sorted(
                {packed_prefill_bucket(r, blk)
                 for r in range(blk, top + 1, blk)})]
            prefill_buckets = []
        decode_buckets = decode_buckets or [scfg.min_decode_bucket]
        chunk = scfg.prefill_chunk_size
        chunk_set = set(chunk_buckets)
        if not scfg.allow_chunked_prefill:
            chunk_set = set()     # no chunk route exists (pp engine)
        if (self.max_seq_len > chunk and scfg.allow_chunked_prefill
                and not scfg.mixed_batching):
            # long prompts hit the chunked path; the full-chunk
            # executable must be warm or the first long request stalls
            # the loop on a compile.  chunk_buckets adds the padded
            # tail shapes of non-multiple prompt lengths.
            chunk_set.add(chunk)
        # how many rows a prefill's pending first tokens can have
        # (PendingFirst.toks): the packed route's one descriptor width,
        # the (B, L) route's batches, 1 on the chunk route
        first_sizes = {self._prefill_seqs for _, _, kind in ragged_warm
                       if kind == "prefill"}
        first_sizes |= {bk[0] if isinstance(bk, tuple) else 1
                        for bk in prefill_buckets}
        if chunk_set:
            first_sizes.add(1)
        if ragged_warm and scfg.mixed_batching:
            # a mixed step leaves its tokens on the device at the ragged
            # descriptor width, for the next window's rows as for the
            # next mixed step's
            first_sizes.add(self._ragged_seqs)
        logits = None
        # Two rounds: round 1 compiles each executable against the cache
        # layouts it happens to see; the kv_cache arrays that come OUT may
        # carry different XLA-chosen layouts, and a jitted call whose input
        # layouts changed recompiles (observed as a 47 s stall on the first
        # real prefill despite a warmed identical shape).  Round 2 runs every
        # bucket again with the settled layouts, so the steady-state
        # executables all exist before the first request arrives.
        # All device work below goes through the _exec_* hooks: on a
        # multi-host slice the coordinator's warmup broadcasts every step to
        # the followers (already in follower_loop), so startup compiles in
        # lockstep instead of deadlocking the SPMD program (round-1 bug).
        for _round in range(2):
            for bucket in _warm_each("prefill", _round, prefill_buckets):
                B, L = bucket if isinstance(bucket, tuple) else (1, bucket)
                tokens = jnp.zeros((B, L), jnp.int32)
                lens = jnp.ones((B,), jnp.int32)
                slots = jnp.full((B, L), PAD_SLOT, jnp.int32)
                wkw = self._row_kw([], B)
                logits, self.kv_cache = self._exec_prefill(tokens, lens,
                                                           slots, **wkw)
                self._warm_sampling(logits, sample_modes)
            for B in _warm_each("decode", _round, decode_buckets):
                tokens = jnp.zeros((B,), jnp.int32)
                positions = jnp.zeros((B,), jnp.int32)
                slots = jnp.full((B,), PAD_SLOT, jnp.int32)
                bt = jnp.zeros((B, self.cache_cfg.max_blocks_per_seq), jnp.int32)
                seq_lens = jnp.ones((B,), jnp.int32)
                wkw = self._row_kw([], B)
                logits, self.kv_cache = self._exec_decode(
                    tokens, positions, slots, bt, seq_lens, **wkw)
                self._warm_sampling(logits, sample_modes)
                if self._multi_step > 1:
                    # the windowed executable is the steady-state decode
                    # path; left cold it stalls the first real window.
                    # Adaptive sizing adds the latency window's executable
                    # (min_multi_step) — it must be warm too or the first
                    # arrival-into-busy-engine stalls on ITS compile.
                    active = jnp.zeros((B,), bool)
                    keys = jnp.zeros((B, 2), jnp.uint32)
                    temp = jnp.zeros((B,), jnp.float32)
                    sizes = {self._multi_step}
                    if self._adaptive_window:
                        sizes.add(self._min_multi_step)
                    for mode in ("greedy", "temperature", "full"):
                        if mode != "greedy" and mode not in sample_modes:
                            continue
                        mkw = dict(wkw)
                        if mode == "full":
                            # truncated sampling runs inside the window
                            # too (window_sample mode="full") — its
                            # executable must be warm or the first top-p
                            # request stalls the loop on a compile
                            mkw.update(
                                top_k=jnp.zeros((B,), jnp.int32),
                                top_p=jnp.ones((B,), jnp.float32),
                                min_p=jnp.zeros((B,), jnp.float32))
                        # in-window logprobs is one extra variant per
                        # (mode, steps) — logprobs_n is FIXED at
                        # MAX_LOGPROBS by the dispatch for exactly this
                        # reason; cold, the first logprobs request
                        # stalls on a full window-trunk compile
                        lp_variants = ((0, self.MAX_LOGPROBS)
                                       if "logprobs" in sample_modes
                                       else (0,))
                        # every mode can carry penalties (greedy +
                        # repetition_penalty is one of the most common
                        # penalized configs) — a cold variant stalls the
                        # loop on a window-trunk compile mid-serving
                        pen_variants = ((False, True)
                                        if not {"penalties", "bias",
                                                "min_tokens"}.isdisjoint(
                                            sample_modes)
                                        else (False,))
                        for steps in sorted(sizes):
                            for lp_n in lp_variants:
                                for pen in pen_variants:
                                    if lp_n and pen:
                                        # logprobs+penalties in one batch
                                        # is rare — compile on demand
                                        # rather than double warmup again
                                        continue
                                    lkw = dict(mkw)
                                    if lp_n:
                                        lkw["logprobs_n"] = lp_n
                                    if pen:
                                        V = self.model_cfg.vocab_size
                                        lkw.update(
                                            counts=jnp.zeros((B, V),
                                                             jnp.float32),
                                            presence=jnp.zeros((B,),
                                                               jnp.float32),
                                            frequency=jnp.zeros((B,),
                                                                jnp.float32),
                                            repetition=jnp.ones((B,),
                                                                jnp.float32),
                                            bias=jnp.zeros((B, V),
                                                           jnp.float32),
                                            floor_bias=jnp.zeros(
                                                (B, V), jnp.float32),
                                            floor_remaining=jnp.zeros(
                                                (B,), jnp.int32))
                                    res = self._exec_decode_multi(
                                        tokens, positions, bt, seq_lens,
                                        active, keys, temp, steps=steps,
                                        mode=mode, **lkw)
                                    self.kv_cache = res[1]
                                    if lp_n:
                                        self._warm_tails.append(res[2])
                if self._pipeline_decode:
                    # the pipelined paths chain steps/windows through
                    # _select_tokens; left cold, its (tiny) compile stalls
                    # the first chained dispatch mid-serving.  Both call
                    # sites pass (B,) int32 tokens (the windowed one via
                    # p.toks[:, -1]), so one shape covers them.
                    self._warm_tails.append(_select_tokens(
                        jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B,), bool)))
                    # and a window's rows take a pending prefill's first
                    # tokens the same way, from a vector of each size a
                    # prefill can leave, into every decode bucket
                    for n in sorted(first_sizes - {B}):
                        self._warm_tails.append(_select_tokens(
                            jnp.zeros((n,), jnp.int32),
                            jnp.zeros((B,), jnp.int32),
                            jnp.zeros((B,), jnp.int32),
                            jnp.zeros((B,), bool)))
                    if self._multi_step > 1:
                        # a window's tail (PendingWindow.tail), each size
                        self._warm_tails += [
                            jnp.zeros((B, steps), jnp.int32)[:, -1]
                            for steps in sorted(sizes)]
                if self._spec is not None:
                    # the speculative verify pass is its own executable;
                    # left cold, the first spec step stalls on its compile
                    K = self._spec.num_draft_tokens + 1
                    vtok = jnp.zeros((B, K), jnp.int32)
                    vslots = jnp.full((B, K), PAD_SLOT, jnp.int32)
                    _, self.kv_cache = self._exec_decode_verify(
                        vtok, jnp.zeros((B,), jnp.int32),
                        jnp.ones((B,), jnp.int32), vslots, bt)
                    if any(m in sample_modes
                           for m in ("temperature", "full")):
                        # sampled batches verify through the
                        # rejection-sampling twin — its executable must
                        # be warm too
                        acc, _, self.kv_cache = \
                            self._exec_decode_verify_sampled(
                                vtok, jnp.zeros((B,), jnp.int32),
                                jnp.ones((B,), jnp.int32), vslots, bt,
                                jnp.zeros((B, 2), jnp.uint32),
                                jnp.zeros((B,), jnp.float32),
                                jnp.zeros((B,), jnp.int32),
                                jnp.ones((B,), jnp.float32),
                                jnp.zeros((B,), jnp.float32))
                        self._warm_tails.append(acc)
            for C in _warm_each("chunk", _round, sorted(chunk_set)):
                tokens = jnp.zeros((1, C), jnp.int32)
                slots = jnp.full((1, C), PAD_SLOT, jnp.int32)
                bt = jnp.zeros((1, self.cache_cfg.max_blocks_per_seq),
                               jnp.int32)
                ckw = self._row_kw([], 1)
                logits, self.kv_cache = self._exec_prefill_chunk(
                    tokens, jnp.zeros((1,), jnp.int32),
                    jnp.ones((1,), jnp.int32), slots, bt, **ckw)
                self._warm_sampling(logits, sample_modes)
            for Tm, Bm, kind in _warm_each("ragged", _round, ragged_warm):
                # ragged trunk (mixed steps, packed prefills): one
                # executable per flat-token bucket (the whole point — no
                # (batch x length) grid); left cold, the first
                # admission-under-load step stalls the loop on its compile
                blkm = self._ragged_blk
                Tm = -(-Tm // blkm) * blkm
                mbm = self.cache_cfg.max_blocks_per_seq
                mkw = {}
                if self._lora_names:
                    mkw["ad"] = jnp.zeros((Tm, len(self._lora_names)),
                                          jnp.float32)
                # host arrays, as a served dispatch hands over: an eager
                # jnp.full of each new shape is a small program of its
                # own to load, three a rung
                logits, self.kv_cache = self._exec_forward_ragged(
                    *map(jnp.asarray, (
                        np.zeros((Tm,), np.int32),
                        np.zeros((Tm,), np.int32),
                        np.full((Tm,), PAD_SLOT, np.int32),
                        np.zeros((Tm,), np.int32),
                        np.zeros((Bm, mbm), np.int32),
                        np.zeros((Bm,), np.int32),
                        np.full((Bm,), Tm, np.int32),
                        np.zeros((Bm,), np.int32),
                        np.zeros((2,), np.int32),
                        np.full((Tm // blkm,), -1, np.int32),
                        np.zeros((Bm,), np.int32))), **mkw, kind=kind)
                self._warm_sampling(logits, sample_modes)
                if kind == "mixed" and self._pipeline_decode:
                    # a mixed step's decode rows take their tokens on the
                    # device from the record in flight: a window's tail at
                    # any decode bucket, a mixed step's own tokens
                    for n in sorted({*decode_buckets, Bm}):
                        self._warm_tails.append(_select_tokens(
                            jnp.zeros((n,), jnp.int32),
                            jnp.zeros((Tm,), jnp.int32),
                            jnp.zeros((Tm,), jnp.int32),
                            jnp.zeros((Tm,), bool)))
        if self._kv_tiers is not None:
            # tiered KV cache: the demote gather and restore scatter pad
            # their block axis to a power of two — warm the small end of
            # that ladder so the first eviction burst doesn't stall the
            # loop on page-copy compiles (bigger buckets compile on
            # demand; they only occur under heavy pressure)
            from tpuserve.runtime.kv_cache import (gather_block_pages,
                                                   scatter_block_pages)
            for n in (1, 2, 4, 8, 16):
                pages = gather_block_pages(self.kv_cache, [0] * n)
                self.kv_cache = scatter_block_pages(self.kv_cache,
                                                    [0] * n, pages)
        if embed_buckets:
            if self._pp > 1:
                raise ValueError("embeddings not supported on the pipeline "
                                 "engine (Engine.embed is gated)")
            # embeddings executables are independent of the KV cache —
            # one pass suffices (no layout round-trip to settle)
            from tpuserve.models.transformer import embed_forward
            for B, T in embed_buckets:
                self._warm_tails.append(embed_forward(
                    self.params, self.model_cfg,
                    jnp.zeros((B, T), jnp.int32), jnp.ones((B,), jnp.int32)))
        # Block on every queued chain, or the first real request pays for
        # the warmup backlog as TTFT: the KV cache — every model
        # executable donates it through, so its chain covers all queued
        # model work (the last logits only cover their own executable) —
        # plus every sampler / token-select warmup output, which consume
        # logits but never touch the cache, so each queued execution sits
        # on a chain of its own.
        jax.block_until_ready((self.kv_cache, self._warm_tails))
        self._warm_tails.clear()
        self._read_demote_budget()
        # (a trunk traces its layer once a KIND under its own jax.jit,
        # models/transformer.py: this process's counts, by body)
        logger.info("warmup complete: prefill buckets %s, ragged buckets %s, "
                    "decode buckets %s; layer bodies traced %d for %d layer "
                    "calls %s", prefill_buckets,
                    [(kind, t) for t, _, kind in ragged_warm], decode_buckets,
                    sum(LAYER_TRACES.values()), sum(LAYER_CALLS.values()),
                    {body: (LAYER_TRACES[body], n)
                     for body, n in LAYER_CALLS.items() if n})

    def _warm_sampling(self, logits: jnp.ndarray,
                       modes: Sequence[str]) -> None:
        """Compile the samplers for this logits shape so no request ever
        stalls the serving loop on a sampler compile.  'full' sorts the
        vocab — by far the slowest compile — so latency-sensitive callers
        that only ever sample greedily can pass a reduced mode list."""
        B = logits.shape[0]
        keys, temp, top_k, top_p = self._greedy_dummies(B)
        for mode in modes:
            self._warm_tails.append(self._exec_sample(
                logits, keys, temp, top_k, top_p, mode=mode))
            if mode == "full":
                # min_p adds an operand to the full sampler: its own trace
                self._warm_tails.append(self._exec_sample(
                    logits, keys, temp, top_k, top_p,
                    min_p=jnp.zeros((B,)), mode="full"))
