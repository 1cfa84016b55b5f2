"""Prometheus serving metrics with vLLM-compatible metric families.

The reference's observability stack scrapes vLLM pods by the
``prometheus.io/scrape`` annotation and queries ``vllm_request_total``,
``vllm_active_requests``, ``vllm_request_duration_seconds`` and friends
(reference: otel-observability-setup.yaml:337-391 scrape job,
:728,:758-761 verification queries).  Emitting the same families means the
ported scrape config and Grafana cookbook carry over unchanged.
"""

from __future__ import annotations

from prometheus_client import (CollectorRegistry, Counter, Gauge, Histogram,
                               generate_latest)

_TTFT_BUCKETS = (0.01, 0.025, 0.05, 0.075, 0.1, 0.15, 0.25, 0.5, 1.0, 2.5,
                 5.0, 10.0)
_ITL_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)
_DURATION_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)
# Per-SLO-class SLI bucket edges (the burn-rate engine's quantization
# grid): PromQL can only evaluate a latency objective AT a bucket edge,
# so every edge here is a legal objective threshold and
# tpuserve/obs/objectives.py rejects thresholds between edges.  e2e
# historically reused _DURATION_BUCKETS, whose first edge is 100ms —
# blind exactly where a fast interactive class lives, which silently
# flattened burn-rate math for any sub-100ms target (ISSUE 13 bucket
# audit).  Edges are PINNED by tests/test_obs.py: changing them is an
# objectives-compatibility decision, not a tuning tweak.
_SLI_E2E_BUCKETS = (0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                    30.0, 60.0, 120.0)
SLI_BUCKETS = {"ttft": _TTFT_BUCKETS, "itl": _ITL_BUCKETS,
               "e2e": _SLI_E2E_BUCKETS}


class ServerMetrics:
    """Per-server metric registry (isolated so tests can run many servers)."""

    def __init__(self, model_name: str):
        self.registry = CollectorRegistry()
        self.model_name = model_name
        label = {"model_name": model_name}

        def counter(name, doc):
            return Counter(name, doc, ["model_name"],
                           registry=self.registry).labels(**label)

        def gauge(name, doc):
            return Gauge(name, doc, ["model_name"],
                         registry=self.registry).labels(**label)

        def histogram(name, doc, buckets):
            return Histogram(name, doc, ["model_name"], buckets=buckets,
                             registry=self.registry).labels(**label)

        # The families the reference's verification queries look for:
        self.request_total = counter(
            "vllm_request_total", "Total requests received")
        self.active_requests = gauge(
            "vllm_active_requests", "Requests currently running or queued")
        self.request_duration = histogram(
            "vllm_request_duration_seconds", "End-to-end request latency",
            _DURATION_BUCKETS)
        # Standard vLLM serving families:
        self.request_success = Counter(
            "vllm_request_success", "Finished requests by reason",
            ["model_name", "finished_reason"], registry=self.registry)
        self.prompt_tokens = counter(
            "vllm_prompt_tokens", "Prefill tokens processed")
        self.generation_tokens = counter(
            "vllm_generation_tokens", "Tokens generated")
        self.ttft = histogram(
            "vllm_time_to_first_token_seconds", "Time to first token",
            _TTFT_BUCKETS)
        self.itl = histogram(
            "vllm_time_per_output_token_seconds", "Inter-token latency",
            _ITL_BUCKETS)
        self.kv_usage = gauge(
            "vllm_kv_cache_usage_perc", "Fraction of KV blocks in use")
        self.preemptions = counter(
            "vllm_num_preemptions", "Sequences preempted and re-prefilled")
        self.running = gauge(
            "vllm_num_requests_running", "Requests in the decode batch")
        self.waiting = gauge(
            "vllm_num_requests_waiting", "Requests queued for prefill")
        self.window_overrun = counter(
            "tpuserve_window_overrun_tokens",
            "Tokens computed past a request's stop point by fused "
            "multi-step windows and dropped at emit (the cost knob for "
            "--multi-step; no vLLM analog)")
        self.prefix_hits = counter(
            "tpuserve_prefix_cache_hits",
            "Prefix-cache lookups that found at least one cached block "
            "(vLLM gpu_prefix_cache_hit_rate analog: divide by queries)."
            "  Counted once PER LOOKUP, exactly like queries — the two "
            "must share a unit or the hit-rate gauge lies when the "
            "first block already misses")
        self.prefix_queries = counter(
            "tpuserve_prefix_cache_queries",
            "Prefix-cache lookups performed (one per real admission "
            "lookup; scheduler routing peeks don't count)")
        self.spec_proposed = counter(
            "tpuserve_spec_draft_tokens_proposed",
            "Draft tokens offered to the speculative verifier (vLLM "
            "spec_decode_num_draft_tokens analog)")
        self.spec_accepted = counter(
            "tpuserve_spec_draft_tokens_accepted",
            "Draft tokens accepted by the verifier; divide by proposed "
            "for the live acceptance rate")
        self.spec_pauses = counter(
            "tpuserve_spec_adaptive_pauses",
            "Times the adaptive governor paused speculation for "
            "below-break-even acceptance (runtime/spec.py)")
        self.released_blocks = counter(
            "tpuserve_window_released_blocks",
            "KV blocks recycled by the sliding-window rolling buffer "
            "(runtime/block_manager.py release_out_of_window)")
        self.latency_windows = counter(
            "tpuserve_latency_windows",
            "Fused decode windows shrunk to min_multi_step because "
            "arrivals were landing into a busy engine (adaptive window "
            "sizing, runtime/engine.py _window_steps)")
        self.guided_fallbacks = counter(
            "tpuserve_guided_fallbacks",
            "Guided-decoding steps where the whole top-K was "
            "grammatically invalid and a structural fallback token was "
            "substituted — the signal that the constraint is fighting "
            "the model (runtime/engine.py _guided_pick)")
        self.guided_fsm_requests = counter(
            "tpuserve_guided_fsm_requests",
            "Guided requests served by compiled grammar-FSM logit masks "
            "(runtime/grammar/) — the distribution-correct path that "
            "rides fused windows; guided traffic NOT counted here ran "
            "the per-step substitution fallback")
        self.step_padded_tokens = gauge(
            "tpuserve_step_padded_tokens",
            "Tokens dispatched by the engine's last step INCLUDING "
            "bucket/alignment padding — compare against "
            "tpuserve_step_actual_tokens to see what the static-shape "
            "buckets cost.  Mixed ragged batching collapses the "
            "(batch x length) grid to one flat-token bucket, which is "
            "exactly the gap these two gauges make observable")
        self.step_actual_tokens = gauge(
            "tpuserve_step_actual_tokens",
            "Real (non-padding) tokens computed by the engine's last "
            "step")
        self.padded_tokens_total = counter(
            "tpuserve_padded_tokens_total",
            "Cumulative dispatched tokens including padding; with "
            "tpuserve_actual_tokens_total this gives the live padding "
            "efficiency ratio for before/after bucketing comparisons")
        self.actual_tokens_total = counter(
            "tpuserve_actual_tokens_total",
            "Cumulative real tokens computed across all engine steps")
        self.prefill_tokens_total = counter(
            "tpuserve_prefill_tokens_total",
            "Real prompt tokens computed by batched-prefill and "
            "prefill-chunk dispatches (mixed steps excluded)")
        self.prefill_padded_tokens_total = counter(
            "tpuserve_prefill_padded_tokens_total",
            "Token slots those dispatches occupied, padding included: "
            "over tpuserve_prefill_tokens_total, what prefill bucketing "
            "costs (decode pads little, so the all-steps pair hides it)")
        self.prefill_packed_steps = counter(
            "tpuserve_prefill_packed_steps_total",
            "Batched prefills dispatched packed on one flat token axis "
            "through the ragged trunk (single chip, no mesh, pages in the "
            "model's dtype) rather than as a (batch x length) grid")
        self.prefill_kv_tokens_paged = counter(
            "tpuserve_prefill_kv_tokens_paged_total",
            "Of tpuserve_prefill_tokens_total, the prompt tokens whose K "
            "and V went into the paged cache one copy a page (packed "
            "prefills and whole-page chunks with the Pallas kernels on) "
            "rather than one scatter row a token")
        self.kv_latent_tokens_attended = counter(
            "tpuserve_kv_latent_tokens_attended_total",
            "Context tokens that decode, window (at its first step), "
            "verify and mixed dispatches attended against LATENT pages "
            "(latent attention: one compressed K/V row a token a layer), "
            "the step records' ctx_tokens summed; 0 for a model whose "
            "cache holds K and V pages")
        self.first_tokens_deferred = counter(
            "tpuserve_prefill_first_tokens_deferred",
            "Prefilled requests whose first token was still on the device "
            "when the next dispatch was enqueued: the host read it behind "
            "that dispatch, so the chip's queue did not drain after the "
            "prefill (pipelined decode, runtime/engine.py _flush_first)")
        self.first_tokens_flushed_early = counter(
            "tpuserve_prefill_first_tokens_flushed_early",
            "Prefilled requests whose first token the host read BEFORE "
            "the next dispatch (penalties, an active min_tokens floor, "
            "guided rows, a dispatch that is not a fused window, an engine "
            "that does not pipeline); deferred / (deferred + flushed "
            "early) is the share of prefills that no longer drain the "
            "chip's queue")
        self.mixed_steps = counter(
            "tpuserve_mixed_steps",
            "Ragged mixed prefill+decode dispatches (scheduler mixed "
            "mode) — zero under admission load means the engine is "
            "phase-splitting")
        self.decode_tokens_ridden = counter(
            "tpuserve_decode_tokens_ridden",
            "Answer tokens produced by a dispatch that also carried prompt "
            "tokens (a mixed step's decode rows): the weights were read "
            "once for both; over vllm generation tokens, the share of "
            "decode that rode a prompt dispatch (0 on an engine whose "
            "decode is bound by its K/V read, /debug/engine decode_route)")
        self.guided_fsm_windows = counter(
            "tpuserve_guided_fsm_windows",
            "Fused multi-step windows that carried grammar-FSM masks — "
            "zero under guided load means constraints are pinning "
            "decode to per-step dispatches")
        self.requests_salvaged = counter(
            "tpuserve_requests_salvaged_total",
            "Requests re-queued through the preemption re-prefill path "
            "after a faulted/stuck engine step and replayed "
            "token-identically (crash-only salvage, server/runner.py) — "
            "each count is a stream that would have died under the "
            "reference's pod-restart-only recovery")
        self.requests_poisoned = counter(
            "tpuserve_requests_poisoned_total",
            "Requests isolated as poison by fault bisection (or out of "
            "salvage budget) and failed with a per-request error while "
            "the rest of their batch resumed — a poisoned batch costs "
            "one request, not a batch")
        self.watchdog_trips = counter(
            "tpuserve_engine_watchdog_trips",
            "Engine dispatches declared stuck by the hang watchdog "
            "(past step_watchdog_s) — the realistic TPU failure mode, "
            "where the device call blocks instead of raising")
        self.engine_restarts = counter(
            "tpuserve_engine_restarts",
            "Whole-engine fail-all fallbacks: fault storms past the "
            "salvage window, unrecoverable hangs, or engines without "
            "the salvage hook — each count failed every in-flight "
            "stream (the pre-salvage crash-only behaviour)")
        # Tiered KV cache (runtime/kv_tiers.py): per-tier residency plus
        # the demote/restore/spill flow.  tier= one of "hbm" (freed-but-
        # hashed blocks parked in the device cached pool), "host"
        # (demoted pages in host DRAM under the byte budget), "spill"
        # (PVC .npz overflow).
        self.kv_tier_blocks = Gauge(
            "tpuserve_kv_tier_blocks",
            "Prefix-cache KV blocks resident per tier (exactly-one-tier "
            "invariant: a chain hash resolves in hbm, host, OR spill)",
            ["model_name", "tier"], registry=self.registry)
        self.kv_demoted = counter(
            "tpuserve_kv_blocks_demoted",
            "Prefix blocks demoted out of HBM into the host-DRAM tier "
            "instead of destroyed on eviction (tiered KV cache; "
            "TPUSERVE_KV_TIERS=0 restores destroy-on-evict)")
        self.kv_demote_declined = counter(
            "tpuserve_kv_blocks_demote_declined",
            "Evicted prefix blocks the tier did not admit: their chain "
            "hash had never left HBM before, so nothing was copied and "
            "the KV died as with no tier; demoted / (demoted + declined) "
            "is the admitted share of evictions")
        # recurrent state (models with state-space layers beside
        # attention): one slot of state a running sequence, in a pool
        # beside the paged KV cache (tpuserve_hbm_bytes{kind="state"})
        self.ssm_state_slots = gauge(
            "tpuserve_ssm_state_slots",
            "Seats of the recurrent-state pool held by a sequence (taken "
            "with its first KV blocks, given back with them); 0 for a "
            "model without state-space layers")
        self.ssm_state_resets = counter(
            "tpuserve_ssm_state_resets",
            "Seats started from zeros for a sequence's first window: one "
            "an admission, and one more each time a pre-empted or "
            "salvaged sequence comes back")
        self.ssm_rebuilt_tokens = counter(
            "tpuserve_ssm_rebuilt_tokens",
            "Tokens prefilled AGAIN (prompt plus everything generated) "
            "because a pre-empted or salvaged sequence's recurrent state "
            "was dropped: the price of having no state snapshot")
        # what kv_bytes_per_token and the state pool's bytes were counted
        # over (ModelConfig.kv_layers / state_layers): a linear-attention
        # layer holds a state and no pages, an attention layer pages and
        # no state, Falcon-H1's every layer both
        self.kv_page_layers = gauge(
            "tpuserve_kv_page_layers",
            "Layers of the running model that hold K/V pages: what the "
            "paged cache's bytes a token are counted over")
        self.state_layers = gauge(
            "tpuserve_state_layers",
            "Layers of the running model that hold a recurrent state a "
            "sequence: what the seat pool's bytes are counted over; 0 for "
            "a model without such layers")
        # expert layers (models/transformer.py _moe_mlp): what the sparse
        # dispatch routed, counted on the device and read with each
        # dispatch's tokens (Engine._moe_note); all zero and the
        # per-expert family empty for a model without experts
        self.moe_routed_rows = counter(
            "tpuserve_moe_routed_rows",
            "Rows the sparse expert dispatch routed: tokens of a dispatch "
            "(its padding rows included) x experts a token, summed over "
            "the expert layers and a window's fused steps")
        self.moe_row_moves_plain = counter(
            "tpuserve_moe_row_moves_plain",
            "Of the two moves of each of tpuserve_moe_routed_rows around "
            "the grouped product (into expert order, and back), those made "
            "by the plain row gather, chosen from each dispatch's shapes; "
            "over twice that counter: the share of moves by the fast form")
        self.moe_expert_rows = Counter(
            "tpuserve_moe_expert_rows",
            "The same rows by the expert they were routed to (one index "
            "over every expert layer: expert e of each layer)",
            ["model_name", "expert"], registry=self.registry)
        self.moe_expert_load = gauge(
            "tpuserve_moe_expert_load_max_over_mean",
            "The busiest expert's DISPATCHED rows over the mean expert's, "
            "since start: what the grouped product's weight traffic in "
            "decode and its tile waste in prefill grow with.  Dispatch "
            "load, not routing skew: a dispatch's padding rows all carry "
            "token 0 and go to that token's experts, so evenly routed "
            "traffic reads 1.4-1.5 at the usual padding share, not 1.0")
        # a share of the experts (ModelConfig.moe_experts_held): all zero
        # for a model that holds every expert
        self.moe_held_rows = counter(
            "tpuserve_moe_held_rows",
            "Of tpuserve_moe_routed_rows, the rows that landed on the "
            "experts this process holds; the rest went to experts other "
            "chips of the deployment hold and are left out here")
        self.moe_held_hits = counter(
            "tpuserve_moe_held_hits",
            "Held expert-layers that got at least one row in a dispatch "
            "step: each reads its three kernels once")
        self.moe_buffer_rows = counter(
            "tpuserve_moe_buffer_rows",
            "Rows of buffer the expert layer gathered and multiplied for "
            "the held rows (whole pieces: over tpuserve_moe_held_rows by "
            "the last piece's slack)")
        self.moe_group_rows = counter(
            "tpuserve_moe_group_rows",
            "Behind a group-limited router, under a share: the rows one "
            "of whose surviving expert groups is held by this process, "
            "summed over the expert layers -- the rows a chip of the "
            "deployment is sent at all (over tpuserve_moe_routed_rows / "
            "experts per token: topk_group / n_group under even routing)")
        self.kda_state_row_layers = counter(
            "tpuserve_kda_state_row_layers",
            "Row-layers the channel-gated (Kimi-delta) state update served "
            "on decode: a decode dispatch's real tokens times the linear "
            "layers, each one state read and written")
        self.moe_experts_held = gauge(
            "tpuserve_moe_experts_held",
            "Experts of each expert layer this process holds (0: all)")
        # windows by layer kind: what an allocator by layer kind would
        # give back (ROADMAP M3)
        self.kv_window_dead_tokens = gauge(
            "tpuserve_kv_window_dead_tokens",
            "Token-layers of KV held for WINDOWED layers at positions "
            "more than the window plus one block behind their sequence's "
            "end, which no step will read again: zero where every layer "
            "is windowed (those blocks are released) or none is.  Over "
            "tpuserve_kv_pool_tokens x layers it is the share of the pool "
            "held for nothing")
        self.kv_pool_tokens = gauge(
            "tpuserve_kv_pool_tokens",
            "Capacity of the paged KV pool in tokens (blocks x block "
            "size): vllm_kv_cache_usage_perc is a fraction of it")
        self.kv_demote_waited = counter(
            "tpuserve_kv_blocks_demote_waited",
            "Demoted blocks whose device-to-host copy the engine loop had "
            "to wait for (the in-flight bound, or a restore of a hash "
            "still in flight); 1 - waited/demoted is the share of "
            "demotion copies hidden behind the chip's work")
        self.kv_spilled = counter(
            "tpuserve_kv_blocks_spilled",
            "Host-tier blocks cascaded to the PVC spill tier under "
            "host-byte-budget pressure")
        self.kv_tier_dropped = counter(
            "tpuserve_kv_blocks_tier_dropped",
            "Blocks that fell off the LAST tier (KV lost; the next "
            "reuse pays full prefill) — rising fast means the spill "
            "tier is undersized for the reuse window")
        self.kv_restored = counter(
            "tpuserve_kv_blocks_restored",
            "Prefix blocks copied back host->HBM ahead of admission "
            "(each one is a block of prefill compute a request skipped)")
        self.kv_restore_latency = histogram(
            "tpuserve_kv_restore_latency_seconds",
            "Tier-restore begin->commit wall time (the async copy "
            "overlaps the current dispatch; this is the admission hold, "
            "one engine cycle + copy tail)", _ITL_BUCKETS)
        # Overload robustness (runtime/slo.py): SLO classes + the
        # brownout ladder.  Shed/preempt counters partition overload's
        # cost by class; the level gauge says which degradation rung the
        # engine is on RIGHT NOW; the labelled queue-delay histogram is
        # the per-class admission-latency SLI the estimator steers by.
        self.requests_shed = counter(
            "tpuserve_requests_shed",
            "Requests rejected at intake by the brownout ladder or "
            "evicted from a full queue for a stricter-class arrival "
            "(HTTP 429 + Retry-After; no prefill was spent) — overload "
            "costs batch work first instead of degrading every class "
            "equally")
        self.requests_preempted = counter(
            "tpuserve_requests_preempted",
            "Running batch-class rows preempted to seat a "
            "stricter-class arrival (token-identical re-prefill "
            "replay; bounded per request by the preemption budget).  "
            "A subset of vllm_num_preemptions, which also counts "
            "decode-OOM evictions")
        self.requests_failed = counter(
            "tpuserve_requests_failed",
            "Terminal engine-decided failures routed to clients other "
            "than shed/poison (admission-deadline 504s, salvage-path "
            "errors) — with shed + poisoned, the bad-event families "
            "the availability SLO's PromQL twin reads, matching what "
            "the in-process burn-rate evaluator counts "
            "(tpuserve/obs/objectives.py)")
        self.brownout_level = gauge(
            "tpuserve_brownout_level",
            "Current graceful-degradation rung (0 normal, 1 spec off "
            "for batch, 2 batch max_tokens capped, 3 batch shed, 4 "
            "standard shed too) — entered on pressure immediately, "
            "exited hysteretically (runtime/slo.py)")
        self.queue_delay = Histogram(
            "tpuserve_queue_delay_seconds",
            "Admission queue delay per SLO class (slo_class= "
            "interactive|standard|batch): arrival to first prefill "
            "scheduling, fresh admissions only — the per-class SLI the "
            "overload estimator steers the brownout ladder by "
            "(sub-100ms edges: an interactive queue should sit well "
            "under the old 100ms first bucket)",
            ["model_name", "slo_class"], buckets=_SLI_E2E_BUCKETS,
            registry=self.registry)
        # Flight-recorder SLIs (runtime/flight.py): the CLIENT-observable
        # latency contract per SLO class, measured at output delivery in
        # the runner loop (queueing, salvage replays and brownout
        # degradation all included — unlike the engine-internal
        # vllm_time_* families, these carry the slo_class label the
        # brownout ladder and the future autoscaler steer by).
        self.ttft_class = Histogram(
            "tpuserve_ttft_seconds",
            "Client-observable time to first token per SLO class "
            "(slo_class=interactive|standard|batch) — the per-class "
            "twin of vllm_time_to_first_token_seconds the brownout "
            "controller logs level transitions against",
            ["model_name", "slo_class"], buckets=_TTFT_BUCKETS,
            registry=self.registry)
        self.itl_class = Histogram(
            "tpuserve_itl_seconds",
            "Client-observable inter-token latency per SLO class "
            "(slo_class= label; re-prefill replay gaps excluded like "
            "vllm_time_per_output_token_seconds)",
            ["model_name", "slo_class"], buckets=_ITL_BUCKETS,
            registry=self.registry)
        self.e2e_class = Histogram(
            "tpuserve_e2e_seconds",
            "Client-observable end-to-end request latency per SLO "
            "class (slo_class= label; submit to finish).  Buckets "
            "include sub-100ms edges (SLI_BUCKETS) so burn-rate math "
            "resolves fast classes",
            ["model_name", "slo_class"], buckets=_SLI_E2E_BUCKETS,
            registry=self.registry)
        self.flight_postmortems = counter(
            "tpuserve_flight_postmortems",
            "Post-mortem bundles written by the engine flight recorder "
            "(watchdog trip, fault-storm fail-all, poison isolation) — "
            "each count is a JSON file of the last N engine cycles + "
            "affected request timelines under TPUSERVE_FLIGHT_DIR "
            "(/debug/engine reports the newest path)")
        self.replay_dumps = counter(
            "tpuserve_replay_dumps",
            "Replay-ready flight bundles exported on demand via "
            "GET /debug/engine/dump (tools/replay.py dump) — unlike "
            "post-mortems these capture a HEALTHY engine's recent "
            "timelines for trace-driven replay (tpuserve/replay/)")
        # Multi-tenant metering (server/tenants.py): tenant = API key /
        # LoRA adapter.  Label cardinality is bounded by the configured
        # tenant set (+ "default").
        self.tenant_tokens = Counter(
            "tpuserve_tenant_tokens",
            "Tokens served per tenant (prompt + generated; settled "
            "against the estimate the rate limiter charged at "
            "admission) — the metering source for per-tenant billing "
            "and the token-bucket rate limits",
            ["model_name", "tenant"], registry=self.registry)
        self.tenant_rate_limited = Counter(
            "tpuserve_tenant_rate_limited",
            "Requests rejected 429 by a tenant's token-bucket rate "
            "limit (Retry-After = time until the bucket refills "
            "enough)",
            ["model_name", "tenant"], registry=self.registry)
        # SLO evaluation (tpuserve/obs): the in-process burn-rate engine
        # runs off the same SLI stream the histograms above export, so a
        # pod can report its own SLO state without a Prometheus in the
        # loop (and the PromQL rules gen_alerts.py compiles from the
        # same objectives registry are the fleet-level twin).
        self.slo_burn_rate = Gauge(
            "tpuserve_slo_burn_rate",
            "Long-window error-budget burn rate per declared SLO "
            "objective and alert window (objective= from "
            "tpuserve/obs/objectives.py, window= fast|slow).  1.0 = "
            "burning exactly the budget; the window's factor (e.g. "
            "14.4 fast) is the firing threshold",
            ["model_name", "objective", "window"], registry=self.registry)
        self.slo_alerts_firing = gauge(
            "tpuserve_slo_alerts_firing",
            "SLO burn-rate alerts currently firing in-process (count "
            "over objective x window pairs) — nonzero means this pod "
            "is eating error budget fast enough to page, even if the "
            "Prometheus stack is down")
        self.slo_transitions = Counter(
            "tpuserve_slo_alert_transitions",
            "In-process burn-rate alert state transitions (state= "
            "firing|resolved, objective=, window=) — the replay "
            "backtester (tools/replay.py backtest) reproduces exactly "
            "this sequence from a recorded incident",
            ["model_name", "objective", "window", "state"],
            registry=self.registry)
        self.canary_requests = counter(
            "tpuserve_canary_requests",
            "Synthetic canary probes served by this pod (tagged "
            "X-TPUServe-Canary; excluded from tenant metering and "
            "every production SLI histogram — this counter is the "
            "proof they still flow through the real path)")
        # Device telemetry (runtime/devprof.py): the engine's own view of
        # device time, HBM occupancy, and the bucketed-executable ladder —
        # the step-time/HBM breakdowns the reference's DCGM-only GPU
        # metrics never had (PARITY.md).
        self.hbm_bytes = Gauge(
            "tpuserve_hbm_bytes",
            "Per-device HBM watermark by kind= weights (loaded param "
            "bytes, draft included), kv (the paged cache's full static "
            "reservation), state (the recurrent-state pool of a model "
            "with state-space layers: one slot a decode seat), other "
            "(workspace/fragmentation the backend reports beyond "
            "weights+kv+state) — reconciled against jax memory_stats at "
            "engine construction",
            ["model_name", "kind"], registry=self.registry)
        self.hbm_headroom = gauge(
            "tpuserve_hbm_headroom_bytes",
            "Detected HBM budget minus weights+kv+other — what is left "
            "before the next ladder bucket, draft model, or KV resize "
            "OOMs; the generated hbm-headroom-low warning fires on the "
            "ratio of this to the budget")
        self.device_seconds = Counter(
            "tpuserve_device_seconds",
            "Host seconds blocked in the engine's designated device_get "
            "sync points, by sync kind= window|decode|sample|verify|"
            "draft|guided — the measurable device time of the pipelined "
            "design (an underestimate of raw device compute: overlapped "
            "work never blocks)",
            ["model_name", "kind"], registry=self.registry)
        self.trunk_layer_traces = Counter(
            "tpuserve_trunk_layer_traces",
            "Layer bodies of the model's trunks that JAX traced in this "
            "process, by body= prefill|chunk|decode|ragged|nocache: a "
            "trunk hands its per-layer body to a function under its own "
            "jax.jit (models/transformer.py), so a program traces and "
            "lowers it once a KIND of layer, not once a layer",
            ["model_name", "body"], registry=self.registry)
        self.trunk_layer_calls = Counter(
            "tpuserve_trunk_layer_calls",
            "Calls of those bodies made while programs were traced, one "
            "a layer a program; traces over calls is the share of a "
            "start's layer tracing that was not saved (Qwen3-0.6B, 28 "
            "layers of one kind: at most 1 in 28)",
            ["model_name", "body"], registry=self.registry)
        self.exec_compiles = counter(
            "tpuserve_executable_compiles",
            "First-dispatch XLA compiles observed by the executable "
            "ladder (one per (dispatch kind, bucket) pair) — a rising "
            "rate in steady state is a compile storm: bucket ladders "
            "too fine, or an unbounded shape leaking into a dispatch")
        self.exec_compile_seconds = counter(
            "tpuserve_executable_compile_seconds",
            "Wall seconds spent inside first-dispatch compile brackets "
            "— the serving stall each new executable cost (warmup "
            "prepays the planned ladder; this counts the rest)")
        self.execs_retained = gauge(
            "tpuserve_executables_retained",
            "Distinct (dispatch kind, bucket) executables the ladder "
            "has ever dispatched and jit retains — ladder bloat is HBM "
            "spent on compiled code, bounded by design by the "
            "power-of-2 bucketing")
        # The process's compile ledger (utils/compile_cache.py: JAX's own
        # monitoring events, process-wide) and the start-up spans
        # (runtime/hostprof.py STARTUP).  One sample each: what a pod
        # restart or a scale-up spent before its first token, and in
        # steady state which stage a compile stall was.
        self.jit_trace_seconds = counter(
            "tpuserve_jit_trace_seconds",
            "Seconds this process spent tracing Python to jaxprs (self "
            "time: a layer body traced inside a trunk counts once) — "
            "paid again at every start, whatever the compile cache holds")
        self.jit_lower_seconds = counter(
            "tpuserve_jit_lower_seconds",
            "Seconds this process spent lowering jaxprs to MLIR modules "
            "— paid again at every start, like tracing")
        self.backend_compile_seconds = counter(
            "tpuserve_backend_compile_seconds",
            "Seconds inside the backend's part of readying programs: "
            "XLA compiles, or the persistent cache's reads in their "
            "place (tpuserve_compile_cache_read_seconds_total is that "
            "part)")
        self.compile_cache_read_seconds = counter(
            "tpuserve_compile_cache_read_seconds",
            "Seconds of tpuserve_backend_compile_seconds_total that were "
            "reads of the persistent compile cache (file read, "
            "decompress, deserialize, load)")
        self.compile_requests = counter(
            "tpuserve_compile_requests",
            "Programs this process asked its backend for, compiled or "
            "read back — rising in steady state is a stall in the engine "
            "loop each time: a shape that was not warmed")
        self.compile_cache_hits = counter(
            "tpuserve_compile_cache_hits",
            "Compile requests the persistent cache answered; misses / "
            "(hits + misses) near 0 is a warm start, near 1 a first run "
            "— the first thing to read on a slow scale-up")
        self.compile_cache_misses = counter(
            "tpuserve_compile_cache_misses",
            "Compile requests XLA compiled and wrote to the persistent "
            "cache (a compile the cache declines to keep counts in "
            "neither: requests - hits - misses is compiled at every "
            "start)")
        self.startup_build_seconds = gauge(
            "tpuserve_startup_build_seconds",
            "Seconds under the startup.build span: flags to the server "
            "object — backend start, weights, cache pools; no warm-up")
        self.startup_warmup_seconds = gauge(
            "tpuserve_startup_warmup_seconds",
            "Seconds under the startup.warmup span (Engine.warmup, every "
            "call): readying and running each executable of the ladder "
            "once; /debug/engine startup.phases splits it by family")
        self.profile_captures = counter(
            "tpuserve_profile_captures",
            "jax.profiler traces captured on demand (POST "
            "/debug/profile) or by the fast-burn SLO auto-capture hook "
            "— trace dirs land under TPUSERVE_FLIGHT_DIR beside the "
            "post-mortem bundles that reference them")
        # Model pool (tpuserve/modelpool): weight tiering + hot-swap so
        # one replica serves a catalog.  TPUSERVE_MODELPOOL=0 (or no
        # catalog) leaves these families at zero.
        self.model_swaps = Counter(
            "tpuserve_model_swaps",
            "Model hot-swaps executed at engine idle boundaries, by "
            "outcome= the source tier the incoming weights restored "
            "from: resident (HBM co-resident — no copy, no XLA), host "
            "(DRAM restore; warm jit/XLA caches skip compilation), "
            "spill (PVC restore), cold (full checkpoint load / init)",
            ["model_name", "outcome"], registry=self.registry)
        self.model_swap_seconds = histogram(
            "tpuserve_model_swap_seconds",
            "Drain-boundary-to-serving wall time of each model hot-swap "
            "(weight restore + engine rebuild; warm swaps reuse the "
            "in-process jit cache and the persistent XLA compile cache, "
            "so they sit orders of magnitude left of cold ones)",
            _COLD_START_BUCKETS)
        self.weight_tier_bytes = Gauge(
            "tpuserve_weight_tier_bytes",
            "Model/LoRA weight bytes resident per tier= hbm (the "
            "serving params plus co-resident sets), host (DRAM tier "
            "under TPUSERVE_WEIGHT_HOST_BYTES), spill (PVC tier) — the "
            "weight twin of tpuserve_kv_tier_blocks",
            ["model_name", "tier"], registry=self.registry)
        self.models_resident = gauge(
            "tpuserve_models_resident",
            "Catalog models with weights live in HBM right now (the "
            "serving model + co-resident sets, <= max_resident) — the "
            "co-serving occupancy the gateway's catalog routing and "
            "the per-model scale-from-zero signal key on")

    def observe_finish(self, reason: str, duration_s: float) -> None:
        self.request_success.labels(model_name=self.model_name,
                                    finished_reason=reason).inc()
        self.request_duration.observe(duration_s)

    def set_layer_kinds(self, model_cfg) -> None:
        """The two gauges of layers by the memory they hold."""
        self.kv_page_layers.set(len(model_cfg.kv_layers))
        self.state_layers.set(len(model_cfg.state_layers))

    def render(self) -> bytes:
        return generate_latest(self.registry)


class CanaryMetrics:
    """The synthetic prober's own registry (tpuserve/obs/canary.py):
    black-box SLIs measured from OUTSIDE the serving process, per SLO
    class, through whatever path the prober was pointed at (gateway ->
    server -> engine in production).  Served from the gateway's
    ``/metrics`` when its embedded prober is enabled, or from a
    standalone prober process."""

    def __init__(self):
        self.registry = CollectorRegistry()
        self.probes = Counter(
            "tpuserve_canary_probes",
            "Synthetic probe requests attempted per SLO class "
            "(slo_class= label) — black-box coverage; "
            "absent(tpuserve_canary_probes_total) in the generated "
            "rules catches a dead prober",
            ["slo_class"], registry=self.registry)
        self.failures = Counter(
            "tpuserve_canary_failures",
            "Probe requests that failed (non-200, malformed body, or "
            "timed out) per SLO class — the numerator of the "
            "black-box availability SLI",
            ["slo_class"], registry=self.registry)
        self.probe_latency = Histogram(
            "tpuserve_canary_probe_latency_seconds",
            "End-to-end wall latency of successful probes per SLO "
            "class — the black-box twin of tpuserve_e2e_seconds, "
            "measured through the full gateway->server->engine path",
            ["slo_class"], buckets=_SLI_E2E_BUCKETS,
            registry=self.registry)
        self.breached = Gauge(
            "tpuserve_canary_breached",
            "1 while any SLO class has >= the configured consecutive "
            "probe failures (0 otherwise) — the scale-out/eject "
            "signal the autoscaler polls off /gateway/status",
            registry=self.registry)

    def render(self) -> bytes:
        return generate_latest(self.registry)


_COLD_START_BUCKETS = (0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0,
                       160.0, 320.0)


class AutoscalerMetrics:
    """The autoscaler control plane's own registry (tpuserve/autoscale):
    served from the scaler Deployment's ``/metrics``, fed by the
    reconciler (and by the simulated pool harness, which exercises the
    same feed paths tier-1)."""

    def __init__(self):
        self.registry = CollectorRegistry()
        self.replicas = Gauge(
            "tpuserve_autoscaler_replicas",
            "Replica count the autoscaler is currently holding the "
            "pool at (pool= the scaled Deployment).  Diverges from the "
            "Deployment's observed replicas only while a scale action "
            "is in flight",
            ["pool"], registry=self.registry)
        self.decisions = Counter(
            "tpuserve_autoscaler_decisions",
            "Non-hold policy decisions applied (action= scale_out | "
            "scale_in).  scale_out fires on brownout-level / "
            "queue-delay-EWMA / TTFT-p95 breaches BEFORE the ladder "
            "sheds; scale_in only after the pool sat idle + drained "
            "for the configured window",
            ["action"], registry=self.registry)
        self.cold_start = Histogram(
            "tpuserve_cold_start_seconds",
            "Cold-pod-to-first-token: wall seconds from server process "
            "boot to the replica's first served token (scraped once "
            "per replica off /debug/engine cold_start_s) — the number "
            "the persistent XLA compile cache, orbax PVC weights, and "
            "KV spill tier's warm prefixes exist to keep small, and "
            "the one that makes scale-from-zero a real operating "
            "point", buckets=_COLD_START_BUCKETS,
            registry=self.registry)

    def render(self) -> bytes:
        return generate_latest(self.registry)
