"""OpenAI-compatible HTTP server (stdlib only — no FastAPI in the image).

Serves the same API surface the reference smoke-tests through the llm-d
gateway: ``GET /v1/models`` and ``POST /v1/completions``
(reference: llm-d-test.yaml:32-78), plus ``/v1/chat/completions`` with SSE
streaming, ``/metrics`` in Prometheus format on the scrape-annotated port
(otel-observability-setup.yaml:337-391 expects port 8000 + the
``prometheus.io/scrape`` annotation), and ``/healthz`` / ``/readyz`` probes.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import os
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from tpuserve.models.tokenizer import default_chat_template
from tpuserve.server.tool_calls import ToolContext, normalize_messages
from tpuserve.runtime.hostprof import STARTUP
from tpuserve.runtime.request import SamplingParams
from tpuserve.runtime.slo import SLO_CLASSES, ShedError
from tpuserve.server.metrics import ServerMetrics
from tpuserve.server.runner import AsyncEngineRunner
from tpuserve.server.tenants import TenantRegistry, estimate_cost
from tpuserve.utils import compile_cache, env_flag

logger = logging.getLogger("tpuserve.server")


class _HTTPServer(ThreadingHTTPServer):
    # socketserver's default TCP accept backlog is 5: a burst of N>5
    # simultaneous connects (batch arrivals are the NORMAL serving
    # pattern) gets connection-reset before the handler ever runs.
    # Found by tests/test_load.py with 32 concurrent streaming clients.
    request_queue_size = 128


@dataclasses.dataclass
class ServerConfig:
    host: str = "0.0.0.0"
    port: int = 8000
    served_model_name: Optional[str] = None     # defaults to engine model
    max_tokens_cap: int = 4096
    request_timeout_s: float = 600.0
    # Jinja chat-template text overriding the tokenizer's (the reference
    # mounts these from ConfigMaps for template-less models, templates/*.yaml)
    chat_template: Optional[str] = None
    # Tool-call parser override (hermes/mistral/llama3_json); None = infer
    # from the model family (server/tool_calls.py).
    tool_call_parser: Optional[str] = None
    # (B, T) embed_forward buckets to pre-compile at startup so the first
    # /v1/embeddings request doesn't stall on a trunk compile.  Empty =
    # compile lazily (deployments that never embed pay nothing).
    warmup_embed: tuple = ()
    # Export tpu_* device metrics alongside vllm_* on /metrics — the engine
    # owns the chips, so it is the authoritative DCGM-analog source.
    tpu_metrics: bool = True
    # Decode-pool role (cross-pod disaggregation): accept KV migrations on
    # POST /internal/migrate (parallel/disagg_net.py).  Off unless the pod
    # is started with --role decode.
    allow_kv_migration: bool = False
    # Retry-After seconds on the drain-time 503 — short: the K8s Service
    # stopped routing here when readyz flipped, so an immediate retry
    # lands on another replica; the header exists so well-behaved clients
    # back off at all instead of treating the 503 as terminal.
    drain_retry_after_s: int = 1
    # Per-tenant metering + rate limits (server/tenants.py): inline JSON
    # or a file path; None = TPUSERVE_TENANTS env (unset: metering only,
    # everything under tenant 'default').  Configure limits HERE only
    # when this server is directly exposed — behind the gateway, enforce
    # there instead (one charge per request, not two).
    tenant_config: Optional[str] = None
    # In-process SLO burn-rate evaluation (tpuserve/obs): the runner
    # feeds the per-class SLI stream into a BurnRateEvaluator over the
    # declared objectives and exports tpuserve_slo_* families; /debug/
    # engine carries the firing state.  TPUSERVE_SLO_BURN=0 kills it.
    slo_burn: bool = True
    # Objectives override (tpuserve/obs/objectives.py): inline JSON
    # list or a file path; None = TPUSERVE_SLO_OBJECTIVES env, else the
    # registry defaults.  Validated at boot — a threshold off the
    # pinned bucket edges fails the server, not the alert.
    slo_objectives: Optional[str] = None
    # Model pool (tpuserve/modelpool): catalog spec — JSON object string
    # ({"name": "/ckpt/dir", ...}) or comma-separated names; None =
    # TPUSERVE_MODEL_CATALOG env.  A non-empty catalog (with
    # TPUSERVE_MODELPOOL != 0) builds a ModelPool: per-request "model"
    # routes through it, and a registered-but-cold name hot-swaps at the
    # next idle boundary or answers 503 + Retry-After per swap_policy.
    model_catalog: Optional[str] = None
    swap_policy: str = "swap"              # "swap" | "reject"
    # co-serving knob: how many models' weights may sit in HBM at once
    max_resident_models: int = 1
    # host-DRAM weight tier budget; 0 = TPUSERVE_WEIGHT_HOST_BYTES / 2 GiB
    weight_host_bytes: int = 0
    # PVC weight spill dir; None = TPUSERVE_WEIGHT_SPILL_DIR (unset: no
    # spill tier — host-budget overflow means a cold load next time)
    weight_spill_dir: Optional[str] = None
    # Retry-After seconds on swap_policy="reject" 503s — longer than the
    # drain 503's: the client should give the gateway's catalog routing
    # a beat to steer the retry at a replica already holding the weights
    swap_retry_after_s: int = 5


def _num(body: dict, key: str, default, cast):
    """Fetch a numeric field; null falls back to the default; junk -> 400."""
    val = body.get(key)
    if val is None:
        return default
    try:
        return cast(val)
    except (TypeError, ValueError, OverflowError):
        # OverflowError: int(float('inf')) — json.loads accepts Infinity
        # literals, and an uncaught cast kills the connection with no
        # response at all (found by single-key fuzzing)
        raise ValueError(f"'{key}' must be a number, got {val!r}") from None


def _sampling_from_request(body: dict, cap: int) -> SamplingParams:
    stop = body.get("stop") or ()
    if isinstance(stop, str):
        stop = (stop,)
    if not isinstance(stop, (list, tuple)) or not all(
            isinstance(s, str) for s in stop):
        raise ValueError("'stop' must be a string or list of strings")
    n_logprobs = body.get("logprobs")
    if isinstance(n_logprobs, bool):            # chat API sends a bool
        n_logprobs = _num(body, "top_logprobs", 5, int) if n_logprobs else None
    elif n_logprobs is not None:
        n_logprobs = _num(body, "logprobs", None, int)
    seed = body.get("seed")
    if seed is not None:
        seed = _num(body, "seed", None, int)
    bias = body.get("logit_bias")
    if bias is not None:
        if not isinstance(bias, dict) or len(bias) > 300:
            raise ValueError(
                "'logit_bias' must be a {token_id: bias} object with at "
                "most 300 entries")
        try:
            bias = {int(k): float(v) for k, v in bias.items()}
        except (TypeError, ValueError):
            raise ValueError("'logit_bias' keys must be token ids and "
                             "values numbers") from None
        if any(k < 0 or k >= 2**31 for k in bias):
            # negative ids would wrap NumPy-style in the scatter and bias
            # the wrong token; ids past int32 would overflow the scatter
            # index array and crash the engine step (failing the whole
            # batch); ids >= vocab are dropped harmlessly
            raise ValueError(
                "'logit_bias' token ids must be in [0, 2**31)")
        if any(math.isnan(v) or math.isinf(v) for v in bias.values()):
            # must run BEFORE the clamp: json.loads accepts NaN/Infinity
            # literals, and max(-100, min(100, nan)) is 100 — a NaN would
            # silently force the token
            raise ValueError("'logit_bias' values must be finite")
        # OpenAI semantics: bias clamped to [-100, 100]
        bias = {k: max(-100.0, min(100.0, v)) for k, v in bias.items()}
    stop_ids = body.get("stop_token_ids") or ()
    if stop_ids:
        if (not isinstance(stop_ids, (list, tuple)) or len(stop_ids) > 64
                or not all(isinstance(t, int) and not isinstance(t, bool)
                           and 0 <= t < 2**31 for t in stop_ids)):
            raise ValueError("'stop_token_ids' must be a list of at most "
                             "64 token ids in [0, 2**31)")
    min_p = _num(body, "min_p", 0.0, float)
    if not 0.0 <= min_p <= 1.0:        # NaN fails both comparisons too
        raise ValueError("'min_p' must be in [0, 1]")
    temperature = _num(body, "temperature", 1.0, float)
    if not 0.0 <= temperature <= 100.0:     # NaN/inf fail; generous cap
        raise ValueError("'temperature' must be in [0, 100]")
    top_k = _num(body, "top_k", 0, int)
    if not -(2**31) <= top_k < 2**31:
        # found by fuzzing: 2**40 reached the int32 sampling arrays and
        # crashed the whole co-batched engine step
        raise ValueError("'top_k' must be a 32-bit integer (<=0 disables)")
    top_p = _num(body, "top_p", 1.0, float)
    if not 0.0 <= top_p <= 1.0:
        raise ValueError("'top_p' must be in [0, 1]")
    penalties = {}
    for pen, default in (("presence_penalty", 0.0),
                         ("frequency_penalty", 0.0),
                         ("repetition_penalty", 1.0)):
        v = _num(body, pen, default, float)
        if not -1e6 <= v <= 1e6:           # NaN/inf fail
            raise ValueError(f"'{pen}' must be a finite number")
        penalties[pen] = v
    if n_logprobs is not None and not 0 <= n_logprobs <= 2**31 - 1:
        raise ValueError("'logprobs' must be a non-negative 32-bit "
                         "integer")
    priority = _num(body, "priority", 0, int)
    if not -(2**31) <= priority < 2**31:
        raise ValueError("'priority' must be a 32-bit integer")
    slo_class = body.get("slo_class")
    if slo_class is not None and slo_class not in SLO_CLASSES:
        raise ValueError(f"'slo_class' must be one of "
                         f"{'/'.join(SLO_CLASSES)}, got {slo_class!r}")
    guided = None
    guided_schema = None
    rf = body.get("response_format")
    if rf is not None:
        if not isinstance(rf, dict) or not isinstance(rf.get("type"), str):
            raise ValueError("'response_format' must be an object with a "
                             "'type'")
        if rf["type"] == "json_object":
            guided = "json"
        elif rf["type"] == "json_schema":
            # OpenAI shape: {"type": "json_schema",
            #               "json_schema": {"name": ..., "schema": {...}}}
            js = rf.get("json_schema")
            if not isinstance(js, dict) or not isinstance(
                    js.get("schema"), dict):
                raise ValueError("response_format json_schema needs a "
                                 "'json_schema' object with a 'schema'")
            from tpuserve.runtime.guided import SchemaError, compile_schema
            try:
                compile_schema(js["schema"])     # 400 unsupported keywords
            except SchemaError as e:
                raise ValueError(f"unsupported json_schema: {e}") from None
            guided = "json_schema"
            guided_schema = json.dumps(js["schema"])
        elif rf["type"] != "text":
            raise ValueError(f"unknown response_format type {rf['type']!r}")
    gre = body.get("guided_regex")
    if gre is not None:
        # vLLM extension: constrain the output to fully match a regex
        if guided is not None:
            raise ValueError("'guided_regex' cannot be combined with "
                             "response_format json modes")
        if not isinstance(gre, str):
            raise ValueError("'guided_regex' must be a string pattern")
        from tpuserve.runtime.guided_regex import RegexError, compile_regex
        try:
            compile_regex(gre)          # 400 on unsupported syntax
        except RegexError as e:
            raise ValueError(f"unsupported guided_regex: {e}") from None
        guided = "regex"
        guided_schema = gre
    gch = body.get("guided_choice")
    if gch is not None:
        # vLLM extension: output must be exactly one of the given strings
        if guided is not None:
            raise ValueError("'guided_choice' cannot be combined with "
                             "other guided modes")
        from tpuserve.runtime.guided_choice import (ChoiceError,
                                                    compile_choices)
        try:
            choices = compile_choices(gch)   # 400 on bad lists
        except ChoiceError as e:
            raise ValueError(f"unsupported guided_choice: {e}") from None
        guided = "choice"
        guided_schema = json.dumps(list(choices))
    tpt = _num(body, "truncate_prompt_tokens", None, int)
    if tpt is not None and tpt < 1:
        raise ValueError("'truncate_prompt_tokens' must be >= 1")
    plp = _num(body, "prompt_logprobs", None, int)
    if plp is not None and plp < 0:
        raise ValueError("'prompt_logprobs' must be >= 0")
    max_tokens = min(_num(body, "max_tokens", 16, int), cap)
    if max_tokens < 0:
        raise ValueError("'max_tokens' must be >= 0 (0 only for prompt "
                         "scoring: completions with echo + logprobs)")
    return SamplingParams(
        max_tokens=max_tokens,
        min_tokens=max(0, min(_num(body, "min_tokens", 0, int), max_tokens)),
        temperature=temperature,
        top_k=top_k,
        top_p=top_p,
        min_p=min_p,
        presence_penalty=penalties["presence_penalty"],
        frequency_penalty=penalties["frequency_penalty"],
        repetition_penalty=penalties["repetition_penalty"],
        stop=tuple(stop),
        ignore_eos=bool(body.get("ignore_eos", False)),
        include_stop_str_in_output=bool(
            body.get("include_stop_str_in_output", False)),
        seed=seed,
        logprobs=n_logprobs,
        logit_bias=bias,
        stop_token_ids=tuple(stop_ids),
        guided=guided,
        guided_schema=guided_schema,
        priority=priority,
        slo_class=slo_class or "standard",
        truncate_prompt_tokens=tpt,
    )


class OpenAIServer:
    """HTTP front end over an AsyncEngineRunner."""

    def __init__(self, engine, config: ServerConfig | None = None,
                 metrics: ServerMetrics | None = None):
        self.config = config or ServerConfig()
        model_name = self.config.served_model_name
        if model_name is None:
            cfg_owner = engine if hasattr(engine, "config") else \
                getattr(engine, "prefill", None)
            model_name = getattr(getattr(cfg_owner, "config", None), "model", "model")
        self.model_name = model_name
        # multi-LoRA adapter names (engine._lora_names; disagg facades
        # expose the prefill engine's) — routed by the request's "model"
        base_eng = getattr(engine, "prefill", engine)
        self.lora_names = list(getattr(base_eng, "_lora_names", None) or [])
        self.metrics = metrics or ServerMetrics(model_name)
        self.runner = AsyncEngineRunner(engine, self.metrics)
        self.engine = engine
        self.ready = threading.Event()
        self.draining = False          # drain(): reject new work, finish old
        # live POST handlers: drain() must wait for DELIVERY, not just for
        # the engine to queue the last token — a slow-reading stream would
        # otherwise be cut when daemon handler threads die at process exit
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._serve_thread: Optional[threading.Thread] = None
        self._chat_template = None
        if self.config.chat_template:
            import jinja2
            self._chat_template = jinja2.Template(self.config.chat_template)
        # Cache-aware routing (server/kv_digest.py): affinity keys of the
        # prompts this replica has served, rendered as the bloom digest
        # /healthz advertises — the gateway's rendezvous prefix affinity
        # weighs what a replica HAS cached across tiers, not just where
        # the static ring says a prefix should live.
        from tpuserve.server.kv_digest import PrefixDigestTracker
        self.kv_digest = PrefixDigestTracker()
        # Multi-tenant metering/limits + per-tenant default SLO class
        # (server/tenants.py); an empty registry still meters usage
        # under 'default' and resolves LoRA adapters as tenants.
        self.tenants = (TenantRegistry.load(self.config.tenant_config)
                        or TenantRegistry())
        # In-process SLO evaluation (tpuserve/obs/burnrate.py): the
        # runner owns the evaluator (single-threaded feed + evaluate on
        # the loop thread, engine-clock timestamps so a replay-driven
        # engine backtests the identical code).  Boot-validated: bad
        # objectives must fail the pod, not silently never alert.
        if self.config.slo_burn and env_flag("TPUSERVE_SLO_BURN"):
            from tpuserve.obs import BurnRateEvaluator, load_objectives
            self.runner.slo_eval = BurnRateEvaluator(
                load_objectives(self.config.slo_objectives),
                clock=self.runner._clock)
        # Model pool (tpuserve/modelpool): one replica, N registered
        # models, hot-swap at idle boundaries.  TPUSERVE_MODELPOOL=0 or
        # an empty catalog means NO pool object exists — every consumer
        # checks `pool is not None`, so the one-model path is
        # byte-identical (same pattern as the SLO controller).
        self.pool = None
        from tpuserve.modelpool import (ModelPool, ModelPoolConfig,
                                        parse_catalog, pool_enabled)
        catalog = parse_catalog(
            self.config.model_catalog
            or os.environ.get("TPUSERVE_MODEL_CATALOG"))
        if catalog and pool_enabled():
            if not hasattr(engine, "config"):
                raise ValueError(
                    "--model-catalog needs a plain single engine; "
                    "disaggregated/handoff topologies cannot hot-swap")
            self.pool = ModelPool(engine.config, ModelPoolConfig(
                catalog=catalog,
                max_resident=self.config.max_resident_models,
                swap_policy=self.config.swap_policy,
                host_bytes=self.config.weight_host_bytes,
                spill_dir=self.config.weight_spill_dir,
                retry_after_s=self.config.swap_retry_after_s))
            self.runner.pool = self.pool
            logger.info("model pool: catalog=%s max_resident=%d policy=%s",
                        self.pool.models(), self.config.max_resident_models,
                        self.config.swap_policy)
        self.tpu_exporter = None
        if self.config.tpu_metrics:
            try:
                from tpuserve.server.tpu_metrics import TpuMetricsExporter
                self.tpu_exporter = TpuMetricsExporter(
                    registry=self.metrics.registry)
                self.runner.on_step_time = self.tpu_exporter.record_busy
            except Exception:
                logger.exception("TPU metrics exporter unavailable")

    # ---- lifecycle -----------------------------------------------------

    def start(self, warmup: bool = False) -> int:
        """Start engine loop + HTTP listener; returns the bound port."""
        self.runner.start()
        if self.tpu_exporter is not None:
            self.tpu_exporter.start()
        if warmup and hasattr(self.engine, "warmup"):
            # embed buckets opt-in: each costs a full trunk compile at
            # startup, wasted on deployments that never call /v1/embeddings.
            # (Mixed-batching engines derive their flat-token bucket
            # ladder themselves — Engine.warmup mixed_buckets=None auto.)
            self.engine.warmup(embed_buckets=self.config.warmup_embed)
        server = self

        class Handler(_Handler):
            ctx = server

        self._httpd = _HTTPServer((self.config.host, self.config.port),
                                  Handler)
        self._serve_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="tpuserve-http")
        self._serve_thread.start()
        self.ready.set()
        port = self._httpd.server_address[1]
        logger.info("serving %s on %s:%d", self.model_name,
                    self.config.host, port)
        return port

    def _handler_enter(self) -> None:
        with self._inflight_lock:
            self._inflight += 1

    def _handler_exit(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    def drain(self, timeout_s: float = 25.0) -> bool:
        """Graceful shutdown, the K8s rolling-update contract: flip
        /readyz to 503 (the Service stops routing here), reject NEW
        requests with a retryable 503, let in-flight generation finish,
        then stop.  Returns True when everything drained inside the
        timeout (which must be shorter than the pod's
        terminationGracePeriodSeconds, or SIGKILL cuts the streams this
        method exists to protect).
        """
        self.draining = True
        self.ready.clear()
        deadline = time.monotonic() + timeout_s
        drained = False
        while time.monotonic() < deadline:
            if self.runner.idle() and self._inflight == 0:
                drained = True
                break
            time.sleep(0.05)
        if not drained:
            logger.warning("drain timed out with work in flight")
        self.shutdown()
        return drained

    def shutdown(self) -> None:
        self.ready.clear()
        if self.tpu_exporter is not None:
            self.tpu_exporter.stop()
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
        self.runner.shutdown()

    # ---- request handling (called from handler threads) ----------------

    MAX_CHOICES = 8

    def parse_n(self, body: dict) -> int:
        n = body.get("n", 1)
        if not isinstance(n, int) or not 1 <= n <= self.MAX_CHOICES:
            raise ValueError(f"'n' must be an integer in 1..{self.MAX_CHOICES}")
        return n

    def parse_best_of(self, body: dict, n: int, chat: bool,
                      params) -> int:
        """OpenAI completions ``best_of``: sample best_of candidates
        server-side, return the top n by cumulative logprob of the
        generated tokens (the vLLM ranking).  Legacy-completions only,
        like OpenAI; greedy best_of>n would sample n identical streams,
        so it is rejected rather than silently wasted."""
        best_of = body.get("best_of")
        if best_of is None:
            return n
        if chat:
            raise ValueError("'best_of' is a completions parameter "
                             "(not supported on chat)")
        if (not isinstance(best_of, int)
                or not n <= best_of <= self.MAX_CHOICES):
            raise ValueError(f"'best_of' must be an integer in "
                             f"n..{self.MAX_CHOICES}")
        if best_of > n:
            if body.get("stream"):
                raise ValueError("cannot stream with best_of > n: ranking "
                                 "needs every candidate finished")
            if params.greedy:
                raise ValueError("best_of > n requires sampling "
                                 "(temperature > 0); greedy candidates "
                                 "would be identical")
            if params.guided is not None:
                raise ValueError("best_of > n cannot be combined with "
                                 "response_format (ranking records "
                                 "logprobs, which guided decoding "
                                 "forbids)")
            import jax
            if jax.process_count() > 1:
                raise ValueError("best_of > n not supported by this "
                                 "multi-host deployment (candidate "
                                 "ranking records logprobs)")
        return best_of

    def _reject_multihost_unsupported(self, params) -> None:
        """Multi-host lockstep mirrors prefill/decode/sample only; the
        penalty/bias/min-tokens/logprob jits are out of protocol
        (parallel/multihost.py "Limitations").  Reject HERE, before
        submission, as a documented OpenAI-style 400 — the engine-side
        ValueError would surface through the generic handler as a 500
        (VERDICT r3 next #8)."""
        import jax
        if jax.process_count() <= 1:
            return
        offending = params.multihost_unsupported()
        if offending:
            raise ValueError(
                f"{', '.join(offending)} not supported by this multi-host "
                "deployment; remove the parameter(s) or route to a "
                "single-host replica")

    def handle_completion(self, body: dict, chat: bool):
        toolctx = None
        if chat:
            messages = body.get("messages")
            if not isinstance(messages, list) or not messages:
                raise ValueError("'messages' must be a non-empty list")
            messages = normalize_messages(messages)
            toolctx = ToolContext.from_body(
                body, self.model_name, self.config.tool_call_parser)
            tools = toolctx.raw_tools if toolctx else None
            tok = getattr(self.engine, "tokenizer", None) or \
                self.engine.prefill.tokenizer
            if self._chat_template is not None:
                prompt = self._chat_template.render(
                    messages=messages, add_generation_prompt=True,
                    tools=tools)
            elif hasattr(tok, "apply_chat_template"):
                prompt = tok.apply_chat_template(messages, tools=tools)
            else:
                instr = (toolctx.parser.prompt_instruction(json.dumps(tools))
                         if toolctx else None)
                prompt = default_chat_template(messages, tools=tools,
                                               tool_instruction=instr)
            if toolctx is not None and toolctx.forced:
                # commit the model to a call (tool_choice required/named):
                # the same prefix is prepended to the output before parsing
                prompt += toolctx.forced
        else:
            prompt = body.get("prompt")
            if isinstance(prompt, list):
                if prompt and isinstance(prompt[0], int):
                    params = _sampling_from_request(
                        body, self.config.max_tokens_cap)
                    self._reject_multihost_unsupported(params)
                    return prompt, params, None
                if len(prompt) != 1:
                    raise ValueError("batched prompt lists are not supported; "
                                     "send one request per prompt")
                prompt = prompt[0]
            if not isinstance(prompt, str) or not prompt:
                raise ValueError("'prompt' must be a non-empty string")
        params = _sampling_from_request(body, self.config.max_tokens_cap)
        self._reject_multihost_unsupported(params)
        return prompt, params, toolctx


class _Handler(BaseHTTPRequestHandler):
    # TCP_NODELAY: per-token SSE events are small writes; Nagle holding
    # them for the delayed ACK adds ~40ms per decode step per stream
    # under concurrent load.
    disable_nagle_algorithm = True
    ctx: OpenAIServer
    protocol_version = "HTTP/1.1"

    # quieter logs
    def log_message(self, fmt, *args):
        logger.debug("%s " + fmt, self.address_string(), *args)

    # ---- helpers -------------------------------------------------------

    def _json(self, code: int, payload: dict,
              headers: Optional[dict] = None) -> None:
        data = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _error(self, code: int, message: str,
               etype: str = "invalid_request_error",
               headers: Optional[dict] = None) -> None:
        self._json(code, {"error": {"message": message, "type": etype}},
                   headers=headers)

    def _read_body(self) -> dict:
        length = int(self.headers.get("Content-Length") or 0)
        if length <= 0:
            raise ValueError("missing request body")
        if length > 10 * 1024 * 1024:
            # The body is left unread; keeping the connection alive would make
            # the handler parse those bytes as the next request line.
            self.close_connection = True
            raise ValueError("request body too large")
        raw = self.rfile.read(length)
        body = json.loads(raw)
        if not isinstance(body, dict):
            raise ValueError("request body must be a JSON object")
        return body

    # ---- routes --------------------------------------------------------

    def do_GET(self):
        ctx = self.ctx
        if self.path == "/v1/models":
            # max_model_len like vLLM's /v1/models, so clients can budget
            # prompts without a /tokenize round-trip; engine config
            # metadata for operators diagnosing a pod.  Disagg wrappers
            # report the MIN over both pools — intake enforces the decode
            # pool's limit, and advertising the larger prefill budget
            # would 4xx prompts the endpoint called fine.
            engines = [e for e in (getattr(ctx.engine, "prefill", None),
                                   getattr(ctx.engine, "decode", None))
                       if e is not None] or [ctx.engine]
            eng = engines[0]
            now = int(time.time())
            data = [{
                "id": ctx.model_name, "object": "model",
                "created": now, "owned_by": "tpuserve",
                "max_model_len": min(e.max_seq_len for e in engines),
                "quantization": eng.config.quantization,
                "kv_cache_dtype": eng.cache_cfg.dtype}]
            # loaded LoRA adapters serve as selectable models (vLLM's
            # --lora-modules listing: parent links the base)
            data += [{"id": name, "object": "model", "created": now,
                      "owned_by": "tpuserve", "parent": ctx.model_name}
                     for name in ctx.lora_names]
            # model-pool catalog entries are selectable too; tier= is
            # the warmth tag (serving/resident/host/spill/cold) clients
            # and the gateway can read without a /healthz round-trip
            if ctx.pool is not None:
                data += [{"id": name, "object": "model", "created": now,
                          "owned_by": "tpuserve",
                          "tier": ctx.pool.tier_of(name)}
                         for name in ctx.pool.models()
                         if name != ctx.model_name]
            self._json(200, {"object": "list", "data": data})
        elif self.path.startswith("/v1/models/"):
            # OpenAI retrieve-model: GET /v1/models/{id} (ids may contain
            # '/', e.g. Qwen/Qwen3-0.6B — match the raw suffix)
            from urllib.parse import unquote
            wanted = unquote(self.path[len("/v1/models/"):])
            now = int(time.time())
            if wanted == ctx.model_name:
                self._json(200, {"id": wanted, "object": "model",
                                 "created": now, "owned_by": "tpuserve"})
            elif wanted in (ctx.lora_names or ()):
                self._json(200, {"id": wanted, "object": "model",
                                 "created": now, "owned_by": "tpuserve",
                                 "parent": ctx.model_name})
            elif ctx.pool is not None and ctx.pool.is_registered(wanted):
                self._json(200, {"id": wanted, "object": "model",
                                 "created": now, "owned_by": "tpuserve",
                                 "tier": ctx.pool.tier_of(wanted)})
            else:
                self._error(404, f"model {wanted!r} not found",
                            "invalid_request_error")
        elif self.path == "/metrics":
            data = ctx.metrics.render()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
        elif self.path == "/healthz":
            self._json(200, self._healthz_payload())
        elif self.path == "/readyz":
            if ctx.ready.is_set():
                self._json(200, {"status": "ready"})
            else:
                self._error(503, "not ready", "server_error")
        elif self.path == "/debug/engine":
            # flight-recorder engine snapshot: recent step records (kind,
            # rows, actual/padded tokens, phase ms), recent request ids,
            # client SLI percentiles, post-mortem pointers
            self._json(200, self._debug_engine_payload())
        elif self.path == "/debug/engine/dump":
            # on-demand replay-ready bundle (tools/replay.py dump): the
            # same schema-versioned format post-mortems use — every ring-
            # reachable request timeline + step records + SLIs + engine
            # facts + ring-integrity markers — so an operator can capture
            # an incident WITHOUT waiting for a watchdog/poison event.
            # Snapshot reads only; the engine keeps serving.
            bundles = [fl.dump_bundle("on_demand")
                       for fl in self._flight_recorders()]
            ctx.metrics.replay_dumps.inc()
            self._json(200, bundles[0] if len(bundles) == 1
                       else {"engines": bundles})
        elif self.path.startswith("/debug/requests/"):
            from urllib.parse import unquote
            rid = unquote(self.path[len("/debug/requests/"):])
            timeline = []
            for fl in self._flight_recorders():
                timeline.extend(fl.request_timeline(rid))
            if timeline:
                timeline.sort(key=lambda e: e["t"])
                self._json(200, {"request_id": rid, "events": timeline})
            else:
                self._error(404, f"no recorded events for {rid!r} (the "
                                 "ring holds the most recent "
                                 "TPUSERVE_FLIGHT_EVENTS events)")
        elif self.path.startswith("/debug/profile"):
            self._handle_profile()
        else:
            self._error(404, f"no route {self.path}")

    def _handle_profile(self) -> None:
        """jax.profiler capture (SURVEY.md §5: the reference has no
        profiler; this is the TPU-native story).  Blocks this handler
        thread only; the engine keeps serving while being traced — the
        trace is OF live serving.  Serialized process-wide (409 when a
        capture is already running); the trace dir lands under
        TPUSERVE_FLIGHT_DIR when configured and is recorded on each
        engine's DeviceProfiler so bundles reference it.  GET kept for
        compatibility; POST is the documented verb (a capture writes
        disk state)."""
        from urllib.parse import parse_qs, urlparse
        from tpuserve.server.tracing import (CaptureBusy,
                                             capture_profile_locked)
        profs = [getattr(e, "devprof", None)
                 for e in self.ctx.runner._inner_engines()]
        try:
            q = parse_qs(urlparse(self.path).query)
            seconds = float(q.get("seconds", ["2"])[0])
            self._json(200, capture_profile_locked(
                seconds, reason="manual", profilers=profs))
        except CaptureBusy as e:
            self._error(409, str(e), "server_error")
        except Exception as e:
            self._error(500, f"profile capture failed: {e}",
                        "server_error")

    def _flight_recorders(self) -> list:
        """Flight recorders across the (possibly disagg) engine — one
        source of truth for inner-engine discovery (the runner's)."""
        return self.ctx.runner._flights()

    def _debug_engine_payload(self) -> dict:
        recorders = self._flight_recorders()
        if len(recorders) == 1:
            out = recorders[0].engine_snapshot()
        else:
            out = {"engines": [f.engine_snapshot() for f in recorders]}
        # cold-pod-to-first-token (wall seconds since process boot):
        # the autoscaler's probe exports this once per replica into
        # tpuserve_cold_start_seconds
        out["cold_start_s"] = getattr(self.ctx.runner, "cold_start_s",
                                      None)
        # what that number is made of (runtime/hostprof.py's start-up
        # spans; the process's compile ledger as it stood at the first
        # served token, as it stands now until then)
        out["startup"] = {
            "cold_start_s": out["cold_start_s"],
            "phases": {k: round(v, 6)
                       for k, v in sorted(STARTUP.seconds.items())},
            "compile": getattr(self.ctx.runner, "startup_compile", None)
            or compile_cache.LEDGER.totals()}
        # in-process SLO burn-rate state (tpuserve/obs): the loop-thread-
        # published snapshot — firing alerts + per-objective burn rates
        # as plain scalars, aggregated fleet-wide by /gateway/slo
        ev = getattr(self.ctx.runner, "slo_eval", None)
        if ev is not None:
            out["slo"] = dict(ev.last_state)
        # compile-cache visibility (the small fix riding the devprof PR):
        # grammar-FSM memo + bucketed-executable ladder hit/miss/size per
        # engine, so compile churn is an endpoint read, not log archaeology
        caches = [e.compile_cache_stats()
                  for e in self.ctx.runner._inner_engines()
                  if hasattr(e, "compile_cache_stats")]
        if caches:
            out["compile_caches"] = (caches[0] if len(caches) == 1
                                     else caches)
        # model-pool residency + swap bookkeeping (catalog, tier bytes,
        # pending swap, demand ledger) — the operator's swap console
        if self.ctx.pool is not None:
            out["modelpool"] = self.ctx.pool.status()
        return out

    def _emit_engine_spans(self, rids) -> None:
        """Export each request's flight timeline as OTLP child spans of
        the current request span — the gateway->server->engine tree the
        reference's OTel pipeline was built for but never fed.  No-op
        unless the SDK is configured (request_span semantics)."""
        from tpuserve.server.tracing import emit_timeline_spans, get_tracer
        tracer = get_tracer()
        if not tracer.active:
            return
        for fl in self._flight_recorders():
            for rid in rids:
                timeline = fl.request_timeline(rid)
                if timeline:
                    emit_timeline_spans(tracer, timeline, fl.wall_of)

    def _healthz_payload(self) -> dict:
        """Liveness plus the cache-affinity advertisement: the prefix
        digest (server/kv_digest.py) and per-tier KV residency.  Reads
        are count/snapshot-only — nothing here touches engine-loop-owned
        block state — and the digest window resizes with the replica's
        total cache reach across tiers, so a tiered replica advertises
        the (much longer) retention it actually has."""
        ctx = self.ctx
        out: dict = {"status": "ok"}
        try:
            engines = [e for e in (getattr(ctx.engine, "prefill", None),
                                   getattr(ctx.engine, "decode", None))
                       if e is not None] or [ctx.engine]
            # cheap control-plane scalars for pollers that don't want
            # the full /debug/engine snapshot (gateway probes, the
            # autoscaler's degraded path)
            out["brownout_level"] = max(
                (getattr(getattr(e, "stats", None), "brownout_level", 0)
                 for e in engines), default=0)
            out["cold_start_s"] = getattr(ctx.runner, "cold_start_s",
                                          None)
            tiers = {"hbm": 0, "host": 0, "spill": 0}
            reach = 0
            for e in engines:
                bm = getattr(e, "block_manager", None)
                tiers["hbm"] += getattr(bm, "num_cached_blocks", 0)
                store = getattr(e, "_kv_tiers", None)
                if store is not None:
                    tiers["host"] += store.host_count
                    tiers["spill"] += store.spill_count
                reach += getattr(bm, "num_blocks", 0) + (len(store)
                                                         if store else 0)
            if reach:
                # reach is in BLOCKS; a tracked key is a whole prompt
                # prefix (several blocks) — divide so the digest window
                # approximates retained conversations, not pages
                ctx.kv_digest.resize(max(4096, reach // 4))
            out["kv_tier_blocks"] = tiers
            out["kv_digest"] = ctx.kv_digest.digest_hex()
            out["kv_digest_bits"] = ctx.kv_digest.bits
            # the key-derivation prefix length this tracker hashed with:
            # the gateway probes membership using OUR value, so its own
            # affinity_prefix_chars setting can't silently de-sync the
            # digest (kv_digest.py)
            from tpuserve.server.kv_digest import AFFINITY_PREFIX_CHARS
            out["kv_digest_chars"] = AFFINITY_PREFIX_CHARS
            # model-pool catalog digest: every registered model with its
            # warmth tag (serving/resident/host/spill/cold) — the
            # gateway's catalog routing prefers replicas already holding
            # the requested weights
            if ctx.pool is not None:
                out["models"] = ctx.pool.catalog_status()
                out["model_current"] = ctx.pool.current
        except Exception:       # liveness must never fail on telemetry
            pass
        return out

    def do_POST(self):
        # enter BEFORE the draining check: checking first races drain()'s
        # inflight==0 poll — a thread descheduled between check and enter
        # would submit into an already-stopped engine loop and hang its
        # client for the submit timeout
        self.ctx._handler_enter()
        self._pid_cache = None     # per-request memo (keep-alive reuse)
        self._tenant = None        # tenant accounting (keep-alive reuse)
        self._charged = None
        try:
            if self.ctx.draining:
                # graceful drain: in-flight streams keep running;
                # everything new gets a retryable 503 WITH Retry-After so
                # K8s-fronted clients/gateways back off instead of
                # hammering a pod that is seconds from termination
                self._error(503, "server is draining; retry another "
                                 "replica", "server_error",
                            headers={"Retry-After": str(
                                self.ctx.config.drain_retry_after_s)})
                return
            self._do_post_inner()
        finally:
            # a request that errored before serving refunds its whole
            # rate-limit charge (settle is once-only; served paths
            # already settled with their real token counts)
            self._settle_tenant(0)
            self.ctx._handler_exit()

    def _settle_tenant(self, actual: int) -> None:
        """Reconcile the tenant rate-limit charge against tokens
        actually served and feed the metering counter.  Idempotent per
        request: the first call wins."""
        charged, tenant = self._charged, self._tenant
        if tenant is None or charged is None:
            return
        self._charged = None
        self.ctx.tenants.settle(tenant, charged, actual)
        if actual:
            self.ctx.metrics.tenant_tokens.labels(
                model_name=self.ctx.model_name, tenant=tenant).inc(actual)

    def _do_post_inner(self):
        if self.path == "/internal/migrate":
            self._handle_migrate()
            return
        if self.path == "/internal/abort":
            self._handle_internal_abort()
            return
        if self.path in ("/tokenize", "/detokenize"):
            self._handle_tokenize(self.path == "/tokenize")
            return
        if self.path == "/v1/embeddings":
            self._handle_embeddings()
            return
        if self.path.startswith("/debug/profile"):
            self._handle_profile()
            return
        chat = self.path == "/v1/chat/completions"
        if self.path not in ("/v1/completions", "/v1/chat/completions"):
            self._error(404, f"no route {self.path}")
            return
        try:
            body = self._read_body()
            if not chat and body.get("suffix") is not None:
                # OpenAI legacy fill-in-the-middle; vLLM rejects it too
                raise ValueError("'suffix' is not supported")
            prompt, params, toolctx = self.ctx.handle_completion(body, chat)
            n = self.ctx.parse_n(body)
            best_of = self.ctx.parse_best_of(body, n, chat, params)
        except (ValueError, json.JSONDecodeError) as e:
            self._error(400, str(e))
            return
        stream = bool(body.get("stream", False))
        if "stream_options" in body and not isinstance(
                body.get("stream_options"), dict):
            self._error(400, "'stream_options' must be an object")
            return
        # ---- multi-tenant + SLO class (server/tenants.py, runtime/slo.py)
        ctx = self.ctx
        # Synthetic canary probes (tpuserve/obs/canary.py) ride the real
        # serving path but are excluded from tenant metering (no tenant
        # resolved, no charge/settle) and from the affinity digest —
        # the identical tiny prompt from every probe would otherwise
        # steer the gateway's cache-aware routing.  The SLO class still
        # applies: a canary must queue like the class it probes.
        # Because the tag bypasses rate limits, deployments with
        # tenancy set TPUSERVE_CANARY_TOKEN — a bare "1" from a client
        # is then just normal (billed, SLI-counted) traffic.
        from tpuserve.obs.canary import is_canary_header
        canary = is_canary_header(self.headers.get("X-TPUServe-Canary"))
        if canary:
            params = dataclasses.replace(params, canary=True)
        tenant = None if canary else ctx.tenants.resolve(
            self.headers.get("Authorization"), body.get("model"),
            tuple(ctx.lora_names or ()))
        self._tenant = tenant
        if body.get("slo_class") is None:
            # body field > X-SLO-Class header > tenant default > standard
            cls = (self.headers.get("X-SLO-Class")
                   or ctx.tenants.slo_class_for(tenant))
            if cls is not None:
                if cls not in SLO_CLASSES:
                    self._error(400, "X-SLO-Class must be one of "
                                     f"{'/'.join(SLO_CLASSES)}, got {cls!r}")
                    return
                params = dataclasses.replace(params, slo_class=cls)
        cost = estimate_cost(body)
        retry = None if canary else ctx.tenants.charge(tenant, cost)
        if retry is not None:
            ctx.metrics.tenant_rate_limited.labels(
                model_name=ctx.model_name, tenant=tenant).inc()
            self._error(429, f"tenant {tenant!r} token rate limit "
                             f"exceeded; retry in {retry:.1f}s",
                        "rate_limit_exceeded",
                        headers={"Retry-After": str(int(retry) + 1)})
            return
        self._charged = None if canary else cost
        # digest the affinity key only after every API-layer validation
        # has passed: a 400'd request caches no KV and must not steer the
        # gateway here.  (Engine-side rejects — oversize prompt, 503
        # backpressure — can still note a key; the bit is advisory and
        # ages out of the LRU window.)
        if not canary:
            from tpuserve.server.kv_digest import affinity_key
            self.ctx.kv_digest.note(affinity_key(body))
        kwargs = ({"prompt_token_ids": prompt} if isinstance(prompt, list)
                  else {"prompt": prompt})
        # multi-LoRA routing (vLLM semantics): "model" naming a loaded
        # adapter selects it; the base model name (or anything else, for
        # compat with clients that send their own aliases) serves base
        adapter = body.get("model")
        if (isinstance(adapter, str) and adapter != self.ctx.model_name
                and adapter in (self.ctx.lora_names or ())):
            kwargs["adapter"] = adapter
        elif ctx.pool is not None and isinstance(adapter, str):
            # model-pool catalog routing: a registered-but-not-current
            # name parks for a hot-swap ("swap" policy) or answers a
            # retryable 503 ("reject" — the gateway's catalog tags steer
            # the retry at a replica already holding the weights).
            # Unregistered names keep the alias-compat fall-through
            # above: they serve whatever is current, exactly as without
            # a pool.  Note demand either way — it is the per-model
            # scale-from-zero signal AND kicks spill->host prefetch.
            verdict = ctx.pool.route(adapter)
            if verdict in ("swap", "reject"):
                ctx.pool.note_demand(adapter)
            if verdict == "swap":
                kwargs["model"] = adapter
            elif verdict == "reject":
                ctx.pool.rejects += 1
                self._error(503, f"model {adapter!r} is registered but "
                                 "not resident on this replica; retry "
                                 "(routing prefers a warm replica)",
                            "server_error",
                            headers={"Retry-After": str(
                                ctx.pool.cfg.retry_after_s)})
                return
        if body.get("prompt_logprobs") is not None:
            # vLLM extension: per-choice prompt logprobs on the response
            if stream:
                self._error(400, "prompt_logprobs is not supported with "
                                 "stream=true; use echo+logprobs for "
                                 "streamed prompt logprobs")
                return
            if "adapter" in kwargs:
                self._error(400, "prompt_logprobs is served by the base "
                                 "model; drop it or use "
                                 f"model={self.ctx.model_name!r}")
                return
        if not chat and body.get("echo") and params.logprobs is not None \
                and "adapter" in kwargs:
            # the scoring trunk has no adapter threading — base-model
            # prompt logprobs next to adapter completions would be wrong
            self._error(400, "echo+logprobs (prompt scoring) is served by "
                             "the base model; drop echo or use "
                             f"model={self.ctx.model_name!r}")
            return
        if params.max_tokens == 0:
            # OpenAI prompt scoring: max_tokens=0 + echo + logprobs returns
            # the prompt's own logprobs with no generation (completions
            # only — chat has no echo, so 0 tokens buys nothing there)
            if "model" in kwargs:
                # scoring runs synchronously against the live engine —
                # it cannot park for a hot-swap like generation does
                self._error(400, "prompt scoring (max_tokens=0) is "
                                 "served by the currently-resident "
                                 "model; retry once it is serving "
                                 f"{kwargs['model']!r}")
                return
            if (chat or stream or not body.get("echo")
                    or params.logprobs is None or n != 1
                    or body.get("prompt_logprobs") is not None):
                self._error(400, "max_tokens=0 is prompt scoring: requires "
                                 "completions with echo=true and logprobs, "
                                 "non-streaming, n=1 (and not combined "
                                 "with prompt_logprobs — it would be "
                                 "redundant)")
                return
            try:
                self._score_only_response(body, params, kwargs)
            except Exception as e:        # scoring faults need a status too
                logger.exception("prompt scoring failed")
                self._error(500, str(e), "server_error")
            return
        from tpuserve.server.tracing import extract_context, get_tracer
        try:
            # parent = the incoming W3C traceparent (the gateway's span,
            # or the caller's own trace) so the whole request is one tree
            with get_tracer().request_span(
                    self.path, context=extract_context(self.headers),
                    **{"gen_ai.request.model": self.ctx.model_name,
                       "gen_ai.request.max_tokens": params.max_tokens,
                       "tpuserve.stream": stream}):
                if stream:
                    # _stream_response owns its error handling: once SSE
                    # headers are out, a second status line would corrupt
                    # the stream.
                    self._stream_response(body, params, chat, kwargs, n,
                                          toolctx=toolctx)
                else:
                    self._full_response(body, params, chat, kwargs, n,
                                        toolctx=toolctx, best_of=best_of)
        except BrokenPipeError:
            pass
        except Exception as e:               # engine-side failure, pre-headers
            logger.exception("request failed")
            if not stream:
                try:
                    self._error(500, str(e), "server_error")
                except Exception:
                    pass

    # ---- cross-pod disaggregation (decode-pool side) --------------------

    MAX_MIGRATION_BYTES = 1 << 30      # KV pages for one long sequence

    def _handle_migrate(self):
        """Adopt a prefilled sequence from a prefill pod and stream its
        remaining tokens back as JSON lines over a close-delimited response
        (parallel/disagg_net.py is the peer)."""
        ctx = self.ctx
        if not ctx.config.allow_kv_migration:
            self._error(403, "this pod is not a decode pool "
                             "(start with --role decode)")
            return
        try:
            length = int(self.headers.get("Content-Length") or 0)
            if not 0 < length <= self.MAX_MIGRATION_BYTES:
                self.close_connection = True
                raise ValueError(f"bad migration payload size {length}")
            from tpuserve.parallel.disagg_net import deserialize_migration
            meta, seq_kv = deserialize_migration(self.rfile.read(length))
        except ValueError as e:
            self._error(400, str(e))
            return
        try:
            rid, q = ctx.runner.submit_prefilled(meta, seq_kv)
        except MemoryError as e:
            self._error(503, str(e), "server_error")   # pool-full backpressure
            return
        except Exception as e:
            self._error(400, str(e))
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Connection", "close")
        self.end_headers()
        self.close_connection = True
        import queue as _queue
        deadline = time.monotonic() + ctx.config.request_timeout_s
        try:
            while True:
                try:
                    item = q.get(timeout=max(deadline - time.monotonic(),
                                             0.001))
                except _queue.Empty:
                    ctx.runner.abort(rid)
                    break
                if item is None:
                    break
                if isinstance(item, Exception):
                    break
                line = json.dumps({
                    "new_token_ids": item.new_token_ids,
                    "new_text": item.new_text,
                    "finished": item.finished,
                    "finish_reason": (item.finish_reason.value
                                      if item.finish_reason else None),
                }) + "\n"
                self.wfile.write(line.encode())
                self.wfile.flush()
        except BrokenPipeError:
            # prefill pod went away (client abort): stop generating
            ctx.runner.abort(rid)
        finally:
            getattr(ctx.engine, "requests", {}).pop(rid, None)

    def _handle_tokenize(self, encode: bool):
        """vLLM-compatible /tokenize and /detokenize: clients use these for
        budget accounting against the SERVER's tokenizer (which may differ
        from whatever they have locally)."""
        eng = getattr(self.ctx.engine, "prefill", self.ctx.engine)
        try:
            body = self._read_body()
            if encode:
                prompt = body.get("prompt")
                if not isinstance(prompt, str):
                    raise ValueError("'prompt' must be a string")
                ids = eng.tokenizer.encode(prompt)
                self._json(200, {"tokens": ids, "count": len(ids),
                                 "max_model_len": eng.max_seq_len})
            else:
                tokens = body.get("tokens")
                vocab = eng.model_cfg.vocab_size
                if (not isinstance(tokens, list)
                        or not all(isinstance(t, int)
                                   and not isinstance(t, bool)
                                   and 0 <= t < vocab for t in tokens)):
                    # bounded by the model's vocab, not just 2**31: an
                    # out-of-vocab id can make HF decode raise a
                    # non-ValueError (OverflowError / rust panic) that
                    # this handler would surface as a 500
                    raise ValueError("'tokens' must be a list of token ids "
                                     f"in [0, {vocab})")
                self._json(200, {"prompt": eng.tokenizer.decode(tokens)})
        except (ValueError, json.JSONDecodeError) as e:
            self._error(400, str(e))

    def _handle_embeddings(self):
        """OpenAI /v1/embeddings: input str | [str] | [ids] | [[ids]];
        encoding_format float (default) or base64; optional `dimensions`
        truncation with re-normalisation (OpenAI semantics).  Pooled from
        the causal trunk's final hidden states (Engine.embed) — the
        reference's serving stack (vLLM) exposes the same route."""
        ctx = self.ctx
        eng = getattr(ctx.engine, "prefill", None) or ctx.engine
        try:
            body = self._read_body()
            if body.get("model") in (ctx.lora_names or ()):
                # /v1/models advertises adapters, but the embed trunk has
                # no adapter threading — a silent base-model 200 would be
                # wrong vectors for a listed model id
                raise ValueError(
                    f"model {body.get('model')!r} is a LoRA adapter; "
                    "embeddings are served by the base model only — "
                    f"use model={ctx.model_name!r}")
            raw = body.get("input")
            if isinstance(raw, str):
                inputs = [raw]
            elif isinstance(raw, list) and raw and \
                    all(isinstance(t, int) and not isinstance(t, bool)
                        for t in raw):
                inputs = [raw]                       # one token-id prompt
            elif isinstance(raw, list) and raw:
                inputs = raw
            else:
                raise ValueError("'input' must be a string, list of "
                                 "strings, or list(s) of token ids")
            vocab = eng.model_cfg.vocab_size
            for x in inputs:
                if isinstance(x, list) and not all(
                        isinstance(t, int) and not isinstance(t, bool)
                        and 0 <= t < vocab for t in x):
                    raise ValueError("token ids must be ints in "
                                     f"[0, {vocab})")
                elif not isinstance(x, (str, list)):
                    raise ValueError("'input' items must be strings or "
                                     "token-id lists")
            fmt = body.get("encoding_format", "float")
            if fmt not in ("float", "base64"):
                raise ValueError("encoding_format must be 'float' or "
                                 "'base64'")
            dims = body.get("dimensions")
            if dims is not None and (not isinstance(dims, int)
                                     or isinstance(dims, bool)
                                     or dims < 1):
                raise ValueError("'dimensions' must be a positive integer")
            vecs, counts = eng.embed(inputs)
            if dims is not None:
                if dims > vecs.shape[1]:
                    raise ValueError(f"'dimensions' {dims} exceeds model "
                                     f"embedding width {vecs.shape[1]}")
                import numpy as _np
                vecs = vecs[:, :dims]
                vecs = vecs / _np.maximum(
                    _np.linalg.norm(vecs, axis=-1, keepdims=True), 1e-12)
            data = []
            for i, v in enumerate(vecs):
                if fmt == "base64":
                    import base64
                    emb = base64.b64encode(
                        v.astype("<f4").tobytes()).decode()
                else:
                    emb = [float(x) for x in v]
                data.append({"object": "embedding", "index": i,
                             "embedding": emb})
            total = sum(counts)
            self._json(200, {
                "object": "list", "data": data, "model": ctx.model_name,
                "usage": {"prompt_tokens": total, "total_tokens": total}})
        except (ValueError, json.JSONDecodeError) as e:
            self._error(400, str(e))
        except Exception as e:
            # engine-side failure (XLA OOM, compile error): a JSON 500
            # beats the dropped connection BaseHTTPRequestHandler gives
            logger.exception("embeddings failed")
            self._error(500, str(e), "server_error")

    def _handle_internal_abort(self):
        """Drop an adopted request (prefill pod's ambiguous-outcome cleanup:
        when a migration's 200 response is lost in flight, the prefill pod
        falls back to local decode and tells this pool to stop so the same
        request isn't decoded on both pods)."""
        ctx = self.ctx
        if not ctx.config.allow_kv_migration:
            self._error(403, "this pod is not a decode pool "
                             "(start with --role decode)")
            return
        try:
            body = self._read_body()
            rid = body["request_id"]
        except (ValueError, KeyError, json.JSONDecodeError) as e:
            self._error(400, f"bad abort request: {e}")
            return
        aborted = ctx.runner.abort(rid)
        getattr(ctx.engine, "requests", {}).pop(rid, None)
        self._json(200, {"request_id": rid, "aborted": bool(aborted)})

    # ---- response shapes ------------------------------------------------

    @staticmethod
    def _choice_params(params, i: int, n: int):
        """Per-choice sampling params for n > 1: a seeded request's choices
        sample distinct deterministic streams (seed+i); unseeded requests
        already decorrelate via their per-request salt.  (The choices share
        prompt KV through the prefix cache, on by default.)"""
        if n == 1 or params.seed is None:
            return params
        return dataclasses.replace(params, seed=params.seed + i)

    def _submit_choices(self, params, kwargs, n):
        """Submit the n per-choice requests; if one fails mid-list, abort
        the already-accepted ones so they don't generate to max_tokens and
        leak their engine records."""
        ctx = self.ctx
        submits = []
        # queue-side admission deadline: a request this handler would
        # time out anyway (request_timeout_s) is aborted by the ENGINE
        # while still queued, so overload never spends prefill on a
        # response nobody is waiting for (runtime/slo.py)
        deadline = time.monotonic() + ctx.config.request_timeout_s
        try:
            for i in range(n):
                submits.append(ctx.runner.submit(
                    params=self._choice_params(params, i, n),
                    deadline=deadline, **kwargs))
        except Exception:
            for rid, _ in submits:
                ctx.runner.abort(rid)
                ctx.engine.requests.pop(rid, None)
            raise
        return submits

    @staticmethod
    def _completions_logprobs(entries) -> dict:
        """OpenAI completions logprobs shape (parallel lists).  A model
        with expert layers adds ``routed_experts`` beside them: for each
        token the experts each expert layer picked for the position it was
        sampled after (``Engine._append_logprob_entry``); and, where the
        entries start at the first token, ``prompt_routed_experts``: the
        same for each position of the prompt, -1 where the prefix cache
        held it (``Engine._file_prompt_picks``).  Together they are every
        pick the tokens' logits went through: what an evaluation of the
        same weights in another precision replays."""
        out = {
            "token_logprobs": [e["logprob"] for e in entries],
            "tokens": [e["token_id"] for e in entries],
            "top_logprobs": [dict(e["top"]) for e in entries],
        }
        if entries and all("routed_experts" in e for e in entries):
            out["routed_experts"] = [e["routed_experts"] for e in entries]
            if "prompt_routed_experts" in entries[0]:
                out["prompt_routed_experts"] = \
                    entries[0]["prompt_routed_experts"]
        return out

    def _chat_logprobs(self, entries) -> dict:
        """OpenAI chat logprobs shape: per-token content entries with
        vocabulary-level token strings (id_to_token keeps special tokens
        and SentencePiece markers that plain decode strips) and top
        alternatives."""
        eng = getattr(self.ctx.engine, "prefill", self.ctx.engine)
        tok = eng.tokenizer.id_to_token
        return {"content": [
            {"token": tok(e["token_id"]), "logprob": e["logprob"],
             "top_logprobs": [{"token": tok(t), "logprob": lp}
                              for t, lp in e["top"]],
             # (a model with expert layers: see _completions_logprobs)
             **{k: e[k] for k in ("routed_experts", "prompt_routed_experts")
                if k in e}}
            for e in entries]}

    @staticmethod
    def _vllm_prompt_logprobs(pent, plp: int, tok) -> list:
        """vLLM prompt_logprobs response shape from scoring entries: one
        element per prompt token — None first (no conditional), then
        {token_id: {logprob, rank, decoded_token}} covering the top-N
        alternatives AND the chosen token, with true full-vocab ranks."""
        out = [None]
        for e in pent[1:]:
            el = {}
            for i, (tid, lp) in enumerate(e["top"][:plp]):
                el[str(tid)] = {"logprob": lp, "rank": i + 1,
                                "decoded_token": tok(tid)}
            el[str(e["token_id"])] = {
                "logprob": e["logprob"], "rank": e["rank"],
                "decoded_token": tok(e["token_id"])}
            out.append(el)
        return out

    def _prompt_ids(self, kwargs, params=None) -> list:
        # memoised per POST (reset in do_POST): echo + truncation +
        # scoring would otherwise re-encode a long prompt up to 3x
        key = params.truncate_prompt_tokens if params is not None else None
        cached = getattr(self, "_pid_cache", None)
        if cached is not None and cached[0] == key:
            return list(cached[1])
        eng = getattr(self.ctx.engine, "prefill", self.ctx.engine)
        if "prompt_token_ids" in kwargs:
            ids = list(kwargs["prompt_token_ids"])
        else:
            ids = list(eng.tokenizer.encode(kwargs["prompt"]))
        if key:
            # scoring must see the SAME context the engine serves, or the
            # logprob arrays misalign with usage and the conditioning
            ids = ids[-key:]
        self._pid_cache = (key, ids)
        return list(ids)

    def _score_only_response(self, body, params, kwargs):
        """OpenAI prompt scoring: completions with max_tokens=0 + echo +
        logprobs — the prompt's own logprobs, no generation (vLLM serves
        the same via prompt_logprobs)."""
        ctx = self.ctx
        eng = getattr(ctx.engine, "prefill", ctx.engine)
        ids = self._prompt_ids(kwargs, params)
        try:
            entries = eng.score_prompts([ids], top_n=params.logprobs)[0]
        except ValueError as e:
            self._error(400, str(e))
            return
        text = kwargs.get("prompt")
        if text is None or params.truncate_prompt_tokens:
            # truncation: echo what actually conditioned the scoring
            text = eng.tokenizer.decode(ids)
        choice = {"index": 0, "text": text, "finish_reason": "length",
                  "logprobs": self._completions_logprobs(entries)}
        self._json(200, {
            "id": f"cmpl-{uuid.uuid4().hex[:24]}",
            "object": "text_completion", "created": int(time.time()),
            "model": ctx.model_name, "choices": [choice],
            "usage": {"prompt_tokens": len(ids), "completion_tokens": 0,
                      "total_tokens": len(ids)}})

    def _echo_text(self, body, chat, kwargs, params=None):
        """OpenAI completions `echo`: the prompt text to prepend, or None.
        Under truncate_prompt_tokens the TRUNCATED text is echoed — that
        is what conditioned the completion (and what the prompt-logprob
        arrays cover)."""
        if chat or not body.get("echo"):
            return None
        eng = getattr(self.ctx.engine, "prefill", self.ctx.engine)
        if params is not None and params.truncate_prompt_tokens:
            return eng.tokenizer.decode(self._prompt_ids(kwargs, params))
        if "prompt" in kwargs:
            return kwargs["prompt"]
        return eng.tokenizer.decode(kwargs["prompt_token_ids"])

    def _full_response(self, body, params, chat, kwargs, n=1, toolctx=None,
                       best_of=None):
        ctx = self.ctx
        # multi-LoRA: echo the ADAPTER id the request selected (vLLM
        # does); mixed-adapter traffic is otherwise unattributable
        # with a pool, the alias fall-through is served by whatever is
        # CURRENT (possibly swapped since boot), not the boot-time name
        served = (kwargs.get("model") or kwargs.get("adapter")
                  or (ctx.pool.current if ctx.pool is not None
                      else ctx.model_name))
        t0 = time.monotonic()
        # best_of > n: sample best_of candidates and keep the top n by
        # cumulative logprob (OpenAI completions semantics; vLLM ranking).
        # Ranking needs per-token logprobs — record chosen-token-only
        # (logprobs=0) when the client didn't ask for logprobs, and strip
        # them from the response afterwards.
        best_of = best_of or n
        rank_params = params
        internal_logprobs = False
        if best_of > n and params.logprobs is None:
            rank_params = dataclasses.replace(params, logprobs=0)
            internal_logprobs = True
        submits = self._submit_choices(rank_params, kwargs, best_of)
        deadline = t0 + ctx.config.request_timeout_s
        import queue as _queue

        def fail(code, message, etype="invalid_request_error",
                 headers=None):
            for rid, _ in submits:
                ctx.runner.abort(rid)
                ctx.engine.requests.pop(rid, None)
            self._error(code, message, etype, headers=headers)

        cands = []
        prompt_tokens = 0
        completion_tokens = 0
        echo_text = self._echo_text(body, chat, kwargs, params)
        # ONE scoring pass feeds both prompt-logprob response shapes:
        # the vLLM prompt_logprobs field and the OpenAI echo+logprobs
        # arrays (double-scoring a long prompt runs the quadratic
        # cache-less trunk twice while generation requests sit submitted)
        prompt_lp_field = None
        prompt_entries = None
        plp = body.get("prompt_logprobs")
        want_echo_entries = (not chat and echo_text is not None
                             and params.logprobs is not None)
        if plp is not None or want_echo_entries:
            eng = getattr(ctx.engine, "prefill", ctx.engine)
            try:
                pent = eng.score_prompts(
                    [self._prompt_ids(kwargs, params)],
                    top_n=max(int(plp or 0), params.logprobs or 0))[0]
            except ValueError as e:
                fail(400, str(e))
                return
            except Exception as e:
                # any scoring fault must still abort the already-submitted
                # generation requests or they decode to max_tokens and
                # leak their engine records
                logger.exception("prompt scoring failed")
                fail(500, str(e), "server_error")
                return
            if want_echo_entries:
                k = params.logprobs
                prompt_entries = [dict(e, top=e["top"][:k]) for e in pent]
            if plp is not None:
                prompt_lp_field = self._vllm_prompt_logprobs(
                    pent, int(plp), eng.tokenizer.id_to_token)
        for rid, q in submits:
            text_parts, token_ids, logprob_entries = [], [], []
            finish_reason = "stop"
            while True:
                try:
                    item = q.get(timeout=max(deadline - time.monotonic(), 0.001))
                except _queue.Empty:
                    # Abandoning without aborting would leave the engine
                    # generating to max_tokens and leak the record.
                    fail(504, "request timed out", "server_error")
                    return
                if item is None:
                    break
                if isinstance(item, Exception):
                    if isinstance(item, ValueError):   # rejected at intake
                        fail(400, str(item))
                    elif isinstance(item, ShedError):
                        # brownout shed / queue-full class eviction:
                        # retryable by contract, with the ladder's own
                        # backoff hint (runtime/slo.py)
                        fail(429, str(item), "overloaded", headers={
                            "Retry-After": str(
                                int(item.retry_after_s) + 1)})
                    elif isinstance(item, MemoryError):
                        # admission backpressure (scheduler max_waiting):
                        # retryable, not a server fault
                        fail(503, str(item), "server_error",
                             headers={"Retry-After": "1"})
                    elif isinstance(item, TimeoutError):
                        # queue-side deadline expiry (engine overloaded)
                        fail(504, str(item), "server_error")
                    else:                              # engine-side fault
                        fail(500, str(item), "server_error")
                    return
                text_parts.append(item.new_text)
                token_ids.extend(item.new_token_ids)
                if item.finish_reason is not None:
                    finish_reason = item.finish_reason.value
            req = ctx.engine.requests.pop(rid, None)
            text = "".join(text_parts)
            if echo_text is not None:
                text = echo_text + text
            if req is not None and rank_params.logprobs is not None:
                logprob_entries = req.logprobs
            if req is not None:
                prompt_tokens = req.num_prompt_tokens
            completion_tokens += len(token_ids)   # usage bills ALL candidates
            cands.append({"text": text, "entries": logprob_entries,
                          "finish_reason": finish_reason})
        if best_of > n:
            # stable sort: ties keep submission order
            cands.sort(key=lambda c: -sum(e["logprob"]
                                          for e in c["entries"]))
            cands = cands[:n]
        choices = []
        for idx, cand in enumerate(cands):
            text = cand["text"]
            finish_reason = cand["finish_reason"]
            logprob_entries = [] if internal_logprobs else cand["entries"]
            if prompt_entries is not None:
                logprob_entries = prompt_entries + logprob_entries
            if chat:
                message = {"role": "assistant", "content": text}
                if toolctx is not None:
                    content, tool_calls = toolctx.postprocess(text)
                    if tool_calls:
                        message = {"role": "assistant", "content": content,
                                   "tool_calls": tool_calls}
                        if finish_reason == "stop":
                            finish_reason = "tool_calls"
                choice = {"index": idx, "message": message,
                          "finish_reason": finish_reason}
                if logprob_entries:
                    choice["logprobs"] = self._chat_logprobs(logprob_entries)
            else:
                choice = {"index": idx, "text": text,
                          "finish_reason": finish_reason}
                if logprob_entries:
                    choice["logprobs"] = self._completions_logprobs(
                        logprob_entries)
            if prompt_lp_field is not None:
                choice["prompt_logprobs"] = prompt_lp_field
            choices.append(choice)
        oid = f"cmpl-{uuid.uuid4().hex[:24]}"
        usage = {
            "prompt_tokens": prompt_tokens,
            "completion_tokens": completion_tokens,
            "total_tokens": prompt_tokens + completion_tokens,
        }
        self._emit_engine_spans([rid for rid, _ in submits])
        self._settle_tenant(usage["total_tokens"])
        obj = "chat.completion" if chat else "text_completion"
        self._json(200, {"id": oid, "object": obj, "created": int(time.time()),
                         "model": served, "choices": choices,
                         "usage": usage})

    def _stream_response(self, body, params, chat, kwargs, n=1, toolctx=None):
        ctx = self.ctx
        # with a pool, the alias fall-through is served by whatever is
        # CURRENT (possibly swapped since boot), not the boot-time name
        served = (kwargs.get("model") or kwargs.get("adapter")
                  or (ctx.pool.current if ctx.pool is not None
                      else ctx.model_name))
        # vLLM-compatible extension: carry each chunk's token ids so
        # clients (and the load harness) can count tokens exactly — chunk
        # count != token count under fused multi-step decode.
        ret_ids = bool(body.get("return_token_ids"))
        submits = self._submit_choices(params, kwargs, n)
        oid = f"cmpl-{uuid.uuid4().hex[:24]}"
        # initialised BEFORE the try: the disconnect handlers settle the
        # tenant with whatever was actually served — a client that drops
        # the socket mid-stream must not refund tokens it received
        prompt_toks = 0
        completion_toks = 0

        def abort_all():
            for rid, _ in submits:
                ctx.runner.abort(rid)

        # HOLD the 200 until EVERY choice produces its first item: an
        # intake rejection (400 validation, 503 backpressure) must surface
        # as a real status line — a gateway doing flow control on 503s
        # never sees an error that only exists as an SSE chunk inside a
        # 200.  All n choices, not just choice 0: backpressure can admit
        # the first and reject the second.  Deferring headers costs
        # nothing: the choices share one prefill batch, so their first
        # tokens land together.
        deadline = time.monotonic() + ctx.config.request_timeout_s
        import queue as _queue
        firsts = []
        err = None
        for rid, q in submits:
            try:
                item = q.get(timeout=max(deadline - time.monotonic(),
                                         0.001))
            except _queue.Empty:
                err = TimeoutError("request timed out")
                break
            firsts.append(item)
            if isinstance(item, Exception):
                err = item
                break
        if err is not None:
            abort_all()
            for rid, _ in submits:
                ctx.engine.requests.pop(rid, None)
            if isinstance(err, TimeoutError):
                self._error(504, str(err), "server_error")
            elif isinstance(err, ShedError):
                self._error(429, str(err), "overloaded", headers={
                    "Retry-After": str(int(err.retry_after_s) + 1)})
            elif isinstance(err, MemoryError):
                self._error(503, str(err), "server_error",
                            headers={"Retry-After": "1"})
            elif isinstance(err, ValueError):
                self._error(400, str(err))
            else:
                self._error(500, str(err), "server_error")
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        # Window-coalesced SSE writes: chunks accumulate in ``buf`` and hit
        # the socket in ONE write+flush per drained batch (a fused decode
        # window's outputs land on the queue together, so a window's
        # events leave in one syscall instead of one write+flush per
        # token).  The BYTES are identical to per-chunk writing — only
        # the syscall grouping changes — and the buffer always flushes
        # before blocking on the queue, so nothing ready is ever held
        # back from the client.
        buf = bytearray()

        def send_chunk(payload: dict):
            data = b"data: " + json.dumps(payload).encode() + b"\n\n"
            buf.extend(hex(len(data))[2:].encode() + b"\r\n" + data
                       + b"\r\n")

        def flush_chunks():
            if buf:
                self.wfile.write(bytes(buf))
                buf.clear()
                self.wfile.flush()

        # n > 1: merge the per-choice output queues into one, tagged with
        # the choice index, so chunks interleave as they are produced (the
        # OpenAI streaming shape — each chunk carries its choice index).
        # The held-back first item re-enters ahead of everything else.
        if n == 1:
            merged = None
        else:
            merged = _queue.Queue()
            for i, item in enumerate(firsts):
                merged.put((i, item))
            import threading as _threading

            def pump(idx, q):
                while True:
                    item = q.get()
                    merged.put((idx, item))
                    if item is None or isinstance(item, Exception):
                        return
            for i, (_, q) in enumerate(submits):
                _threading.Thread(target=pump, args=(i, q),
                                  daemon=True).start()
        try:
            # computed BEFORE any chunk goes out: with include_usage,
            # OpenAI sends "usage": null on EVERY non-final chunk — role
            # and echo chunks included; strict clients index
            # chunk["usage"] unconditionally
            include_usage = bool(
                (body.get("stream_options") or {}).get("include_usage"))
            if chat:
                for i in range(n):
                    chunk = {"id": oid, "object": "chat.completion.chunk",
                             "model": served,
                             "choices": [{"index": i,
                                          "delta": {"role": "assistant"},
                                          "finish_reason": None}]}
                    if include_usage:
                        chunk["usage"] = None
                    send_chunk(chunk)
            echo_text = self._echo_text(body, chat, kwargs, params)
            if echo_text is not None:
                # OpenAI echo semantics: the prompt text leads the stream.
                # Prompt tokens are not completion tokens, so token_ids is
                # empty — but present when requested, preserving the
                # every-chunk counting contract.  With logprobs, the echo
                # chunk carries the PROMPT's logprob arrays (first entry
                # null) so the stream's arrays align with the echoed
                # tokens like the non-streaming response (vLLM streams
                # prompt_logprobs the same way).
                prompt_lp = None
                if params.logprobs is not None:
                    eng = getattr(ctx.engine, "prefill", ctx.engine)
                    try:
                        prompt_lp = self._completions_logprobs(
                            eng.score_prompts(
                                [self._prompt_ids(kwargs, params)],
                                top_n=params.logprobs)[0])
                    except Exception as e:   # headers are out: error chunk
                        logger.exception("prompt scoring failed")
                        abort_all()
                        send_chunk({"error": {"message": str(e)}})
                        flush_chunks()
                        done = b"data: [DONE]\n\n"
                        self.wfile.write(hex(len(done))[2:].encode()
                                         + b"\r\n" + done + b"\r\n")
                        self.wfile.write(b"0\r\n\r\n")
                        self.wfile.flush()
                        return
                for i in range(n):
                    choice = {"index": i, "text": echo_text,
                              "finish_reason": None}
                    if prompt_lp is not None:
                        choice["logprobs"] = prompt_lp
                    if ret_ids:
                        choice["token_ids"] = []
                    chunk = {"id": oid, "object": "text_completion",
                             "created": int(time.time()),
                             "model": served,
                             "choices": [choice]}
                    if include_usage:
                        chunk["usage"] = None
                    send_chunk(chunk)
            errored = False
            lp_cursor = [0] * n        # per-choice logprob emission offset
            # tools: hold marker text out of content deltas per choice;
            # parsed calls are emitted as a trailing tool_calls delta
            filters = ([toolctx.stream_filter() for _ in range(n)]
                       if chat and toolctx is not None else None)
            live = n
            # every choice's first item was read before the headers; for
            # n > 1 they were re-injected into the merged queue instead.
            # Sentinel, not None: a first item of None (finish marker
            # after an instant abort) must still be delivered, not
            # dropped.
            _consumed = object()
            held = firsts[0] if merged is None else _consumed
            while live:
                try:
                    if held is not _consumed:
                        idx, item = 0, held
                        held = _consumed
                    elif merged is None:
                        try:
                            # drain ready items without flushing between
                            # them (one window = one write)
                            idx, item = 0, submits[0][1].get_nowait()
                        except _queue.Empty:
                            flush_chunks()
                            idx, item = 0, submits[0][1].get(
                                timeout=max(deadline - time.monotonic(),
                                            0.001))
                    else:
                        try:
                            idx, item = merged.get_nowait()
                        except _queue.Empty:
                            flush_chunks()
                            idx, item = merged.get(
                                timeout=max(deadline - time.monotonic(),
                                            0.001))
                except _queue.Empty:
                    abort_all()
                    send_chunk({"error": {"message": "request timed out"}})
                    errored = True
                    break
                if item is None:
                    live -= 1
                    continue
                if isinstance(item, Exception):
                    send_chunk({"error": {"message": str(item)}})
                    errored = True
                    live -= 1
                    continue
                finish = item.finish_reason.value if item.finish_reason else None
                tc_deltas = None
                if chat:
                    text_out = item.new_text
                    if filters is not None:
                        text_out = filters[idx].feed(item.new_text)
                        if finish is not None:
                            tail, calls = filters[idx].finish()
                            text_out += tail
                            if calls:
                                tc_deltas = [dict(c.as_openai(), index=ci)
                                             for ci, c in enumerate(calls)]
                                if finish == "stop":
                                    finish = "tool_calls"
                    delta = {"content": text_out} if text_out else {}
                    choice = {"index": idx, "delta": delta,
                              "finish_reason": None if tc_deltas else finish}
                    obj = "chat.completion.chunk"
                else:
                    choice = {"index": idx, "text": item.new_text,
                              "finish_reason": finish}
                    obj = "text_completion"
                if params.logprobs is not None and item.new_token_ids:
                    # incremental logprobs: this chunk's slice of the
                    # request's accumulated entries (append-only, so the
                    # cross-thread read is safe)
                    req = ctx.engine.requests.get(submits[idx][0])
                    if req is not None:
                        lo = lp_cursor[idx]
                        entries = req.logprobs[lo:lo + len(item.new_token_ids)]
                        lp_cursor[idx] = lo + len(entries)
                        if entries:
                            choice["logprobs"] = (
                                self._chat_logprobs(entries) if chat
                                else self._completions_logprobs(entries))
                if ret_ids:
                    choice["token_ids"] = list(item.new_token_ids)
                completion_toks += len(item.new_token_ids)
                # the prompt is shared across the n choices: count it once
                prompt_toks = item.num_prompt_tokens
                chunk = {"id": oid, "object": obj,
                         "created": int(time.time()),
                         "model": served, "choices": [choice]}
                if include_usage:
                    chunk["usage"] = None     # OpenAI: null until the final chunk
                send_chunk(chunk)
                if tc_deltas:
                    # trailing delta carrying the parsed calls + the real
                    # finish_reason (the content chunk above sent None)
                    tchunk = {"id": oid, "object": obj,
                              "created": int(time.time()),
                              "model": served,
                              "choices": [{"index": idx,
                                           "delta": {"tool_calls": tc_deltas},
                                           "finish_reason": finish}]}
                    if include_usage:
                        tchunk["usage"] = None
                    send_chunk(tchunk)
            if include_usage and not errored:
                # OpenAI stream_options.include_usage: one final chunk with
                # empty choices carrying the aggregate usage (skipped after
                # an error chunk — a zero-prompt usage line would misreport)
                send_chunk({"id": oid,
                            "object": ("chat.completion.chunk" if chat
                                       else "text_completion"),
                            "created": int(time.time()),
                            "model": served, "choices": [],
                            "usage": {
                                "prompt_tokens": prompt_toks,
                                "completion_tokens": completion_toks,
                                "total_tokens": prompt_toks + completion_toks,
                            }})
            self._settle_tenant(prompt_toks + completion_toks)
            flush_chunks()
            done = b"data: [DONE]\n\n"
            self.wfile.write(hex(len(done))[2:].encode() + b"\r\n" + done + b"\r\n")
            self.wfile.write(b"0\r\n\r\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            abort_all()                 # client went away mid-stream
            # tokens already written to the socket were SERVED: settle
            # them, or dropping the connection before [DONE] would evade
            # the tenant's rate limit indefinitely
            self._settle_tenant(prompt_toks + completion_toks)
        except Exception:
            logger.exception("streaming failed")
            abort_all()
            self._settle_tenant(prompt_toks + completion_toks)
        finally:
            # still inside the request span: engine lifecycle child spans
            # attach under it (survives client-gone paths too)
            self._emit_engine_spans([rid for rid, _ in submits])
            for rid, _ in submits:
                ctx.engine.requests.pop(rid, None)


def build_server(argv=None):
    """Parse ``argv`` and build the :class:`OpenAIServer` it describes
    (engine included), not yet started.  Returns ``(server, args)``;
    ``server`` is None on a multi-host follower, which has by then run its
    lockstep loop to the coordinator's stop.  Everything that maps flags
    to ``EngineConfig`` lives here, so a caller that drives the server
    in-process (chip_smoke.py) gets exactly what ``python -m
    tpuserve.server`` would."""
    import argparse

    ap = argparse.ArgumentParser("tpuserve.server")
    ap.add_argument("--model", default="Qwen/Qwen3-0.6B")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--num-blocks", type=int, default=2048,
                    help="KV cache blocks; 0 auto-sizes to the device "
                         "memory the weights leave free (vLLM "
                         "gpu_memory_utilization analog)")
    ap.add_argument("--max-blocks-per-seq", type=int, default=64)
    ap.add_argument("--max-num-seqs", type=int, default=64)
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="admission backpressure: reject (HTTP 503) new "
                         "requests beyond this many waiting (0 = auto, "
                         "4x max-num-seqs; -1 disables)")
    ap.add_argument("--mixed-batching", action="store_true",
                    help="FORCE ragged mixed prefill+decode batching: "
                         "every step with admissible prefill work runs "
                         "ONE flat-token dispatch carrying all running "
                         "decode rows plus prefill-chunk tokens — no "
                         "phase split, so no stream waits out an "
                         "admission burst (supersedes "
                         "--interleave-batched-prefill).  Without the "
                         "flag the engine observes the route: mixed "
                         "where a decode step is bound by its weights "
                         "(/debug/engine decode_route)")
    ap.add_argument("--mixed-token-budget", type=int, default=2048,
                    help="flat-row budget per mixed step (Sarathi "
                         "chunk sizing; the decode rows' region comes "
                         "off it) — the p50-ITL vs admission-latency "
                         "knob")
    ap.add_argument("--interleave-batched-prefill", action="store_true",
                    help="compat shim (superseded by --mixed-batching): "
                         "one decode step between prefill admission "
                         "batches")
    ap.add_argument("--attn-impl", default="auto")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor parallel degree (0 = no mesh)")
    ap.add_argument("--pp", type=int, default=0,
                    help="pipeline parallel stages (0 = no mesh): layers + "
                         "KV cache stage-stacked over a ('pp',) mesh "
                         "(parallel/pipeline.py) — per-device weight and "
                         "cache bytes divide by the stage count.  "
                         "Mutually exclusive with --tp")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode pools in-process "
                         "(KV handoff over ICI within the slice)")
    ap.add_argument("--role", default=None, choices=["prefill", "decode"],
                    help="cross-pod disaggregation (parallel/disagg_net.py):"
                         " 'prefill' prefills locally and migrates KV to the"
                         " decode pool at --decode-url; 'decode' accepts"
                         " migrations on /internal/migrate")
    ap.add_argument("--decode-url", default=None,
                    help="decode-pool base URL (required with"
                         " --role prefill)")
    ap.add_argument("--chat-template", default=None,
                    help="path to a Jinja chat template overriding the "
                         "tokenizer's (ConfigMap-mounted in K8s)")
    ap.add_argument("--tool-call-parser", default=None,
                    choices=["hermes", "mistral", "llama3_json"],
                    help="tool-call output format for /v1/chat/completions "
                         "tools (default: inferred from the model family)")
    ap.add_argument("--warmup-embed", default=None,
                    help="comma-separated BxT embed buckets to pre-compile "
                         "(e.g. '8x128,1x512') so first /v1/embeddings "
                         "requests don't stall on a trunk compile")
    ap.add_argument("--speculative-k", type=int, default=0,
                    help="speculative decoding with k draft tokens "
                         "(0 disables; greedy requests only).  Proposals "
                         "come from n-gram prompt lookup, or a draft "
                         "model with --speculative-draft-model")
    ap.add_argument("--speculative-draft-model", default=None,
                    help="registered model name proposing the draft "
                         "tokens (stateless truncated-window drafts — "
                         "vLLM's draft-model mode); needs the target's "
                         "vocab")
    ap.add_argument("--speculative-draft-dir", default=None,
                    help="checkpoint dir for the draft model (default: "
                         "random init — test/smoke only)")
    ap.add_argument("--multi-step", type=int, default=None,
                    help="fused decode window size — S decode+sample steps "
                         "per dispatch (default: auto — 32 on TPU, off on "
                         "CPU; 1 disables).  Tokens stream in bursts of S")
    ap.add_argument("--no-adaptive-window", action="store_true",
                    help="fixed S windows: disable the arrival-triggered "
                         "shrink to --min-multi-step that bounds a new "
                         "request's admission wait under load")
    ap.add_argument("--min-multi-step", type=int, default=4,
                    help="window size while arrivals are landing "
                         "(adaptive window sizing; default 4)")
    ap.add_argument("--no-kv-tiers", action="store_true",
                    help="disable the tiered KV cache (HBM -> host-DRAM "
                         "-> PVC prefix offload; runtime/kv_tiers.py) — "
                         "evicted prefix blocks are destroyed instead of "
                         "demoted, the pre-tiering behaviour "
                         "(TPUSERVE_KV_TIERS=0 is the env twin)")
    ap.add_argument("--kv-host-bytes", type=int, default=0,
                    help="host-DRAM KV tier byte budget (0 = "
                         "TPUSERVE_KV_HOST_BYTES or 1 GiB)")
    ap.add_argument("--kv-spill-dir", default=None, metavar="DIR",
                    help="PVC spill directory for the third KV tier "
                         "(default: TPUSERVE_KV_SPILL_DIR; unset = no "
                         "spill tier, host overflow is dropped)")
    ap.add_argument("--kv-cache-dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "int8"],
                    help="KV cache storage dtype; int8 quantizes on write "
                         "(per-token, per-kv-head scales), halving KV read "
                         "bandwidth and doubling cache capacity")
    ap.add_argument("--lora", default=None, metavar="DIR",
                    help="PEFT LoRA adapter directory merged into the "
                         "weights at load (one adapter per engine, zero "
                         "runtime cost)")
    ap.add_argument("--lora-modules", default=None, nargs="+",
                    metavar="NAME=DIR",
                    help="multi-LoRA serving (vLLM flag): load adapters as "
                         "a stacked bank; requests select one by sending "
                         "its NAME as the 'model' field, mixed-adapter "
                         "batches run in one dispatch; composes with "
                         "--quantization int8")
    ap.add_argument("--quantization", default=None, choices=["int8"],
                    help="weight-only quantization (int8 halves decode's "
                         "HBM weight traffic)")
    ap.add_argument("--multihost", action="store_true",
                    help="join a multi-host TPU slice via jax.distributed "
                         "(GKE injects TPU_WORKER_* env); process 0 serves, "
                         "others follow in lockstep")
    ap.add_argument("--pipeline", dest="pipeline", action="store_true",
                    default=None,
                    help="force pipelined decode (in-flight step/window "
                         "resolved one engine iteration late); default: "
                         "auto — on on TPU, off on CPU")
    ap.add_argument("--no-pipeline", dest="pipeline", action="store_false",
                    help="force synchronous decode")
    ap.add_argument("--step-watchdog-s", type=float, default=0.0,
                    help="hang watchdog: a dispatch blocking longer than "
                         "this is declared stuck — in-flight requests are "
                         "salvaged (re-queued + replayed) the same way an "
                         "exception would trigger, instead of clients "
                         "hanging forever on a wedged device call "
                         "(0 disables; scaled up for early compile steps)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="deterministic fault injection for chaos drills "
                         "(runtime/faults.py), e.g. "
                         "'decode_dispatch:raise:0.02'; equivalent to the "
                         "TPUSERVE_FAULTS env var")
    ap.add_argument("--no-slo-classes", action="store_true",
                    help="disable SLO class scheduling + the brownout "
                         "ladder (runtime/slo.py): classless FIFO, no "
                         "class-aware admission/preemption/shedding "
                         "(TPUSERVE_SLO_CLASSES=0 is the env twin)")
    ap.add_argument("--tenant-config", default=None, metavar="JSON|PATH",
                    help="per-tenant token metering + rate limits "
                         "(server/tenants.py); inline JSON or a file "
                         "path (default: TPUSERVE_TENANTS).  Behind the "
                         "gateway, configure limits there instead")
    ap.add_argument("--no-slo-burn", action="store_true",
                    help="disable the in-process SLO burn-rate "
                         "evaluator (tpuserve/obs; TPUSERVE_SLO_BURN=0 "
                         "is the env twin)")
    ap.add_argument("--slo-objectives", default=None,
                    metavar="JSON|PATH",
                    help="SLO objectives override (tpuserve/obs/"
                         "objectives.py); inline JSON list or a file "
                         "path (default: TPUSERVE_SLO_OBJECTIVES, else "
                         "the registry defaults).  Validated at boot")
    ap.add_argument("--model-catalog", default=None, metavar="JSON|LIST",
                    help="model-pool catalog (tpuserve/modelpool): a JSON "
                         "object of name -> checkpoint dir, or a comma-"
                         "separated name list; requests naming a "
                         "registered model hot-swap the engine at the "
                         "next idle boundary (default: "
                         "TPUSERVE_MODEL_CATALOG; TPUSERVE_MODELPOOL=0 "
                         "disables the pool entirely)")
    ap.add_argument("--swap-policy", default="swap",
                    choices=["swap", "reject"],
                    help="registered-but-cold model requests: 'swap' "
                         "parks them for a hot-swap, 'reject' answers "
                         "503 + Retry-After so the gateway retries a "
                         "replica already holding the weights")
    ap.add_argument("--max-resident-models", type=int, default=1,
                    help="co-serving: how many models' weights may stay "
                         "live in HBM at once (swapping between resident "
                         "models skips both the weight copy and XLA)")
    ap.add_argument("--weight-host-bytes", type=int, default=0,
                    help="host-DRAM weight tier byte budget for demoted "
                         "models (0 = TPUSERVE_WEIGHT_HOST_BYTES or "
                         "2 GiB)")
    ap.add_argument("--weight-spill-dir", default=None, metavar="DIR",
                    help="PVC spill directory for the third weight tier "
                         "(default: TPUSERVE_WEIGHT_SPILL_DIR; unset = "
                         "host overflow means a cold load next time)")
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--drain-timeout", type=float, default=25.0,
                    help="graceful-drain budget on SIGTERM, seconds; keep "
                         "below the pod's terminationGracePeriodSeconds")
    args = ap.parse_args(argv)
    with STARTUP.phase("startup.build"):
        return _server_from_args(ap, args), args


def _server_from_args(ap, args):
    """``build_server``'s second half, under its ``startup.build`` span:
    the parsed flags to the server object (None on a multi-host
    follower)."""
    import jax

    from tpuserve.runtime.engine import Engine, EngineConfig
    from tpuserve.runtime.kv_cache import CacheConfig
    from tpuserve.runtime.scheduler import SchedulerConfig

    logging.basicConfig(level=logging.INFO)
    if args.multihost:
        from tpuserve.parallel.mesh import multihost_initialize
        multihost_initialize()
    with STARTUP.phase("startup.backend"):
        # the first touch of the backend: the TPU runtime starts here,
        # unless the caller touched it first
        jax.devices()
    spec = None
    if args.speculative_k > 0:
        from tpuserve.runtime.spec import SpecConfig
        spec = SpecConfig(num_draft_tokens=args.speculative_k,
                          draft_model=args.speculative_draft_model,
                          draft_checkpoint_dir=args.speculative_draft_dir)
    elif args.speculative_draft_model:
        ap.error("--speculative-draft-model needs --speculative-k > 0")
    if args.speculative_draft_dir and not args.speculative_draft_model:
        ap.error("--speculative-draft-dir needs --speculative-draft-model "
                 "(the dir would be silently ignored)")
    lora_modules = None
    if args.lora_modules:
        lora_modules = {}
        for spec_str in args.lora_modules:
            name, sep, path = spec_str.partition("=")
            if not sep or not name or not path:
                ap.error(f"--lora-modules entries must be NAME=DIR, got "
                         f"{spec_str!r}")
            if name == args.model:
                ap.error(f"adapter name {name!r} collides with the base "
                         "model name")
            if name in lora_modules:
                ap.error(f"duplicate adapter name {name!r} in "
                         "--lora-modules")
            lora_modules[name] = path
    ecfg = EngineConfig(
        model=args.model, checkpoint_dir=args.checkpoint_dir,
        lora_dir=args.lora, lora_modules=lora_modules,
        cache=CacheConfig(block_size=args.block_size,
                          num_blocks=args.num_blocks,
                          max_blocks_per_seq=args.max_blocks_per_seq,
                          dtype=args.kv_cache_dtype),
        scheduler=SchedulerConfig(
            max_num_seqs=args.max_num_seqs,
            max_waiting=args.max_waiting,
            mixed_batching=args.mixed_batching,
            mixed_token_budget=args.mixed_token_budget,
            interleave_batched_prefill=args.interleave_batched_prefill),
        attn_impl=args.attn_impl, speculative=spec,
        multi_step=args.multi_step, pipeline_decode=args.pipeline,
        adaptive_multi_step=not args.no_adaptive_window,
        min_multi_step=args.min_multi_step,
        quantization=args.quantization,
        kv_tiers=False if args.no_kv_tiers else None,
        kv_host_bytes=args.kv_host_bytes, kv_spill_dir=args.kv_spill_dir,
        slo_classes=False if args.no_slo_classes else None,
        faults=args.faults, step_watchdog_s=args.step_watchdog_s)
    mesh = None
    if args.pp > 1 and args.tp > 1:
        ap.error("--pp and --tp are mutually exclusive (tp-within-stage "
                 "composition is future work)")
    if args.pp > 1 and (args.disagg or args.role or args.multihost):
        ap.error("--pp is a single-process colocated topology; drop "
                 "--disagg/--role/--multihost")
    if args.pp > 1:
        from tpuserve.parallel import MeshConfig, make_mesh
        mesh = make_mesh(MeshConfig(pp=args.pp))
    elif args.tp > 1:
        from tpuserve.parallel import MeshConfig, make_mesh
        mesh = make_mesh(MeshConfig(dp=1, tp=args.tp))
    elif args.multihost:
        # Lockstep serving needs a global mesh on EVERY process; default to
        # TP over all devices.  Deciding this here (before the
        # coordinator/follower split) matters: a coordinator-only failure
        # would strand followers in broadcast_one_to_all forever.
        from tpuserve.parallel import make_mesh
        mesh = make_mesh()
    if args.role and (args.disagg or args.multihost):
        ap.error("--role prefill/decode is its own topology; drop "
                 "--disagg/--multihost")
    if args.role == "prefill":
        if not args.decode_url:
            ap.error("--role prefill requires --decode-url")
        from tpuserve.parallel.disagg_net import PrefillHandoffEngine
        engine = PrefillHandoffEngine(ecfg, args.decode_url, mesh=mesh)
    elif args.disagg:
        from tpuserve.parallel.disagg import DisaggregatedEngine
        engine = DisaggregatedEngine(ecfg, ecfg, mesh=mesh)
    else:
        engine = Engine(ecfg, mesh=mesh)
    if args.multihost:
        from tpuserve.parallel import multihost
        if not multihost.is_coordinator():
            # Followers never serve HTTP: mirror the coordinator's steps
            # until it broadcasts OP_STOP, then exit.
            multihost.follower_loop(engine)
            return None
        multihost.MultihostCoordinator(engine)
    chat_template = None
    if args.chat_template:
        chat_template = open(args.chat_template).read()
    warmup_embed = ()
    if args.warmup_embed:
        try:
            warmup_embed = tuple(
                (int(b.lower().split("x")[0]), int(b.lower().split("x")[1]))
                for b in args.warmup_embed.split(","))
        except (ValueError, IndexError):
            ap.error("--warmup-embed must be comma-separated BxT pairs, "
                     "e.g. '8x128,1x512'")
    server = OpenAIServer(engine, ServerConfig(
        host=args.host, port=args.port, chat_template=chat_template,
        tool_call_parser=args.tool_call_parser, warmup_embed=warmup_embed,
        tenant_config=args.tenant_config,
        slo_burn=not args.no_slo_burn,
        slo_objectives=args.slo_objectives,
        model_catalog=args.model_catalog,
        swap_policy=args.swap_policy,
        max_resident_models=args.max_resident_models,
        weight_host_bytes=args.weight_host_bytes,
        weight_spill_dir=args.weight_spill_dir,
        allow_kv_migration=args.role == "decode"))
    return server


def main(argv=None):
    """Start the server ``argv`` describes, wait for SIGTERM, drain."""
    compile_cache.configure()
    server, args = build_server(argv)
    if server is None:
        return
    port = server.start(warmup=not args.no_warmup)
    print(f"tpuserve listening on {args.host}:{port}", flush=True)
    # K8s rolling updates SIGTERM the pod, then SIGKILL after
    # terminationGracePeriodSeconds: drain (readyz->503, new work 503,
    # in-flight finishes) inside that window instead of dying mid-stream
    import signal
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
        logger.info("SIGTERM: draining")
        server.drain(timeout_s=args.drain_timeout)
    except KeyboardInterrupt:
        server.drain(timeout_s=args.drain_timeout)


if __name__ == "__main__":
    main()
