"""One shared deploy config for the whole pipeline.

The reference scatters its configuration across per-playbook ``vars:`` blocks
with duplicated values — the served model name appears in both
llm-d-deploy.yaml:118 and llm-d-test.yaml:7, namespaces in three files
(SURVEY.md §5 flags this as a flaw to fix).  Here every layer reads the same
``DeployConfig``, loadable from a YAML file with env-var overrides.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass
class DeployConfig:
    # --- infra (launch-instance.yaml:6-13 analog: instance/AMI/region) ----
    provider: str = "gke"                  # "gke" | "local" (existing kubeconfig / kind)
    project: str = ""                      # GCP project (like AWS account implied by creds)
    region: str = "us-central1"            # reference: us-east-2 (launch-instance.yaml:7)
    zone: str = "us-central1-a"            # reference: us-east-2b availability zone
    cluster_name: str = "tpu-serve"
    tpu_type: str = "v5litepod-4"          # reference: g6.4xlarge 1xL4 (launch-instance.yaml:8)
    tpu_topology: str = "2x2"
    num_nodes: int = 1                     # single-node by design, like the reference
    disk_size_gb: int = 500                # reference: 500GB gp3 (launch-instance.yaml:12)
    machine_type: str = "ct5lp-hightpu-4t"
    gke_version: Optional[str] = None      # reference pins K8s 1.33 (kubernetes-single-node.yaml:7)

    # --- serving (llm-d-deploy.yaml:113-119 analog) -----------------------
    namespace: str = "tpu-serve"           # reference: llm-d
    model: str = "Qwen/Qwen3-0.6B"         # reference: llm-d-deploy.yaml:118
    replicas: int = 1                      # DP via replica count + gateway LB
    tensor_parallel: int = 4               # chips per replica, sharded over ICI
    disaggregated: bool = False            # prefill/decode pool split (llm-d topology)
    # Cross-pod disaggregation: SEPARATE prefill and decode Deployments,
    # independently scalable (llm-d's actual topology; KV rides the pod
    # network via /internal/migrate — parallel/disagg_net.py).  False keeps
    # both pools in one pod with the KV handoff over ICI, which is strictly
    # cheaper within a slice (parallel/disagg.py).
    disagg_cross_pod: bool = False
    prefill_replicas: int = 1              # cross-pod: prefill pool size
    decode_replicas: int = 1               # cross-pod: decode pool size
    # Engine performance knobs, forwarded to `python -m tpuserve.server`:
    # the deploy layer must be able to express every serving-perf feature
    # the engine has, or clusters ship with the slow defaults.
    quantization: Optional[str] = None     # "int8" weight-only quant
    kv_cache_dtype: str = "bfloat16"       # "int8" = quantized KV cache
    speculative_k: int = 0                 # n-gram speculative decoding
    multi_step: Optional[int] = None       # fused decode window override
    # Pipeline parallelism: stage count per replica (mutually exclusive
    # with tensor_parallel > 1; parallel/pipeline.py).  Chips per replica
    # become pipeline_parallel instead of tensor_parallel.
    pipeline_parallel: int = 1
    # Multi-LoRA serving: {adapter_name: path-inside-model-pvc}; forwarded
    # as --lora-modules so requests pick adapters by the "model" field
    lora_modules: Optional[dict] = None
    # Model pool (tpuserve/modelpool, ISSUE 17): catalog of models one
    # replica may serve by weight tiering + hot-swap.  A YAML mapping
    # {name: checkpoint-dir-or-null}, a JSON object string, or a comma
    # list of names; exported as TPUSERVE_MODEL_CATALOG to the engine
    # pods.  None/empty = no pool — one-model behaviour byte-identical.
    model_catalog: Optional[str] = None
    # Host-DRAM weight tier byte budget for demoted param sets
    # (TPUSERVE_WEIGHT_HOST_BYTES); 0 = engine default (2 GiB)
    weight_host_bytes: int = 0
    # Tiered KV cache (runtime/kv_tiers.py): demote evicted prefix KV to
    # host DRAM and from there to a spill dir on the model PVC instead of
    # destroying it; restore asynchronously ahead of admission.  The
    # reference's pods are stateless — every pod restart or cache miss
    # re-prefills from zero (PARITY.md).
    kv_tiers: bool = True
    # host-DRAM tier byte budget (server --kv-host-bytes); 0 = engine
    # default (TPUSERVE_KV_HOST_BYTES or 1 GiB)
    kv_host_bytes: int = 0
    # PVC spill dir for the third tier (server --kv-spill-dir); lives on
    # the model PVC next to the compile caches so demoted prefixes
    # survive pod restarts.  Empty = no spill tier.
    kv_spill_dir: str = "/models/.kv-spill"
    # Admission backpressure cap (server --max-waiting); 0 = auto
    max_waiting: int = 0
    # SLO class scheduling + brownout ladder (runtime/slo.py): class-
    # ordered admission, budget headroom for interactive traffic,
    # priority preemption of batch rows, graceful shed under overload.
    # False emits --no-slo-classes (classless FIFO, the pre-SLO
    # behaviour; TPUSERVE_SLO_CLASSES=0 is the runtime twin).
    slo_classes: bool = True
    # Per-tenant token metering + rate limits (server/tenants.py),
    # exported as TPUSERVE_TENANTS to the engine pods.  For gateway-
    # fronted fleets configure the gateway instead (one charge per
    # request).  None = no tenancy config (metering under 'default').
    tenants: Optional[dict] = None
    # In-process SLO burn-rate evaluator (tpuserve/obs): firing state on
    # /debug/engine, aggregated by /gateway/slo.  False exports
    # TPUSERVE_SLO_BURN=0 to the engine pods (the env twin of the
    # server's --no-slo-burn).
    slo_burn: bool = True
    # Post-mortem bundle directory of the engine flight recorder
    # (runtime/flight.py) — on the model PVC next to the compile
    # caches, so watchdog/fault-storm bundles and the profiler traces
    # of runtime/devprof.py survive the pod that wrote them (exported
    # as TPUSERVE_FLIGHT_DIR).
    flight_dir: str = "/models/.flight"
    # Hang watchdog threshold (server --step-watchdog-s): a dispatch
    # blocking past this is failed + salvaged like an exception instead
    # of stranding clients behind a wedged device call.  0 disables.
    step_watchdog_s: float = 0.0
    # Chaos drills: fault-injection spec exported as TPUSERVE_FAULTS to
    # the engine pods (runtime/faults.py), e.g.
    # "decode_dispatch:raise:0.02".  None = no injection (production).
    faults: Optional[str] = None
    # SLI-driven autoscaler (tpuserve/autoscale, ISSUE 12): a scaler
    # Deployment that scrapes every engine pod's /debug/engine scalars
    # (brownout level, per-class queue-delay EWMAs, TTFT p95) and
    # drives `kubectl scale` on the engine Deployment — out on SLI
    # pressure BEFORE the brownout ladder sheds, in only when the pool
    # sat idle + drained, from zero on gateway-reported demand.  Plain
    # single-Deployment engine topologies only (the scaler targets ONE
    # Deployment; disagg/multihost pools aren't scalable units here).
    autoscale: bool = False
    autoscale_min_replicas: int = 0        # 0 = scale-to-zero allowed
    autoscale_max_replicas: int = 4
    autoscale_interval_s: int = 5          # control-loop cadence
    # Synthetic canary (tpuserve/obs/canary.py, ISSUE 13): the gateway
    # probes itself with one tagged tiny request per SLO class every
    # this-many seconds — black-box tpuserve_canary_* SLIs on the
    # gateway /metrics, breach state on /gateway/status (an autoscale
    # scale-out trigger).  0 disables the prober.
    canary_interval_s: float = 15.0
    # Graceful-drain budget on SIGTERM (server --drain-timeout); the
    # emitted pod spec's terminationGracePeriodSeconds is derived from
    # this (+35 s headroom) so K8s never SIGKILLs mid-drain
    drain_timeout_s: int = 25
    storage_class: str = "standard-rwo"    # reference: local-path (llm-d-deploy.yaml:115)
    # General model-storage PVC size (reference: llm-d-deploy.yaml:116
    # ships 50Gi).  None = track model_pvc_size: earlier releases sized
    # the model-storage PVCs from that field, and K8s forbids shrinking
    # an existing PVC's storage request — an independent default would
    # break idempotent re-provisioning for anyone who overrode
    # model_pvc_size while this field was dead.
    storage_size: Optional[str] = None
    model_pvc_size: str = "100Gi"          # reference workaround PVC (llm-d-deploy.yaml:207)
    image: str = "tpuserve:latest"         # engine container image (tag)
    # Registry prefix the image is pushed to and pulled from (e.g.
    # "us-central1-docker.pkg.dev/PROJECT/tpuserve").  Required for
    # provider=gke (nodes can't pull a local-only tag); empty on
    # provider=local, where the image is side-loaded into kind/minikube.
    image_registry: str = ""
    # Build+push/load the image during deploy (provision/image.py).  False =
    # the image reference is already pullable (CI pushed it).
    build_image: bool = True
    hf_token_file: str = "~/.cache/huggingface/token"  # reference: llm-d-deploy.yaml:117
    chat_template: Optional[str] = None    # name of a bundled template (phi/opt)
    engine_port: int = 8000                # vLLM-compatible metrics port (otel-observability-setup.yaml:379)
    gateway_port: int = 8080
    # HA gateway pool (llm-d's gateway is HA by platform, llm-d-test.yaml:
    # 14-18).  Safe >1 since affinity is stateless rendezvous hashing —
    # every replica computes the same prefix->backend mapping.
    gateway_replicas: int = 2
    # Gateway API class for the optional Gateway/HTTPRoute front (applied
    # only when the cluster has the CRDs; GKE ships this class built in).
    gateway_class: str = "gke-l7-regional-external-managed"

    # --- observability (otel-observability-setup.yaml:7-12 analog) --------
    monitoring_namespace: str = "monitoring"
    observability_namespace: str = "observability"
    otel_namespace: str = "otel-monitoring"
    tpu_metrics_interval_s: int = 5        # reference: DCGM 5s (kubernetes-single-node.yaml:487)
    otel_scrape_interval_s: int = 15       # reference: otel-observability-setup.yaml:190
    prometheus_retention: str = "15d"      # reference: kubernetes-single-node.yaml:428
    otel_prometheus_retention: str = "30d" # reference: otel-observability-setup.yaml:236
    otel_prometheus_retention_size: str = "10GB"
    grafana_admin_password: str = "admin"  # reference: kubernetes-single-node.yaml:427

    # --- timeouts (reference envelope, SURVEY.md §6) ----------------------
    install_timeout_s: int = 1800          # llm-d-deploy.yaml:192
    pods_ready_timeout_s: int = 1800       # llm-d-deploy.yaml:232
    # Node-Ready poll budget, the reference's SSH-up analog
    # (launch-instance.yaml:69 waits 300).  600 preserves the ceiling
    # the poll historically had (30 retries x ~20s/attempt) — fresh GKE
    # TPU slices routinely take 6-9 min to go Ready.
    node_ready_timeout_s: int = 600

    def validate(self) -> None:
        if self.provider not in ("gke", "local"):
            raise ValueError(f"unknown provider {self.provider!r}")
        if self.tensor_parallel < 1 or self.replicas < 1:
            raise ValueError("replicas and tensor_parallel must be >= 1")
        if self.prefill_replicas < 1 or self.decode_replicas < 1:
            raise ValueError("prefill_replicas and decode_replicas must "
                             "be >= 1")
        if self.gateway_replicas < 1:
            raise ValueError("gateway_replicas must be >= 1")
        # Engine knobs are forwarded verbatim to the server's argparse:
        # reject HERE what it would reject, or an invalid value passes the
        # build-time manifest validation and only surfaces as an
        # in-cluster CrashLoopBackOff.
        if self.quantization not in (None, "int8"):
            raise ValueError(f"quantization must be int8 or unset, "
                             f"got {self.quantization!r}")
        if self.kv_cache_dtype not in ("bfloat16", "float32", "int8"):
            raise ValueError(f"kv_cache_dtype must be bfloat16/float32/"
                             f"int8, got {self.kv_cache_dtype!r}")
        if self.speculative_k < 0:
            raise ValueError("speculative_k must be >= 0")
        if self.multi_step is not None and self.multi_step < 1:
            raise ValueError("multi_step must be >= 1 when set")
        if self.pipeline_parallel < 1:
            raise ValueError("pipeline_parallel must be >= 1")
        if self.step_watchdog_s < 0:
            raise ValueError("step_watchdog_s must be >= 0 (0 disables)")
        if self.faults:
            # parse at deploy time: a typo'd chaos spec must fail HERE,
            # not as an in-cluster CrashLoopBackOff
            from tpuserve.runtime.faults import FaultInjector
            FaultInjector.from_spec(self.faults)
        if self.tenants is not None:
            # same deploy-time-parse rule as faults: a malformed tenant
            # config must fail the deploy, not CrashLoop the pods
            from tpuserve.server.tenants import TenantRegistry
            TenantRegistry.from_config(self.tenants)
        if self.pipeline_parallel > 1 and self.tensor_parallel > 1:
            raise ValueError("pipeline_parallel and tensor_parallel are "
                             "mutually exclusive (the server rejects "
                             "--pp with --tp)")
        if self.pipeline_parallel > 1 and (self.disaggregated
                                           or self.disagg_cross_pod):
            raise ValueError("pipeline_parallel is incompatible with "
                             "disaggregated topologies")
        if self.pipeline_parallel > self.chips_per_node:
            # the multihost StatefulSet path is tp-only (the server
            # rejects --pp with --multihost); an oversized pp would emit
            # an unschedulable single-pod chip request and hang the
            # deploy for pods_ready_timeout_s
            raise ValueError(
                f"pipeline_parallel={self.pipeline_parallel} exceeds the "
                f"{self.chips_per_node} chips of one {self.tpu_type} node "
                "(pipeline stages are single-host)")
        if self.lora_modules is not None:
            if not isinstance(self.lora_modules, dict) or not all(
                    isinstance(k, str) and isinstance(v, str) and k and v
                    and "=" not in k
                    for k, v in self.lora_modules.items()):
                raise ValueError("lora_modules must map adapter names "
                                 "(no '=') to paths")
        if self.lora_modules:      # empty dict = no adapters = no limits
            if self.model in self.lora_modules:
                # the server's argparse rejects this at startup — catch it
                # before it becomes an in-cluster CrashLoopBackOff
                raise ValueError(f"adapter name {self.model!r} collides "
                                 "with the served model name")
            if self.tensor_parallel > 1 or self.pipeline_parallel > 1 \
                    or self.disaggregated or self.disagg_cross_pod \
                    or self.speculative_k:
                raise ValueError("lora_modules needs plain single-chip "
                                 "replicas (the engine rejects multi-LoRA "
                                 "with tp/pp/disagg/speculation)")
        if self.kv_host_bytes < 0:
            raise ValueError("kv_host_bytes must be >= 0 (0 = engine "
                             "default)")
        if self.weight_host_bytes < 0:
            raise ValueError("weight_host_bytes must be >= 0 (0 = "
                             "engine default)")
        if self.model_catalog:
            # deploy-time-parse rule (same as faults/tenants): a typo'd
            # catalog must fail the deploy, not CrashLoop the pods
            from tpuserve.modelpool import parse_catalog
            parse_catalog(self.model_catalog)
            if self.disaggregated or self.disagg_cross_pod:
                raise ValueError("model_catalog needs a plain engine "
                                 "topology (the pool swaps ONE engine; "
                                 "disagg replicas are two)")
        if self.max_waiting < -1:
            raise ValueError("max_waiting must be >= -1")
        if self.drain_timeout_s < 0:
            raise ValueError("drain_timeout_s must be >= 0")
        if self.canary_interval_s < 0:
            raise ValueError("canary_interval_s must be >= 0 "
                             "(0 disables the gateway canary)")
        if self.autoscale:
            if not (0 <= self.autoscale_min_replicas
                    <= self.autoscale_max_replicas) \
                    or self.autoscale_max_replicas < 1:
                raise ValueError(
                    "need 0 <= autoscale_min_replicas <= "
                    "autoscale_max_replicas (and max >= 1), got "
                    f"{self.autoscale_min_replicas}.."
                    f"{self.autoscale_max_replicas}")
            if self.autoscale_interval_s < 1:
                raise ValueError("autoscale_interval_s must be >= 1")
            if self.disaggregated or self.disagg_cross_pod:
                raise ValueError(
                    "autoscale targets the plain engine Deployment; "
                    "disaggregated pools are not a scalable unit here "
                    "(see ROADMAP: the disagg-pool autoscale question "
                    "rides on the TPU A/B)")
            if self.tensor_parallel > self.chips_per_node:
                raise ValueError(
                    "autoscale does not cover multihost StatefulSet "
                    "replicas (one replica = N pods there)")
            if not self.slo_classes:
                # the policy's scale-out triggers ARE the SLO
                # controller's scalars; a pool without them looks
                # permanently idle to the scaler
                raise ValueError(
                    "autoscale consumes the SLO controller's brownout/"
                    "queue-delay scalars — it requires slo_classes")
        # NOTE: the GCP-project requirement is enforced at provision time
        # (infra._provision_gke), not here — subcommands like `test` read
        # cluster identity from the inventory file and need no project.

    @property
    def chips_per_node(self) -> int:
        # v5litepod-N exposes N chips on the node; topology 2x2 -> 4.
        try:
            return int(self.tpu_type.rsplit("-", 1)[1])
        except (IndexError, ValueError):
            return 4

    @property
    def chips_per_replica(self) -> int:
        """TPU chips one engine replica requests — pipeline stages or
        tensor shards, whichever parallelism is active.  The ONE place
        the pp-vs-tp arithmetic lives (manifests + CLI consume it)."""
        return (self.pipeline_parallel if self.pipeline_parallel > 1
                else self.tensor_parallel)

    @property
    def parallelism_desc(self) -> str:
        return (f"pp={self.pipeline_parallel}"
                if self.pipeline_parallel > 1
                else f"tp={self.tensor_parallel}")


_ENV_PREFIX = "TPUSERVE_"


def load_config(path: Optional[str] = None, preset: Optional[str] = None,
                **overrides) -> DeployConfig:
    """Load config from preset (if given), then YAML, env vars, overrides.

    Env override example: TPUSERVE_MODEL=facebook/opt-1.3b.  The reference
    supports only HF_TOKEN via env (llm-d-deploy.yaml:187-189); everything
    else required editing playbooks (README.md:80-104).
    """
    data: dict = {}
    if path:
        import yaml
        with open(os.path.expanduser(path)) as f:
            data.update(yaml.safe_load(f) or {})
    fields = {f.name: f for f in dataclasses.fields(DeployConfig)}
    for name, field in fields.items():
        env = os.environ.get(_ENV_PREFIX + name.upper())
        if env is not None:
            data[name] = _coerce(env, field.type)
    data.update({k: v for k, v in overrides.items() if v is not None})
    if preset:
        data = apply_preset(data, preset)
    unknown = set(data) - set(fields)
    if unknown:
        raise ValueError(f"unknown config keys: {sorted(unknown)}")
    cfg = DeployConfig(**data)
    cfg.validate()
    return cfg


def _coerce(value: str, typ) -> object:
    t = str(typ)
    if "int" in t:
        return int(value)
    if "bool" in t:
        return value.lower() in ("1", "true", "yes", "on")
    return value


# --------------------------------------------------------------------------
# Deploy presets — the BASELINE.json "configs" as one-flag deployments
# --------------------------------------------------------------------------

#: Named presets for the tracked BASELINE configs (BASELINE.md "Tracked
#: configs"); each is a dict of DeployConfig overrides applied on top of the
#: YAML/env/CLI layers.  The reference needed playbook edits to change any
#: of this (README.md:80-104).
PRESETS: dict[str, dict] = {
    # default single-host serve target (llm-d-deploy.yaml:118)
    "qwen3-0.6b-v5e4": {
        "model": "Qwen/Qwen3-0.6B",
        "tpu_type": "v5litepod-4", "tpu_topology": "2x2",
        "machine_type": "ct5lp-hightpu-4t", "tensor_parallel": 4,
    },
    # alternate models (kubernetes-single-node.yaml:15, templates/*.yaml)
    "phi3-mini-v5e4": {
        "model": "microsoft/Phi-3-mini-4k-instruct",
        "tpu_type": "v5litepod-4", "tpu_topology": "2x2",
        "machine_type": "ct5lp-hightpu-4t", "tensor_parallel": 4,
        "chat_template": "phi",
    },
    "opt-1.3b-v5e4": {
        "model": "facebook/opt-1.3b",
        "tpu_type": "v5litepod-4", "tpu_topology": "2x2",
        "machine_type": "ct5lp-hightpu-4t", "tensor_parallel": 4,
        "chat_template": "opt",
    },
    # sliding-window long-context serving (beyond the reference's model
    # set): rolling-buffer KV keeps cache footprint O(window), int8
    # weights+KV halve decode's HBM bytes
    "mistral-7b-v5e4": {
        "model": "mistralai/Mistral-7B-Instruct-v0.1",
        "tpu_type": "v5litepod-4", "tpu_topology": "2x2",
        "machine_type": "ct5lp-hightpu-4t", "tensor_parallel": 4,
        "quantization": "int8", "kv_cache_dtype": "int8",
    },
    # disaggregated prefill/decode pools on a v5e-8 (BASELINE "Llama-3-8B
    # disaggregated prefill/decode on v5e-8"): 4 chips prefill + 4 decode,
    # KV handoff over ICI within the slice
    "llama3-8b-disagg-v5e8": {
        "model": "meta-llama/Meta-Llama-3-8B-Instruct",
        "tpu_type": "v5litepod-8", "tpu_topology": "2x4",
        "machine_type": "ct5lp-hightpu-8t", "tensor_parallel": 4,
        "disaggregated": True,
    },
    # multi-host TP=8 at v5e-16 total capacity (BASELINE "Qwen2-72B TP=8
    # multi-host v5e-16"): two 2x4 slices (2 hosts x 4 chips each), each a
    # tp=8 replica — jax.distributed joins each slice and GSPMD routes the
    # collectives over ICI; the gateway load-balances the two replicas
    "qwen2-72b-tp8-v5e16": {
        "model": "Qwen/Qwen2-72B-Instruct",
        "tpu_type": "v5litepod-4", "tpu_topology": "2x4",
        "machine_type": "ct5lp-hightpu-4t", "num_nodes": 4,
        "tensor_parallel": 8, "replicas": 2,
        "model_pvc_size": "300Gi",
    },
    # cross-pod variant of the disaggregated config: separate prefill and
    # decode Deployments on their own v5e-4 slices, independently scalable
    # (llm-d's actual topology; KV rides the pod network — disagg_net.py)
    "llama3-8b-disagg-xpod-v5e8": {
        "model": "meta-llama/Meta-Llama-3-8B-Instruct",
        "tpu_type": "v5litepod-4", "tpu_topology": "2x2",
        "machine_type": "ct5lp-hightpu-4t", "num_nodes": 2,
        "tensor_parallel": 4,
        "disaggregated": True, "disagg_cross_pod": True,
        "prefill_replicas": 1, "decode_replicas": 1,
    },
    # pipeline-parallel serving on a v5e-4: 8B bf16 weights (~16 GB)
    # exceed one chip's HBM; four stages hold ~4 GB of layers + their KV
    # slice each (parallel/pipeline.py — the footprint-scaling path,
    # without quantizing)
    "llama3-8b-pp4-v5e4": {
        "model": "meta-llama/Meta-Llama-3-8B-Instruct",
        "tpu_type": "v5litepod-4", "tpu_topology": "2x2",
        "machine_type": "ct5lp-hightpu-4t",
        "tensor_parallel": 1, "pipeline_parallel": 4,
        "storage_size": "100Gi",
    },
    # harness-friendly CPU smoke path (BASELINE "CPU smoke" config)
    "cpu-smoke": {
        "provider": "local", "model": "tiny-qwen3",
        "tensor_parallel": 1, "replicas": 1,
    },
}


def apply_preset(data: dict, preset: str) -> dict:
    """Overlay a named preset under explicit YAML/env/override values."""
    if preset not in PRESETS:
        raise ValueError(
            f"unknown preset {preset!r}; available: {sorted(PRESETS)}")
    merged = dict(PRESETS[preset])
    merged.update(data)
    return merged
