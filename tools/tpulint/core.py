"""tpulint core: findings, suppressions, config, and the pass runner.

Passes are modules exposing ``NAME`` (pass id), ``TAG`` (suppression tag,
e.g. ``sync-ok``) and ``run(files, config) -> list[Finding]`` where
``files`` maps repo-relative posix paths to ``(source, ast.Module)``.
Cross-file checks (metrics consistency, thread roots) get the whole map.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import hashlib
import os
import re
from typing import Optional

# The fault-site registry shared with the engine: one source of truth,
# so a site renamed in runtime/faults.py breaks the lint fixture in the
# same commit.
from tpuserve.runtime.faults import SITES as FAULT_SITES  # noqa: F401

_SUPPRESS_RE = re.compile(
    r"#\s*tpulint:\s*([a-z][a-z0-9-]*-ok)\s*(?:\(([^)]*)\))?")


@dataclasses.dataclass
class Finding:
    file: str                  # repo-relative posix path
    line: int
    rule: str                  # e.g. "host-sync-in-jit"
    message: str
    pass_name: str             # owning pass id ("host-sync", ...)
    severity: str = "error"    # "error" | "warning"

    def as_dict(self) -> dict:
        out = dataclasses.asdict(self)
        # machine-consumer conveniences (tools/ scripts, pre-commit
        # filters): the owning pass under its CLI name, and whether a
        # per-line tag can silence this finding at all (core findings —
        # syntax errors, suppression hygiene — cannot be suppressed,
        # and suppressions are Python comments, so findings anchored in
        # README/YAML/shell files have nowhere to carry a tag)
        out["pass"] = self.pass_name
        out["suppressible"] = (self.pass_name != "core"
                               and self.file.endswith(".py"))
        return out

    def render(self) -> str:
        return (f"{self.file}:{self.line}: [{self.pass_name}/{self.rule}] "
                f"{self.severity}: {self.message}")


@dataclasses.dataclass
class Suppression:
    file: str
    line: int
    tag: str                   # "sync-ok", "thread-ok", ...
    reason: str
    used: bool = False


DEFAULT_CONFIG: dict = {
    "passes": ["host-sync", "thread-ownership", "kv-leak", "pallas",
               "metrics", "protocol", "config-surface"],
    # suppression tags that may appear in the tree at all
    "suppression_allowlist": ["sync-ok", "thread-ok", "leak-ok",
                              "pallas-ok", "metric-ok", "proto-ok",
                              "config-ok"],
    "severity": {},            # pass id -> "error" | "warning"
    "host_sync": {
        # the pipelined dispatch path: methods where ANY host sync must be
        # an explicitly designated (sync-ok) point — this is the code that
        # owns the one-sync-per-window property
        "dispatch_paths": [
            "tpuserve/runtime/engine.py::Engine.step",
            "tpuserve/runtime/engine.py::Engine._step_inner",
            "tpuserve/runtime/engine.py::Engine._run_*",
            "tpuserve/runtime/engine.py::Engine._flush_*",
            "tpuserve/runtime/engine.py::Engine._exec_*",
            "tpuserve/runtime/engine.py::Engine._sample*",
            "tpuserve/runtime/engine.py::Engine._apply_*",
            "tpuserve/runtime/engine.py::Engine._draft_propose",
            "tpuserve/runtime/engine.py::Engine._append_and_emit",
            "tpuserve/runtime/engine.py::Engine._emit_one",
            "tpuserve/runtime/engine.py::Engine._emit_window_row",
            "tpuserve/runtime/engine.py::Engine._bm_*",
            "tpuserve/runtime/engine.py::Engine._record_logprobs",
        ],
        # replay-reachable files: the ONLY blessed time source here is
        # the injectable clock seam (runtime/clock.py) — a direct
        # time.monotonic would mix wall time into virtual-time replays
        "clock_paths": [
            "tpuserve/runtime/engine.py",
            "tpuserve/runtime/scheduler.py",
            "tpuserve/runtime/slo.py",
            "tpuserve/runtime/flight.py",
            "tpuserve/runtime/devprof.py",
            "tpuserve/runtime/request.py",
            "tpuserve/server/runner.py",
            "tpuserve/autoscale/*.py",
            # model pool: swap decisions happen on the engine loop and
            # must replay under VirtualClock like everything else there
            "tpuserve/modelpool/*.py",
            # SLO burn-rate engine: backtests under VirtualClock
            # (canary.py deliberately absent — HTTP probes are
            # wall-bound)
            "tpuserve/obs/objectives.py",
            "tpuserve/obs/burnrate.py",
            "tpuserve/obs/backtest.py",
        ],
    },
    "thread_ownership": {
        # thread entry points that ARE the engine loop (mutations fine)
        "loop_roots": [
            "tpuserve/server/runner.py::AsyncEngineRunner._loop",
        ],
        # per-class engine-loop-owned attributes; "engine" is always owned
        "owned_attrs": {
            "AsyncEngineRunner": ["engine", "_out_queues", "_req_started",
                                  "_last_token_time", "_salvage",
                                  "_singleton_faults"],
        },
        # methods on owned state that are safe from any thread
        "safe_methods": ["release_hangs", "get", "items", "keys", "values",
                         "empty", "qsize"],
        # native-handle attributes: calls through these cross the ctypes/
        # C-extension boundary and must be thread-ok-annotated from any
        # foreign thread (the C++ core races concurrent access)
        "native_attrs": ["_core"],
    },
    "kv_leak": {
        # substrings identifying a block-manager receiver
        "receivers": ["block_manager", "bm"],
        # self.<sink>[seq_id] = ... transfers ownership (abort_request's
        # orphan fallback frees via this record)
        "ownership_sinks": ["requests"],
    },
    "pallas": {
        "vmem_budget_mb": 16,      # ~VMEM/core on v5e (pallas guide)
    },
    "metrics": {
        "registry": "tpuserve/server/metrics.py",
        "readme": "README.md",
    },
    # P6 protocol consistency: the HTTP surface wiring the four
    # processes together.  Producer files HANDLE paths (compare
    # self.path); consumer files DIAL them (str-concat / f-string /
    # probe dicts).  ``endpoints`` pins the JSON contract per endpoint:
    # every key a consumer indexes must be written by that endpoint's
    # payload builders (and write-only keys outside ``operator_keys``
    # are dead-surface warnings).
    "protocol": {
        "producer_files": ["tpuserve/server/openai_api.py",
                           "tpuserve/server/gateway.py",
                           "tpuserve/autoscale/__main__.py"],
        "consumer_files": ["tpuserve/server/gateway.py",
                           "tpuserve/autoscale/signals.py",
                           "tpuserve/autoscale/reconciler.py",
                           "tpuserve/obs/canary.py",
                           "tpuserve/parallel/disagg_net.py",
                           "tpuserve/provision/manifests.py",
                           "tools/replay.py"],
        "header_files": ["tpuserve/server/openai_api.py",
                         "tpuserve/server/gateway.py",
                         "tpuserve/server/tracing.py",
                         "tpuserve/obs/canary.py"],
        # consumer/producer sources outside the default lint roots,
        # loaded from the working tree when not already being linted
        "extra_paths": ["tools/replay.py"],
        # non-X- headers the cross-process contract rides on
        "checked_headers": ["traceparent", "tracestate"],
        # served routes with no in-repo dialer BY DESIGN: the client
        # API surface (dialed by users/SDKs) and human/ops endpoints
        # (dashboards, jq, kubectl port-forward)
        "operator_endpoints": [
            "/v1/completions", "/v1/chat/completions", "/v1/embeddings",
            "/v1/models", "/v1/models/", "/tokenize", "/detokenize",
            "/debug/requests/", "/debug/profile", "/gateway/slo",
            "/decisions",
        ],
        # payload keys written for operators (jq / dashboards /
        # post-mortem readers), not for any in-repo consumer — exempt
        # from the write-only dead-surface warning
        "operator_keys": [
            # /debug/engine ring bookkeeping + per-request detail
            "events_recorded", "steps_recorded", "requests",
            "steps", "postmortems", "last_postmortem",
            # what the engine is: its sizes and the decode route it
            # observed with the route's two numbers (note_engine_facts)
            "engine",
            # SLI/controller scalars beyond what the autoscaler reads
            "n", "p50", "pressure",
            # burn-rate evaluator detail (objectives list, transition
            # log) — /gateway/slo consumes only "firing"
            "objectives", "burn", "transitions", "objective", "window",
            "state", "severity", "t", "burn_long", "burn_short",
            "long_s", "short_s",
            # /healthz degraded-poller scalars + per-tier KV residency
            # (brownout/cold-start ride here for pollers that skip the
            # full /debug/engine snapshot; hbm/host/spill are the
            # kv_tier_blocks breakdown)
            "status", "kv_tier_blocks", "brownout_level",
            "cold_start_s", "hbm", "host", "spill",
            # /gateway/status ops view beyond the reconciler's reads
            "backends", "affinity", "tenants", "breached",
            "consecutive_failures", "last", "ok", "latency_s", "detail",
            # device telemetry (runtime/devprof.py): the /debug/engine
            # "devprof" section + compile-cache stats are operator/jq
            # surface; the autoscaler reads control scalars, not these
            "devprof", "compile_caches",
            # what a start is made of (hostprof's start-up spans, the
            # compile ledger at the first token): operator/jq surface
            "startup", "phases", "compile",
            # model pool (tpuserve/modelpool): the /debug/engine
            # "modelpool" block is operator/jq surface; the gateway
            # consumes the /healthz catalog ("models"/"model_current"),
            # not this
            "modelpool",
        ],
        "endpoints": {
            "/debug/engine": {
                "producers": [
                    "tpuserve/runtime/flight.py::FlightRecorder"
                    ".engine_snapshot",
                    "tpuserve/runtime/flight.py::FlightRecorder"
                    ".sli_summary",
                    "tpuserve/runtime/slo.py::SloController.snapshot",
                    # the engine publishes the per-cycle control scalars
                    # as note_control KEYWORDS — renaming one here must
                    # break the stale signals.py reader below
                    "tpuserve/runtime/engine.py::call:note_control",
                    "tpuserve/server/openai_api.py::*"
                    "._debug_engine_payload",
                    "tpuserve/obs/burnrate.py::BurnRateEvaluator"
                    ".evaluate",
                ],
                "consumers": [
                    "tpuserve/autoscale/signals.py::_merge_engines",
                    "tpuserve/autoscale/signals.py::signals_from_debug",
                    "tpuserve/server/gateway.py::Gateway.slo_status",
                ],
            },
            "/healthz": {
                "producers": [
                    "tpuserve/server/openai_api.py::*._healthz_payload",
                    # the per-replica model catalog ("models" rows with
                    # name/tier warmth tags the gateway routes on)
                    "tpuserve/modelpool/pool.py::ModelPool"
                    ".catalog_status",
                ],
                "consumers": [
                    "tpuserve/server/gateway.py::Gateway"
                    ".probe_backends_once",
                ],
            },
            "/gateway/status": {
                "producers": [
                    "tpuserve/server/gateway.py::Gateway.status",
                    "tpuserve/obs/canary.py::CanaryProber.snapshot",
                ],
                "consumers": [
                    "tpuserve/autoscale/reconciler.py::KubePool"
                    "._pending_demand",
                ],
            },
        },
    },
    # P7 config-surface drift: TPUSERVE_* env vars, argparse flags,
    # DeployConfig fields and the README flag tables, checked both
    # directions (the P5 enforcement style applied to configuration).
    "config_surface": {
        "readme": "README.md",
        "deploy_config": "tpuserve/provision/config.py",
        "manifests": "tpuserve/provision/manifests.py",
        "provision_dir": "tpuserve/provision",
        "env_prefix": "TPUSERVE_",
        # env/flag read sites outside the default lint roots
        "extra_paths": ["tools"],
        # operator-facing entrypoints whose every flag must be in the
        # README flag tables (both directions; tools keep their own
        # --help as documentation)
        "argparse_files": ["tpuserve/server/openai_api.py",
                           "tpuserve/server/gateway.py",
                           "tpuserve/autoscale/__main__.py"],
        # debug-only vars: harness plumbing and tuning levers that are
        # deliberately NOT part of the deploy config or README surface.
        # The reason string is the documentation.
        "env_debug_only": {
            "TPUSERVE_HBM_BYTES": "test HBM budget override",
            "TPUSERVE_FSM_MAX_STATES": "grammar-compile guard rail",
            "TPUSERVE_FSM_MAX_WALK_CHARS": "grammar-compile guard rail",
            "TPUSERVE_FSM_JSON_DEPTH": "grammar-compile guard rail",
        },
        # operator-injected vars: documented in README but deliberately
        # not derived from a DeployConfig field (secrets, A/B levers the
        # operator sets per-pod, ring sizes)
        "env_operator": [
            "TPUSERVE_CANARY_TOKEN", "TPUSERVE_SLO_OBJECTIVES",
            "TPUSERVE_HOST_BATCHED", "TPUSERVE_STRICT_BLOCKS",
            "TPUSERVE_BLOCK_MANAGER", "TPUSERVE_FLIGHT_EVENTS",
            "TPUSERVE_FLIGHT_STEPS", "TPUSERVE_FSM_CACHE_DIR",
            # model-pool kill switch (the byte-identity A/B lever, like
            # TPUSERVE_KV_TIERS): operators set it per-pod, the deploy
            # layer turns the pool on via model_catalog instead
            "TPUSERVE_MODELPOOL",
        ],
        # vars read by shell entrypoints the AST can't see: var -> the
        # script that reads it.  The pass verifies the var still appears
        # in that file, so an entry can't outlive the read site.
        "env_shell": {
            "TPUSERVE_CONFIG": "deploy-tpu-cluster.sh",
        },
        # DeployConfig fields allowed to have no provision-layer read
        "deploy_field_allow": [],
    },
}


@dataclasses.dataclass
class Config:
    data: dict

    def passes(self) -> list[str]:
        return list(self.data.get("passes", DEFAULT_CONFIG["passes"]))

    def severity_for(self, pass_name: str) -> str:
        return self.data.get("severity", {}).get(pass_name, "error")

    def section(self, name: str) -> dict:
        base = dict(DEFAULT_CONFIG.get(name, {}))
        base.update(self.data.get(name, {}))
        return base

    def allowlist(self) -> list[str]:
        return list(self.data.get("suppression_allowlist",
                                  DEFAULT_CONFIG["suppression_allowlist"]))


def _load_toml(path: str) -> Optional[dict]:
    try:
        import tomllib as toml_mod          # py >= 3.11
    except ModuleNotFoundError:
        try:
            import tomli as toml_mod        # the backport this image ships
        except ModuleNotFoundError:
            return None
    with open(path, "rb") as f:
        return toml_mod.load(f)


def find_repo_root(start: str) -> str:
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    while True:
        if os.path.exists(os.path.join(cur, "pyproject.toml")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.path.abspath(start)
        cur = parent


def load_config(repo_root: str) -> Config:
    """[tool.tpulint] from pyproject.toml, defaults when absent (or when
    no TOML parser is available — the config is an overlay, never a
    requirement)."""
    data: dict = {}
    pyproject = os.path.join(repo_root, "pyproject.toml")
    if os.path.exists(pyproject):
        parsed = _load_toml(pyproject)
        if parsed:
            data = parsed.get("tool", {}).get("tpulint", {}) or {}
    merged = dict(DEFAULT_CONFIG)
    merged.update(data)
    return Config(merged)


def collect_files(paths: list[str], repo_root: str) -> dict:
    """{repo-relative posix path: (source, ast.Module)} for every .py file
    under ``paths``.  Unparseable files become a finding downstream (the
    runner reports them), not a crash."""
    out: dict = {}
    for p in paths:
        p = os.path.abspath(p)
        if os.path.isfile(p):
            files = [p]
        else:
            files = []
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git")]
                files += [os.path.join(dirpath, f) for f in filenames
                          if f.endswith(".py")]
        for f in sorted(files):
            rel = os.path.relpath(f, repo_root).replace(os.sep, "/")
            with open(f, "r", encoding="utf-8") as fh:
                out[rel] = fh.read()
    return out


# Single-parse AST cache, shared across passes, fixtures, and repeat
# run_lint invocations in one process (the tier-1 suite lints the full
# tree several times; with seven passes re-parsing would dominate lint
# wall time).  Keyed by content, so a fixture shadowing a real path can
# never collide with it, and trees are read-only by pass contract.
_AST_CACHE: dict = {}


def cached_parse(src: str) -> ast.Module:
    key = hashlib.sha256(src.encode("utf-8")).digest()
    tree = _AST_CACHE.get(key)
    if tree is None:
        tree = ast.parse(src)
        _AST_CACHE[key] = tree
    return tree


def parse_sources(sources: dict) -> tuple[dict, list[Finding]]:
    files: dict = {}
    errors: list[Finding] = []
    for rel, src in sources.items():
        try:
            files[rel] = (src, cached_parse(src))
        except SyntaxError as e:
            errors.append(Finding(
                file=rel, line=e.lineno or 1, rule="syntax-error",
                message=f"cannot parse: {e.msg}", pass_name="core"))
    return files, errors


def collect_suppressions(sources: dict) -> list[Suppression]:
    sups: list[Suppression] = []
    for rel, src in sources.items():
        for i, line in enumerate(src.splitlines(), start=1):
            m = _SUPPRESS_RE.search(line)
            if m:
                sups.append(Suppression(file=rel, line=i, tag=m.group(1),
                                        reason=(m.group(2) or "").strip()))
    return sups


def apply_suppressions(findings: list[Finding], sups: list[Suppression],
                       tag_for_pass: dict, allowlist: list[str],
                       active_tags: Optional[set] = None,
                       staleness_files: Optional[set] = None
                       ) -> list[Finding]:
    """Drop findings covered by a matching suppression on the same line or
    the line directly above; emit findings for malformed suppressions
    (missing reason, unknown tag, unused).

    ``active_tags``: tags whose owning pass actually ran this invocation.
    Staleness (unused-suppression) is only judged for those — a subset
    run (``--passes kv-leak``) must not condemn the sync-ok comments the
    skipped host-sync pass would have consumed.  None means all ran.

    ``staleness_files``: files whose suppressions may be judged stale.
    Files pulled in only because a finding anchored there (the P6/P7
    disk-loaded set) are excluded — judging them would make staleness
    appear and vanish with unrelated findings.  None means all."""
    by_loc: dict = {}
    for s in sups:
        by_loc.setdefault((s.file, s.tag), []).append(s)
    kept: list[Finding] = []
    for f in findings:
        tag = tag_for_pass.get(f.pass_name)
        hit = None
        for s in by_loc.get((f.file, tag), ()):
            if s.line in (f.line, f.line - 1) and s.reason:
                hit = s
                break
        if hit is not None:
            hit.used = True
        else:
            kept.append(f)
    for s in sups:
        if not s.reason:
            kept.append(Finding(
                file=s.file, line=s.line, rule="suppression-missing-reason",
                message=f"tpulint suppression '{s.tag}' has no reason "
                        "string — every suppression must explain itself: "
                        f"# tpulint: {s.tag}(why this is safe)",
                pass_name="core"))
        elif s.tag not in allowlist:
            kept.append(Finding(
                file=s.file, line=s.line, rule="suppression-not-allowed",
                message=f"suppression tag '{s.tag}' is not in "
                        "[tool.tpulint] suppression_allowlist",
                pass_name="core"))
        elif not s.used and (active_tags is None or s.tag in active_tags) \
                and (staleness_files is None or s.file in staleness_files):
            kept.append(Finding(
                file=s.file, line=s.line, rule="unused-suppression",
                message=f"suppression '{s.tag}' matches no finding — "
                        "stale suppressions hide future regressions; "
                        "remove it", pass_name="core"))
    return kept


def _pass_modules() -> dict:
    from tools.tpulint import (config_surface, host_sync, kv_leak,
                               metrics_consistency, pallas_contracts,
                               protocol_consistency, thread_ownership)
    mods = (host_sync, thread_ownership, kv_leak, pallas_contracts,
            metrics_consistency, protocol_consistency, config_surface)
    return {m.NAME: m for m in mods}


def run_lint_sources(sources: dict, config: Config,
                     repo_root: str = ".",
                     passes: Optional[list[str]] = None) -> list[Finding]:
    """Lint in-memory sources ({relpath: source}).  The entry point both
    the CLI and the fixture tests share, so fixtures exercise the exact
    shipping pipeline (suppression handling included)."""
    mods = _pass_modules()
    enabled = [p for p in (passes or config.passes()) if p in mods]
    files, findings = parse_sources(sources)
    for name in enabled:
        mod = mods[name]
        sev = config.severity_for(name)
        for f in mod.run(files, config, repo_root):
            # pass-emitted warnings (dead-surface findings) keep their
            # severity; the per-pass config level applies to errors
            if f.severity == "error":
                f.severity = sev
            findings.append(f)
    tag_for_pass = {name: mods[name].TAG for name in mods}
    # P6/P7 anchor findings in files they load from disk (tools/,
    # interface files outside the lint roots); their per-line
    # suppressions must work there too, so pull in the source of any
    # finding-bearing file the lint set doesn't already hold.  Python
    # only: suppressions are Python comments, and scanning a
    # finding-bearing README would mis-flag its documentation EXAMPLE
    # of the tag syntax as an unused suppression.
    sup_sources = dict(sources)
    for f in findings:
        if f.file not in sup_sources and f.file.endswith(".py"):
            path = os.path.join(repo_root, f.file)
            if os.path.isfile(path):
                with open(path, "r", encoding="utf-8") as fh:
                    sup_sources[f.file] = fh.read()
    sups = collect_suppressions(sup_sources)
    findings = apply_suppressions(findings, sups, tag_for_pass,
                                  config.allowlist(),
                                  active_tags={mods[p].TAG
                                               for p in enabled},
                                  staleness_files=set(sources))
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return findings


def run_lint(paths: list[str], config: Optional[Config] = None,
             repo_root: Optional[str] = None,
             passes: Optional[list[str]] = None) -> list[Finding]:
    repo_root = repo_root or find_repo_root(paths[0] if paths else ".")
    config = config or load_config(repo_root)
    sources = collect_files(paths, repo_root)
    return run_lint_sources(sources, config, repo_root, passes=passes)


# ---- shared AST helpers ------------------------------------------------

def dotted(node: ast.AST) -> str:
    """Best-effort dotted source form of an expression ('self.engine.x',
    'jax.device_get', 'getattr(self.engine, ...)' -> 'self.engine')."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Call):
        # getattr(x, "a") chains count as x for ownership purposes
        if isinstance(node.func, ast.Name) and node.func.id == "getattr" \
                and node.args:
            return dotted(node.args[0])
        return dotted(node.func)
    if isinstance(node, ast.Subscript):
        return dotted(node.value)
    return ""


def call_name(node: ast.Call) -> str:
    return dotted(node.func)


def qual_match(relpath: str, qualname: str, patterns: list[str]) -> bool:
    """'tpuserve/runtime/engine.py::Engine._run_*'-style matching."""
    for pat in patterns:
        if "::" in pat:
            fpat, qpat = pat.split("::", 1)
        else:
            fpat, qpat = "*", pat
        if fnmatch.fnmatch(relpath, fpat) and fnmatch.fnmatch(qualname, qpat):
            return True
    return False


def const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None
