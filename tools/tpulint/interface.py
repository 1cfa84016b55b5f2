"""Shared interface-extraction layer for the control-plane passes.

The fleet is four cooperating processes (server, gateway, autoscaler,
provisioner) wired together by hand-written strings: HTTP paths, JSON
field names, headers, ``TPUSERVE_*`` env vars, argparse flags,
``DeployConfig`` fields, and the env vars the manifests inject into
pods.  This module builds ONE AST model of that surface so the
protocol-consistency (P6) and config-surface (P7) passes — and their
fixtures in ``tests/test_tpulint.py`` — can never disagree about what
"the interface" means (the same single-fixture discipline P5 uses for
the metric registry).

Everything here is extraction only: no findings, no policy.  Sites keep
their file/line so the passes can anchor findings on the drifted string
itself rather than on a config entry.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import os
import re
from typing import Optional

from tools.tpulint.core import cached_parse, const_str, dotted, qual_match

#: URL-path shaped string: what a consumer dials / a handler compares
#: self.path against.  Deliberately tight — no spaces, no dots — so
#: filesystem fragments ("/file.json") and prose never count.
_PATH_RE = re.compile(r"^/[A-Za-z0-9_{}/-]*$")

#: dict keys whose constant string value is an HTTP path dialed by the
#: deploy layer (K8s http probes, prometheus scrape annotations)
_PROBE_PATH_KEYS = ("path", "prometheus.io/path")


@dataclasses.dataclass(frozen=True)
class Site:
    """One occurrence of an interface string in the tree."""
    file: str
    line: int
    name: str                  # path / env var / header / flag / field
    kind: str = ""             # routes: "exact" | "prefix"


# ---- source loading ------------------------------------------------------

def get_source(files: dict, repo_root: str, rel: str,
               errors: Optional[list] = None):
    """(source, tree) for ``rel``: the in-memory lint set first (so
    fixtures can shadow any real file), the working tree second, None
    when neither has it.  Disk parses go through the shared AST cache.
    An unparseable disk file appends a syntax-error Finding to
    ``errors`` (when given) instead of silently dropping the file —
    a broken consumer file must not quietly disable its protocol
    checks."""
    if rel in files:
        return files[rel]
    path = os.path.join(repo_root, rel)
    if not os.path.isfile(path):
        return None
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    try:
        return src, cached_parse(src)
    except SyntaxError as e:
        if errors is not None:
            from tools.tpulint.core import Finding
            errors.append(Finding(
                file=rel, line=e.lineno or 1, rule="syntax-error",
                message=f"cannot parse interface file: {e.msg}",
                pass_name="core"))
        return None


def expand_paths(repo_root: str, paths: list) -> list:
    """Config ``extra_paths`` entries -> repo-relative .py files (a
    directory entry walks, skipping __pycache__)."""
    out: list = []
    for p in paths:
        full = os.path.join(repo_root, p)
        if os.path.isfile(full):
            out.append(p)
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git")]
                for f in sorted(filenames):
                    if f.endswith(".py"):
                        rel = os.path.relpath(os.path.join(dirpath, f),
                                              repo_root)
                        out.append(rel.replace(os.sep, "/"))
    return out


# ---- function-scope walking ---------------------------------------------

def iter_functions(tree: ast.Module):
    """Yield ``(qualname, node)`` for every function/method, with class
    nesting dotted in ('Gateway.slo_status')."""

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                yield qual, child
                yield from walk(child, qual + ".")
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.")
            else:
                yield from walk(child, prefix)

    yield from walk(tree, "")


# (qualname, node) index per tree, cached: the payload-key extractors
# resolve one pattern at a time, and re-walking every module's AST per
# pattern would undo the single-parse cache's wall-time win.  Keyed by
# tree identity — cached_parse returns one tree object per content, and
# the stored reference keeps it alive, so ids can't be reused.
_FUNC_INDEX: dict = {}


def func_index(tree: ast.Module) -> list:
    got = _FUNC_INDEX.get(id(tree))
    if got is None or got[0] is not tree:
        got = (tree, list(iter_functions(tree)))
        _FUNC_INDEX[id(tree)] = got
    return got[1]


def module_str_consts(tree: ast.Module) -> dict:
    """Module-level ``NAME = "literal"`` bindings — lets extraction
    resolve header constants like ``CANARY_HEADER``."""
    out: dict = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            s = const_str(node.value)
            if s is not None:
                out[node.targets[0].id] = s
    return out


# ---- HTTP routes (producer side) ----------------------------------------

def routes_served(rel: str, tree: ast.Module) -> list:
    """Every path a handler file compares its request path against:
    ``self.path == "/x"`` / ``self.path in ("/x", "/y")`` (exact) and
    ``self.path.startswith("/x/")`` (prefix)."""
    out: list = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Compare) and len(node.ops) == 1:
            if not dotted(node.left).endswith(".path"):
                continue
            comp = node.comparators[0]
            if isinstance(node.ops[0], ast.Eq):
                s = const_str(comp)
                if s and _PATH_RE.match(s):
                    out.append(Site(rel, node.lineno, s, "exact"))
            elif isinstance(node.ops[0], ast.In) \
                    and isinstance(comp, (ast.Tuple, ast.List)):
                for elt in comp.elts:
                    s = const_str(elt)
                    if s and _PATH_RE.match(s):
                        out.append(Site(rel, node.lineno, s, "exact"))
        elif isinstance(node, ast.Call):
            d = dotted(node.func)
            if d.endswith(".path.startswith") and node.args:
                s = const_str(node.args[0])
                if s and _PATH_RE.match(s):
                    out.append(Site(rel, node.lineno, s, "prefix"))
    return out


# ---- HTTP paths dialed (consumer side) ----------------------------------

def paths_dialed(rel: str, tree: ast.Module) -> list:
    """Every URL path a consumer file builds a request to:

    - ``base + "/debug/engine"`` — string concat onto a non-constant
      (the urllib idiom every in-repo client uses),
    - ``f"{url}/internal/migrate"`` — f-string with a trailing path
      constant,
    - ``{"path": "/readyz"}`` / ``{"prometheus.io/path": "/metrics"}``
      — the deploy layer's probe and scrape-annotation dicts, which are
      consumers too: a probe dialing a dead route bricks the rollout.
    """
    out: list = []
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            s = const_str(node.right)
            if s and s != "/" and _PATH_RE.match(s) \
                    and not isinstance(node.left, ast.Constant):
                out.append(Site(rel, node.lineno, s))
        elif isinstance(node, ast.JoinedStr) and len(node.values) > 1:
            last = node.values[-1]
            s = const_str(last)
            if s and s != "/" and _PATH_RE.match(s):
                out.append(Site(rel, node.lineno, s))
        elif isinstance(node, ast.Dict):
            for k, v in zip(node.keys, node.values):
                if k is not None and const_str(k) in _PROBE_PATH_KEYS:
                    s = const_str(v)
                    if s and _PATH_RE.match(s):
                        out.append(Site(rel, node.lineno, s))
    return out


def route_serves(route: Site, path: str) -> bool:
    if route.kind == "prefix":
        return path.startswith(route.name)
    return path == route.name


# ---- JSON payload keys ---------------------------------------------------

def _func_nodes(files: dict, pattern: str) -> list:
    """Resolve a ``file::qualname`` glob over the source map into
    function nodes (the ``qual_match`` pattern language the host-sync
    pass already uses)."""
    out = []
    fpat = pattern.split("::", 1)[0] if "::" in pattern else "*"
    for rel, (_src, tree) in files.items():
        # cheap file prefilter before touching the function index; the
        # per-function match stays on core.qual_match so P6 patterns
        # can never diverge from P1's documented syntax
        if not fnmatch.fnmatch(rel, fpat):
            continue
        for qual, node in func_index(tree):
            if qual_match(rel, qual, [pattern]):
                out.append((rel, node))
    return out


def keys_written(files: dict, patterns: list) -> dict:
    """{key: first Site} for every JSON key the named payload builders
    write: dict-literal string keys and ``out["key"] = ...`` subscript
    stores.  A ``file::call:name`` pattern instead collects the keyword
    names of every call to ``name`` in that file — the shape of
    ``flight.note_control(waiting=..., running=...)``, whose keywords
    ARE the published scalar names."""
    out: dict = {}

    def note(rel, line, key):
        if isinstance(key, str):
            out.setdefault(key, Site(rel, line, key))

    for pattern in patterns:
        if "::call:" in pattern:
            fpat, call = pattern.split("::call:", 1)
            for rel, (_src, tree) in files.items():
                if not fnmatch.fnmatch(rel, fpat):
                    continue
                for node in ast.walk(tree):
                    if isinstance(node, ast.Call) \
                            and dotted(node.func).split(".")[-1] == call:
                        for kw in node.keywords:
                            if kw.arg:
                                note(rel, node.lineno, kw.arg)
            continue
        for rel, fn in _func_nodes(files, pattern):
            for node in ast.walk(fn):
                if isinstance(node, ast.Dict):
                    for k in node.keys:
                        if k is not None:
                            note(rel, node.lineno, const_str(k))
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        for sub in ast.walk(t):
                            if isinstance(sub, ast.Subscript):
                                note(rel, sub.lineno, const_str(sub.slice))
                elif isinstance(node, ast.Call) \
                        and dotted(node.func).endswith(".setdefault") \
                        and node.args:
                    note(rel, node.lineno, const_str(node.args[0]))
    return out


#: lookup receivers that are never a parsed payload — a consumer
#: function reading os.environ or request headers must not turn those
#: constant keys into payload-contract reads
_NON_PAYLOAD_RECV = ("environ", "headers")


def _payload_receiver(node: ast.AST) -> bool:
    recv = dotted(node).split(".")[-1]
    return recv not in _NON_PAYLOAD_RECV


def keys_read(files: dict, patterns: list) -> dict:
    """{key: first Site} for every constant JSON key the named consumer
    functions index out of a parsed payload: ``x.get("key")`` and
    ``x["key"]`` in Load context (environ/headers receivers excluded)."""
    out: dict = {}
    for pattern in patterns:
        for rel, fn in _func_nodes(files, pattern):
            for node in ast.walk(fn):
                key = None
                # dotted() collapses a chained get on a parenthesized
                # expression ("(x.get('a') or {}).get('b')") to bare
                # "get" — that read counts too
                if isinstance(node, ast.Call) \
                        and dotted(node.func).split(".")[-1] == "get" \
                        and node.args \
                        and isinstance(node.func, ast.Attribute) \
                        and _payload_receiver(node.func.value):
                    key = const_str(node.args[0])
                elif isinstance(node, ast.Subscript) \
                        and isinstance(node.ctx, ast.Load) \
                        and _payload_receiver(node.value):
                    key = const_str(node.slice)
                if key is not None:
                    out.setdefault(key, Site(rel, node.lineno, key))
    return out


# ---- headers -------------------------------------------------------------

def headers_in(rel: str, tree: ast.Module, interesting) -> tuple:
    """(reads, writes) of HTTP headers in one file, filtered through
    ``interesting(name)``.  Understands the gateway's forwarding idiom —
    a ``for h in ("X-A", "X-B"): fwd[h] = self.headers[h]`` loop counts
    every constant as both read and set — and resolves module-level
    name constants (``CANARY_HEADER``) used as dict keys."""
    consts = module_str_consts(tree)
    loop_vars: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.For) and isinstance(node.target, ast.Name) \
                and isinstance(node.iter, (ast.Tuple, ast.List)):
            vals = [const_str(e) for e in node.iter.elts]
            if vals and all(v is not None for v in vals):
                loop_vars.setdefault(node.target.id, []).extend(vals)

    def resolve(key_node) -> list:
        s = const_str(key_node)
        if s is not None:
            return [s]
        if isinstance(key_node, ast.Name):
            if key_node.id in consts:
                return [consts[key_node.id]]
            if key_node.id in loop_vars:
                return list(loop_vars[key_node.id])
        return []

    reads: list = []
    writes: list = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            if d.endswith("headers.get") and node.args:
                for name in resolve(node.args[0]):
                    if interesting(name):
                        reads.append(Site(rel, node.lineno, name))
            elif d.endswith(".send_header") and node.args:
                for name in resolve(node.args[0]):
                    if interesting(name):
                        writes.append(Site(rel, node.lineno, name))
        elif isinstance(node, ast.Subscript):
            names = [n for n in resolve(node.slice) if interesting(n)]
            if not names:
                continue
            if isinstance(node.ctx, ast.Load) \
                    and dotted(node.value).endswith("headers"):
                reads.extend(Site(rel, node.lineno, n) for n in names)
            elif isinstance(node.ctx, ast.Store):
                writes.extend(Site(rel, node.lineno, n) for n in names)
        elif isinstance(node, ast.Dict):
            for k in node.keys:
                if k is None:
                    continue
                for name in resolve(k):
                    if interesting(name):
                        writes.append(Site(rel, node.lineno, name))
    return reads, writes


# ---- env vars + argparse flags (one cached walk) -------------------------

# P7 scans EVERY source (tpuserve + tools); one walk per tree
# per process, cached like func_index, keeps the added passes out of the
# tier-1 wall-time budget.
_ENV_FLAG_CACHE: dict = {}


def _scan_env_and_flags(rel: str, tree: ast.Module, prefix: str,
                        helpers: tuple) -> tuple:
    envs: list = []
    flags: list = []

    def note_env(node, s):
        if s and s.startswith(prefix):
            envs.append(Site(rel, node.lineno, s))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "add_argument":
                for a in node.args:
                    s = const_str(a)
                    if s and s.startswith("--"):
                        flags.append(Site(rel, node.lineno, s))
                continue
            d = dotted(node.func)
            tail = d.split(".")[-1]
            if node.args and (
                    d.endswith("environ.get") or d.endswith("os.getenv")
                    or d == "getenv" or d.endswith("environ.setdefault")
                    or tail in helpers):
                note_env(node, const_str(node.args[0]))
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and dotted(node.value).endswith("environ"):
            note_env(node, const_str(node.slice))
    return envs, flags


def _env_and_flags(rel: str, tree: ast.Module, prefix: str,
                   helpers: tuple = ("env_flag", "_env_int")) -> tuple:
    key = (id(tree), rel, prefix, helpers)
    got = _ENV_FLAG_CACHE.get(key)
    if got is None or got[0] is not tree:
        got = (tree, _scan_env_and_flags(rel, tree, prefix, helpers))
        _ENV_FLAG_CACHE[key] = got
    return got[1]


def env_reads(rel: str, tree: ast.Module, prefix: str,
              helpers: tuple = ("env_flag", "_env_int")) -> list:
    """Every literal read of a ``prefix``-named env var: os.environ.get /
    os.getenv / os.environ[...] / os.environ.setdefault, plus the repo's
    shared boolean/int helpers (``env_flag`` et al), which are reads by
    construction."""
    return _env_and_flags(rel, tree, prefix, helpers)[0]


def argparse_flags(rel: str, tree: ast.Module) -> list:
    return _env_and_flags(rel, tree, "TPUSERVE_")[1]


# ---- DeployConfig / manifests -------------------------------------------

def deploy_config_fields(tree: ast.Module,
                         cls: str = "DeployConfig") -> dict:
    """{field: line} for the deploy dataclass's declared fields."""
    out: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls:
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) \
                        and isinstance(stmt.target, ast.Name):
                    out[stmt.target.id] = stmt.lineno
    return out


def manifest_env_names(tree: ast.Module, prefix: str) -> list:
    """Env vars the manifest builders inject into pod specs: every
    ``{"name": "TPUSERVE_X", "value"/"valueFrom": ...}`` dict literal."""
    out: list = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict):
            continue
        pairs = {const_str(k): v for k, v in zip(node.keys, node.values)
                 if k is not None}
        name = const_str(pairs["name"]) if "name" in pairs else None
        if name and name.startswith(prefix) \
                and ("value" in pairs or "valueFrom" in pairs):
            out.append(Site("", node.lineno, name))
    return out


def attr_reads(tree: ast.Module, receivers: tuple = ("cfg", "config")) -> set:
    """Attribute names read off a receiver that looks like a deploy
    config object ('cfg.model', 'self.config.namespace')."""
    out: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(node.ctx,
                                                          ast.Load):
            base = dotted(node.value).split(".")[-1]
            if base in receivers:
                out.add(node.attr)
    return out
