"""P1: host-sync lint.

Three sync-discipline rules plus the fault-site registry check:

- ``host-sync-in-jit``: a host synchronization (``jax.device_get``,
  ``np.asarray``/``np.array`` on a traced value, ``.item()``,
  ``block_until_ready``, ``float()/int()/bool()`` of a traced value, or
  implicit truthiness on a traced value) inside a jit-compiled function or
  a ``lax.scan``/``cond``/``while_loop``/``fori_loop``/``shard_map`` body.
  These either crash at trace time (truthiness) or silently force a
  device round-trip per call.
- ``sync-in-dispatch-path``: an explicit sync primitive inside the
  pipelined dispatch path (config ``host_sync.dispatch_paths`` — the
  engine methods that own the one-sync-per-S-tokens property behind the
  fused-window throughput).  The handful of designed sync points carry
  ``# tpulint: sync-ok(reason)``.
- ``monotonic-outside-clock-seam``: a direct ``time.monotonic``
  reference in a replay-reachable file (config
  ``host_sync.clock_paths``).  Those files must read time through the
  injectable clock seam (``runtime/clock.py`` — the engine's ``clock``
  attribute), or trace replay (``tpuserve/replay/``) silently mixes
  wall time into virtual-time policy state (queue-delay EWMAs,
  brownout hysteresis, deadlines).  Genuinely wall-bound sites
  (watchdog hang detection, client-side queue waits) carry a reasoned
  ``# tpulint: sync-ok(...)``.
- ``unknown-fault-site``: a literal site name passed to
  ``faults.check(...)`` that is not in ``tpuserve.runtime.faults.SITES``.
"""

from __future__ import annotations

import ast

from tools.tpulint.core import (FAULT_SITES, Config, Finding, call_name,
                                const_str, dotted, qual_match)

NAME = "host-sync"
TAG = "sync-ok"

#: rule texts for ``python -m tools.tpulint --explain CODE``
RULES = {
    "host-sync-in-jit": "jax.device_get / np.asarray / .item() / traced "
                        "truthiness inside a jit/scan body forces a "
                        "device round-trip per trace",
    "sync-in-dispatch-path": "ANY sync primitive inside the pipelined "
                             "dispatch path breaks one-sync-per-window",
    "monotonic-outside-clock-seam": "direct time.monotonic in a "
                                    "replay-reachable file bypasses the "
                                    "injectable clock seam "
                                    "(runtime/clock.py)",
    "unknown-fault-site": "a literal fault-site name not in "
                          "runtime/faults.SITES",
}

# explicit sync primitives (flagged in both traced and dispatch contexts)
_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
_SYNC_METHODS = {"item", "block_until_ready", "tolist"}
_NP_MATERIALIZE = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                   "np.copy"}
_SCALARIZE = {"float", "int"}

# RHS forms that yield STATIC Python values even when their operands are
# tracers/pytrees — assigning from them does not propagate taint:
# `guided = gstate is not None`, `quantized = bool(scales)` (tuple
# length), len()/isinstance()/hasattr() checks.
_STATIC_PRODUCERS = {"bool", "len", "isinstance", "hasattr", "callable"}


def _rhs_is_static(value: ast.AST) -> bool:
    if isinstance(value, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in value.ops):
        return True
    if isinstance(value, ast.Call) and isinstance(value.func, ast.Name) \
            and value.func.id in _STATIC_PRODUCERS:
        return True
    return False

_TRACED_WRAPPERS = {
    "jax.lax.scan": 0, "lax.scan": 0,
    "jax.lax.while_loop": (0, 1), "lax.while_loop": (0, 1),
    "jax.lax.fori_loop": 2, "lax.fori_loop": 2,
    "jax.lax.cond": (1, 2), "lax.cond": (1, 2),
    "jax.lax.switch": None, "lax.switch": None,   # None = all callable args
    "shard_map": 0, "jax.experimental.shard_map.shard_map": 0,
    "jax.vmap": 0, "vmap": 0, "jax.pmap": 0,
}


def _is_jit_decorator(dec: ast.AST) -> tuple[bool, set]:
    """(is_jit, static_argnames) for one decorator node."""
    name = dotted(dec if not isinstance(dec, ast.Call) else dec.func)
    statics: set = set()
    if name in ("jax.jit", "jit"):
        if isinstance(dec, ast.Call):
            statics = _static_argnames(dec)
        return True, statics
    if isinstance(dec, ast.Call) and name in ("partial",
                                              "functools.partial"):
        if dec.args and dotted(dec.args[0]) in ("jax.jit", "jit"):
            statics = _static_argnames(dec)
            return True, statics
    return False, statics


def _static_argnames(call: ast.Call) -> set:
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            v = kw.value
            names = set()
            if isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    s = const_str(el)
                    if s:
                        names.add(s)
            else:
                s = const_str(v)
                if s:
                    names.add(s)
            return names
    return set()


def _collect_traced(tree: ast.Module) -> dict:
    """{FunctionDef: static_argnames} for every function whose body is
    traced: jit-decorated, passed to a lax control-flow combinator /
    shard_map, or nested inside one of those."""
    by_name: dict = {}
    parents: dict = {}

    class Indexer(ast.NodeVisitor):
        def __init__(self):
            self.stack: list = []

        def _visit_fn(self, node):
            by_name.setdefault(node.name, node)
            if self.stack:
                parents[node] = self.stack[-1]
            self.stack.append(node)
            self.generic_visit(node)
            self.stack.pop()

        visit_FunctionDef = _visit_fn
        visit_AsyncFunctionDef = _visit_fn

    Indexer().visit(tree)

    traced: dict = {}

    def mark(fn, statics=frozenset()):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and fn not in traced:
            traced[fn] = set(statics)

    for fn in by_name.values():
        for dec in fn.decorator_list:
            is_jit, statics = _is_jit_decorator(dec)
            if is_jit:
                mark(fn, statics)

    lambdas: list = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name not in _TRACED_WRAPPERS:
            continue
        which = _TRACED_WRAPPERS[name]
        idxs = (range(len(node.args)) if which is None
                else which if isinstance(which, tuple) else (which,))
        for i in idxs:
            if i >= len(node.args):
                continue
            arg = node.args[i]
            if isinstance(arg, ast.Lambda):
                lambdas.append(arg)
            elif isinstance(arg, ast.Name) and arg.id in by_name:
                mark(by_name[arg.id])

    # nested defs inside traced functions run under the same trace
    changed = True
    while changed:
        changed = False
        for fn, parent in parents.items():
            if parent in traced and fn not in traced:
                mark(fn)
                changed = True
    return traced, lambdas


def _tainted_names(fn, statics: set) -> set:
    """Function params minus static argnames, closed over simple
    assignments — the values that are tracers inside the body."""
    args = fn.args
    names = {a.arg for a in (args.posonlyargs + args.args + args.kwonlyargs)}
    if args.vararg:
        names.add(args.vararg.arg)
    names -= statics
    names.discard("self")
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and not _rhs_is_static(node.value) \
                    and _mentions(node.value, names):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and n.id not in names:
                            names.add(n.id)
                            changed = True
    return names


def _mentions(node: ast.AST, names: set) -> bool:
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(node))


def _root_name(node: ast.AST) -> str:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else ""


def _scan_traced_body(rel, fn_name, body_nodes, tainted, findings):
    for node in body_nodes:
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in _SYNC_CALLS:
                findings.append(Finding(
                    file=rel, line=node.lineno, rule="host-sync-in-jit",
                    message=f"{name}() inside traced code ({fn_name}) "
                            "forces a device->host sync on every call",
                    pass_name=NAME))
            elif name in _NP_MATERIALIZE and node.args and _mentions(
                    node.args[0], tainted):
                findings.append(Finding(
                    file=rel, line=node.lineno, rule="host-sync-in-jit",
                    message=f"{name}(traced value) inside {fn_name} "
                            "materializes the array on host (implicit "
                            "sync); use jnp ops on device",
                    pass_name=NAME))
            elif name in _SCALARIZE and len(node.args) == 1 and _mentions(
                    node.args[0], tainted):
                findings.append(Finding(
                    file=rel, line=node.lineno, rule="host-sync-in-jit",
                    message=f"{name}(traced value) inside {fn_name} "
                            "forces concretization (TracerConversionError "
                            "at trace time, a sync under jit disable)",
                    pass_name=NAME))
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_METHODS \
                    and _root_name(node.func.value) in tainted:
                findings.append(Finding(
                    file=rel, line=node.lineno, rule="host-sync-in-jit",
                    message=f".{node.func.attr}() on a traced value "
                            f"inside {fn_name} is a host sync",
                    pass_name=NAME))
        elif isinstance(node, (ast.If, ast.While)):
            test = node.test
            if isinstance(test, (ast.Name, ast.Attribute)) and _mentions(
                    test, tainted):
                findings.append(Finding(
                    file=rel, line=node.lineno, rule="host-sync-in-jit",
                    message="implicit truthiness on a traced value inside "
                            f"{fn_name} — use jnp.where / lax.cond "
                            "(this raises TracerBoolConversionError on a "
                            "real tracer)",
                    pass_name=NAME))


def _check_dispatch_path(rel, fn, cls_name, findings):
    qual = f"{cls_name}.{fn.name}" if cls_name else fn.name
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        hit = None
        if name in _SYNC_CALLS:
            hit = f"{name}()"
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_METHODS:
            hit = f".{node.func.attr}()"
        elif name in _NP_MATERIALIZE and node.args and any(
                isinstance(a, ast.Call)
                and dotted(a.func).split(".")[-1].startswith("_exec_")
                for a in node.args):
            hit = f"{name}(device result)"
        if hit:
            findings.append(Finding(
                file=rel, line=node.lineno, rule="sync-in-dispatch-path",
                message=f"{hit} in pipelined dispatch path {qual} — the "
                        "fused-window pipeline allows ONE designated sync "
                        "per window; mark designed sync points with "
                        "# tpulint: sync-ok(reason)",
                pass_name=NAME))


def _check_clock_seam(rel, tree, findings):
    """Flag every direct ``time.monotonic`` reference (calls AND bare
    references like a dataclass ``default_factory=time.monotonic``) —
    the file is replay-reachable, so its time must come from the
    injectable clock seam (runtime/clock.py)."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and node.attr == "monotonic"
                and isinstance(node.value, ast.Name)
                and node.value.id == "time"):
            findings.append(Finding(
                file=rel, line=node.lineno,
                rule="monotonic-outside-clock-seam",
                message="direct time.monotonic in a replay-reachable "
                        "path — read the engine's injectable clock seam "
                        "instead (runtime/clock.py: self.clock"
                        ".monotonic()), or tag a genuinely wall-bound "
                        "site with # tpulint: sync-ok(reason)",
                pass_name=NAME))


def _check_fault_sites(rel, tree, findings):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr == "check"
                and "faults" in dotted(node.func.value)):
            continue
        if not node.args:
            continue
        site = const_str(node.args[0])
        if site is not None and site not in FAULT_SITES:
            findings.append(Finding(
                file=rel, line=node.lineno, rule="unknown-fault-site",
                message=f"fault site {site!r} is not in "
                        f"runtime.faults.SITES {tuple(FAULT_SITES)} — the "
                        "injection point would silently never fire",
                pass_name=NAME))


def run(files: dict, config: Config, repo_root: str) -> list:
    import fnmatch
    findings: list = []
    sec = config.section("host_sync")
    dispatch_patterns = sec.get("dispatch_paths", [])
    clock_paths = sec.get("clock_paths", [])
    for rel, (_src, tree) in files.items():
        if any(fnmatch.fnmatch(rel, pat) for pat in clock_paths):
            _check_clock_seam(rel, tree, findings)
        traced, lambdas = _collect_traced(tree)
        for fn, statics in traced.items():
            tainted = _tainted_names(fn, statics)
            body = [n for stmt in fn.body for n in ast.walk(stmt)]
            _scan_traced_body(rel, fn.name, body, tainted, findings)
        for lam in lambdas:
            tainted = {a.arg for a in lam.args.args}
            _scan_traced_body(rel, "<lambda>", list(ast.walk(lam.body)),
                              tainted, findings)
        # dispatch-path rule: class-qualified method matching
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and qual_match(rel, f"{node.name}.{item.name}",
                                           dispatch_patterns):
                        _check_dispatch_path(rel, item, node.name, findings)
            elif isinstance(node, ast.FunctionDef) and qual_match(
                    rel, node.name, dispatch_patterns):
                _check_dispatch_path(rel, node, "", findings)
        _check_fault_sites(rel, tree, findings)
    # a traced function flagged by BOTH rules would double-report; keep
    # the dispatch-path finding (it names the invariant being protected)
    seen = {}
    out = []
    for f in sorted(findings,
                    key=lambda f: (f.file, f.line,
                                   f.rule != "sync-in-dispatch-path")):
        key = (f.file, f.line)
        prev = seen.get(key, set())
        if f.rule in prev or ({f.rule} | prev) >= {"host-sync-in-jit",
                                                   "sync-in-dispatch-path"}:
            continue
        seen[key] = prev | {f.rule}
        out.append(f)
    return out
