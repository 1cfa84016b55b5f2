"""tpulint in tier-1: the shipped tree lints clean, and each of the seven
passes provably catches a seeded violation of its bug class — including a
re-introduction of the PR-3 watchdog cross-thread mutation, a seeded
KV-block leak, and (P6) a renamed ``/debug/engine`` control scalar read
by the REAL, now-stale ``autoscale/signals.py`` — the historical drift
class the protocol pass exists for.

Fixtures run through ``run_lint_sources`` — the exact pipeline the CLI
uses, suppression handling included — so a fixture that stops firing
means the shipping analyzer regressed, not a test double.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.tpulint import PASS_NAMES
from tools.tpulint.core import (FAULT_SITES, Config, DEFAULT_CONFIG,
                                find_repo_root, load_config, run_lint,
                                run_lint_sources)
from tools.tpulint.metrics_consistency import (documented_families,
                                               registry_from_source,
                                               table_families)

REPO = find_repo_root(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))) + "/tpuserve")


def lint_snippet(src, passes=None, path="tpuserve/fixture.py", extra=None):
    cfg_data = dict(DEFAULT_CONFIG)
    if extra:
        cfg_data = {**cfg_data, **extra}
    return run_lint_sources({path: textwrap.dedent(src)}, Config(cfg_data),
                            repo_root=REPO, passes=passes)


def rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------
# the shipped tree lints clean (the tier-1 gate)
# ---------------------------------------------------------------------

def test_tree_lints_clean():
    findings = run_lint([os.path.join(REPO, "tpuserve")],
                        config=load_config(REPO), repo_root=REPO)
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, "tpulint findings on the shipped tree:\n" + \
        "\n".join(f.render() for f in errors)


def test_cli_exits_zero_on_tree():
    r = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "tpuserve", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(r.stdout) == []


def test_cli_lists_passes():
    r = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--list-passes"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0
    assert set(r.stdout.split()) == set(PASS_NAMES)


# ---------------------------------------------------------------------
# P1 host-sync
# ---------------------------------------------------------------------

def test_p1_flags_device_get_in_jit_body():
    findings = lint_snippet("""
        import jax

        @jax.jit
        def step(tokens):
            host = jax.device_get(tokens)
            return host
    """, passes=["host-sync"])
    assert "host-sync-in-jit" in rules(findings)


def test_p1_flags_item_and_asarray_in_scan_body():
    findings = lint_snippet("""
        import jax
        import numpy as np

        def window(carry, xs):
            bad = np.asarray(carry)
            worse = carry.item()
            return carry, xs

        def run(carry0, xs):
            return jax.lax.scan(window, carry0, xs)
    """, passes=["host-sync"])
    assert rules(findings).count("host-sync-in-jit") == 2


def test_p1_flags_traced_truthiness_not_static_bools():
    findings = lint_snippet("""
        import jax

        @jax.jit
        def decode(tokens, gstate):
            guided = gstate is not None       # static: not flagged
            if guided:
                tokens = tokens + 1
            if tokens:                        # traced: flagged
                tokens = tokens * 2
            return tokens
    """, passes=["host-sync"])
    assert rules(findings) == ["host-sync-in-jit"]


def test_p1_respects_static_argnames():
    findings = lint_snippet("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("mode",))
        def decode(tokens, mode):
            if mode:                          # static argname: fine
                tokens = tokens + 1
            return tokens
    """, passes=["host-sync"])
    assert findings == []


def test_p1_flags_sync_in_dispatch_path_and_accepts_sync_ok():
    src = """
        import jax
        import numpy as np

        class Engine:
            def _run_decode_multi(self, p):
                toks = jax.device_get(p.toks)
                return toks
    """
    findings = lint_snippet(src, passes=["host-sync"],
                            path="tpuserve/runtime/engine.py")
    assert "sync-in-dispatch-path" in rules(findings)
    ok = src.replace(
        "toks = jax.device_get(p.toks)",
        "toks = jax.device_get(p.toks)  "
        "# tpulint: sync-ok(fixture designated sync)")
    findings = lint_snippet(ok, passes=["host-sync"],
                            path="tpuserve/runtime/engine.py")
    assert findings == []


def test_p1_unknown_fault_site():
    findings = lint_snippet("""
        class Engine:
            def _exec_prefill(self):
                self.faults.check("prefil_dispatch", ())
    """, passes=["host-sync"])
    assert "unknown-fault-site" in rules(findings)
    # and the registry names themselves pass
    findings = lint_snippet(f"""
        class Engine:
            def _exec_prefill(self):
                self.faults.check({FAULT_SITES[0]!r}, ())
    """, passes=["host-sync"])
    assert findings == []


def test_p1_clock_seam_rule_fires_in_replay_reachable_files():
    """ISSUE 11 satellite: direct time.monotonic (calls AND bare
    references like a default_factory) in a clock_paths file is an
    error — the injectable clock seam (runtime/clock.py) is the only
    blessed engine-side time source."""
    findings = lint_snippet("""
        import time

        class Engine:
            def _expire(self):
                now = time.monotonic()
                return now
    """, passes=["host-sync"], path="tpuserve/runtime/engine.py")
    assert "monotonic-outside-clock-seam" in rules(findings)
    # bare reference (the request.py default_factory shape) fires too
    findings = lint_snippet("""
        import dataclasses
        import time

        @dataclasses.dataclass
        class Request:
            arrival_time: float = dataclasses.field(
                default_factory=time.monotonic)
    """, passes=["host-sync"], path="tpuserve/runtime/request.py")
    assert "monotonic-outside-clock-seam" in rules(findings)


def test_p1_clock_seam_covers_autoscale():
    """ISSUE 12 satellite: the autoscaler's decision path runs under
    VirtualClock in the pool replay harness, so tpuserve/autoscale/ is
    clock_paths-covered — a policy reading the wall clock directly is
    an error; the injected clock is clean."""
    findings = lint_snippet("""
        import time

        class AutoscalePolicy:
            def decide(self, sig):
                return time.monotonic()
    """, passes=["host-sync"], path="tpuserve/autoscale/policy.py")
    assert "monotonic-outside-clock-seam" in rules(findings)
    assert lint_snippet("""
        class AutoscalePolicy:
            def decide(self, sig):
                return self.clock.monotonic()
    """, passes=["host-sync"], path="tpuserve/autoscale/pool.py") == []


def test_p1_clock_seam_covers_devprof():
    """ISSUE 16 satellite: runtime/devprof.py is clock_paths-covered —
    its attribution brackets must stay on perf_counter (replay-safe
    interval clock), so a direct time.monotonic is an error while the
    perf_counter hot path is clean."""
    findings = lint_snippet("""
        import time

        class DeviceProfiler:
            def bracket(self):
                return time.monotonic()
    """, passes=["host-sync"], path="tpuserve/runtime/devprof.py")
    assert "monotonic-outside-clock-seam" in rules(findings)
    assert lint_snippet("""
        import time

        class DeviceProfiler:
            def bracket(self):
                return time.perf_counter()
    """, passes=["host-sync"], path="tpuserve/runtime/devprof.py") == []


def test_p1_clock_seam_scope_and_sync_ok():
    """The rule stays scoped to clock_paths (gateway/tenants keep their
    real clocks) and accepts reasoned sync-ok tags on genuinely
    wall-bound sites; the seam itself is clean."""
    src = """
        import time

        class Gateway:
            def probe(self):
                return time.monotonic()
    """
    assert lint_snippet(src, passes=["host-sync"],
                        path="tpuserve/server/gateway.py") == []
    findings = lint_snippet("""
        import time

        class AsyncEngineRunner:
            def _watchdog_loop(self):
                # tpulint: sync-ok(watchdog measures REAL hang time)
                t = time.monotonic()
                return t - self._clock.monotonic()
    """, passes=["host-sync"], path="tpuserve/server/runner.py")
    assert findings == []


# ---------------------------------------------------------------------
# P2 thread-ownership — incl. the PR-3 watchdog regression, re-introduced
# ---------------------------------------------------------------------

PR3_WATCHDOG_REGRESSION = """
    import threading

    class AsyncEngineRunner:
        def __init__(self, engine):
            self.engine = engine
            self._thread = threading.Thread(target=self._loop)
            self._watchdog = threading.Thread(target=self._watchdog_loop)

        def _loop(self):
            self.engine.step()                 # loop thread: fine

        def _watchdog_loop(self):
            # the exact PR-3 bug: engine mutated under the loop's feet
            self.engine.abort_request("r1")
            self.engine.scheduler.running.clear()
"""


def test_p2_catches_reintroduced_pr3_watchdog_mutation():
    findings = lint_snippet(PR3_WATCHDOG_REGRESSION,
                            passes=["thread-ownership"],
                            path="tpuserve/server/runner.py")
    assert rules(findings).count("cross-thread-mutation") == 2
    lines = {f.line for f in findings}
    src = textwrap.dedent(PR3_WATCHDOG_REGRESSION).splitlines()
    assert any("abort_request" in src[l - 1] for l in lines)
    assert any("running.clear" in src[l - 1] for l in lines)


def test_p2_loop_thread_mutations_are_fine():
    findings = lint_snippet(PR3_WATCHDOG_REGRESSION.replace(
        "def _watchdog_loop(self):",
        "def _watchdog_loop(self):\n            return\n\n"
        "        def _unreachable(self):"),
        passes=["thread-ownership"], path="tpuserve/server/runner.py")
    assert findings == []


def test_p2_transitive_reachability_and_setattr():
    findings = lint_snippet("""
        import threading

        class Runner:
            def __init__(self, engine):
                self.engine = engine
                threading.Thread(target=self._health_loop).start()

            def _health_loop(self):
                self._helper()

            def _helper(self):
                setattr(self.engine.stats, "trips", 1)
                self.engine.requests.pop("x", None)
    """, passes=["thread-ownership"])
    got = rules(findings)
    assert "cross-thread-setattr" in got
    assert "cross-thread-mutation" in got


def test_p2_native_boundary_call_flagged():
    """A foreign thread reaching THROUGH the native handle (``._core``)
    on loop-owned state is a finding even when the method name is
    unknown to the mutator heuristics — ownership transfer across the
    ctypes boundary must be annotated, never silently exempt."""
    findings = lint_snippet("""
        import threading

        class Runner:
            def __init__(self, engine):
                self.engine = engine
                threading.Thread(target=self._health_loop).start()

            def _health_loop(self):
                # not in _MUTATOR_HINTS, still crosses the boundary
                self.engine.block_manager._core.lookup_prefix([1, 2])
                self.engine.block_manager._core.charge_decode(["a"], None)
    """, passes=["thread-ownership"])
    assert rules(findings).count("native-boundary-call") == 2


def test_p2_native_boundary_thread_ok_and_loop_root_clean():
    # annotated boundary crossing passes; loop-root crossings are free
    findings = lint_snippet("""
        import threading

        class Runner:
            def __init__(self, engine):
                self.engine = engine
                threading.Thread(target=self._wd).start()
                threading.Thread(target=self._loop).start()

            def _wd(self):
                # tpulint: thread-ok(fixture: engine loop parked, lock held)
                self.engine.block_manager._core.num_free_blocks()

            def _loop(self):
                self.engine.block_manager._core.charge_decode(["a"], None)
    """, passes=["thread-ownership"],
        path="tpuserve/server/runner.py",
        extra={"thread_ownership": {
            **DEFAULT_CONFIG["thread_ownership"],
            "loop_roots": ["tpuserve/server/runner.py::Runner._loop"]}})
    assert findings == []


def test_p2_batched_block_ops_are_mutator_hints():
    # the per-cycle batched ops mutate a whole cycle's allocation state
    # in one call: flagged as cross-thread mutations WITHOUT the native
    # handle in the chain (e.g. through the pure-Python manager)
    findings = lint_snippet("""
        import threading

        class Runner:
            def __init__(self, engine):
                self.engine = engine
                threading.Thread(target=self._wd).start()

            def _wd(self):
                self.engine.block_manager.advance_batch(["a"], 4)
    """, passes=["thread-ownership"])
    assert rules(findings) == ["cross-thread-mutation"]


def test_p2_thread_ok_suppression():
    findings = lint_snippet("""
        import threading

        class Runner:
            def __init__(self, engine):
                self.engine = engine
                threading.Thread(target=self._wd).start()

            def _wd(self):
                # tpulint: thread-ok(fixture: guarded by a lock)
                self.engine.requests.pop("x", None)
    """, passes=["thread-ownership"])
    assert findings == []


# ---------------------------------------------------------------------
# P3 kv-leak — incl. the seeded KV-block leak
# ---------------------------------------------------------------------

SEEDED_KV_LEAK = """
    class Engine:
        def adopt(self, request_id, ids, pages):
            alloc = self.block_manager.allocate(request_id, ids)
            self.kv_cache = self.scatter(pages, alloc.blocks)  # can raise
            self.requests[request_id] = ids
"""


def test_p3_catches_seeded_kv_block_leak():
    findings = lint_snippet(SEEDED_KV_LEAK, passes=["kv-leak"])
    assert rules(findings) == ["kv-alloc-leak-on-exception"]


def test_p3_try_finally_free_is_clean():
    findings = lint_snippet("""
        class Engine:
            def adopt(self, request_id, ids, pages):
                alloc = self.block_manager.allocate(request_id, ids)
                try:
                    self.kv_cache = self.scatter(pages, alloc.blocks)
                except Exception:
                    self.block_manager.free(request_id, cache_blocks=False)
                    raise
                self.requests[request_id] = ids
    """, passes=["kv-leak"])
    assert findings == []


def test_p3_never_released():
    findings = lint_snippet("""
        class Engine:
            def leak(self, rid, ids):
                self.block_manager.allocate(rid, ids)
    """, passes=["kv-leak"])
    assert rules(findings) == ["kv-alloc-never-released"]


def test_p3_owned_elsewhere_requests_are_engine_scope():
    # allocate(req.request_id): the request is registered with the
    # engine's salvage/abort recovery — no local obligation
    findings = lint_snippet("""
        class Engine:
            def _run_prefill(self, batch):
                for req in batch.requests:
                    self.block_manager.allocate(req.request_id, req.ids)
                return self._exec_prefill(batch)
    """, passes=["kv-leak"])
    assert findings == []


def test_p3_return_transfers_ownership():
    findings = lint_snippet("""
        def helper(bm, rid, ids):
            alloc = bm.allocate(rid, ids)
            return alloc
    """, passes=["kv-leak"])
    assert findings == []


# ---------------------------------------------------------------------
# P4 pallas contracts
# ---------------------------------------------------------------------

def test_p4_index_map_arity():
    findings = lint_snippet("""
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _k(bt_ref, q_ref, o_ref):
            o_ref[...] = q_ref[...]

        def call(q, bt):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda p: (p, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda p, bt: (p, 0)),
            )
            return pl.pallas_call(_k, grid_spec=grid_spec,
                                  out_shape=q)(bt, q)
    """, passes=["pallas"])
    # in_specs lambda takes 1 param; grid rank 1 + 1 scalar-prefetch = 2
    assert rules(findings).count("pallas-index-map-arity") == 1


def test_p4_kernel_arity():
    findings = lint_snippet("""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _k(q_ref, o_ref):            # missing the scalar-prefetch ref
            o_ref[...] = q_ref[...]

        def call(q, bt):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda p, bt: (p, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda p, bt: (p, 0)),
            )
            return pl.pallas_call(_k, grid_spec=grid_spec,
                                  out_shape=q)(bt, q)
    """, passes=["pallas"])
    assert "pallas-kernel-arity" in rules(findings)


def test_p4_call_arity():
    findings = lint_snippet("""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _k(bt_ref, q_ref, o_ref):
            o_ref[...] = q_ref[...]

        def call(q, bt, extra):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda p, bt: (p, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda p, bt: (p, 0)),
            )
            return pl.pallas_call(_k, grid_spec=grid_spec,
                                  out_shape=q)(bt, q, extra)
    """, passes=["pallas"])
    assert "pallas-call-arity" in rules(findings)


def test_p4_dtype_rules():
    findings = lint_snippet("""
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _decode_kernel(q_ref, k_ref, o_ref):
            k = dequantize_kv(k_ref[...], None, jnp.float32)
            sc = jax.lax.dot_general(q_ref[...].astype(jnp.float32), k,
                                     (((1,), (1,)), ((0,), (0,))))
            o_ref[...] = sc
    """, passes=["pallas"])
    got = rules(findings)
    assert "pallas-dot-accum" in got            # no preferred_element_type
    assert "pallas-upcast-before-dot" in got
    assert "pallas-dequant-dtype" in got


def test_p4_vmem_budget():
    findings = lint_snippet("""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _k(q_ref, o_ref, scr):
            o_ref[...] = q_ref[...]

        def call(q):
            return pl.pallas_call(
                _k,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                scratch_shapes=[pltpu.VMEM((2, 64, 512, 128), jnp.float32)],
                out_shape=q,
            )(q)
    """, passes=["pallas"])
    # 2*64*512*128*4 = 32 MiB > 16 MiB budget
    assert "pallas-vmem-budget" in rules(findings)


def test_p4_real_kernel_shapes_pass():
    # the shipped kernels (conditional in_specs/scratch, partial-wrapped
    # kernels, Name-assigned grids) must parse clean — regression-pinned
    # here so analyzer changes can't silently skip them
    ops = os.path.join(REPO, "tpuserve", "ops")
    findings = run_lint([ops], config=load_config(REPO), repo_root=REPO,
                        passes=["pallas"])
    assert findings == []


# ---------------------------------------------------------------------
# P5 metrics consistency + the shared registry fixture
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def metric_registry():
    """The shared fixture: P5's own parse of server/metrics.py, consumed
    by both the lint test and the doc-sync test below."""
    path = os.path.join(REPO, "tpuserve", "server", "metrics.py")
    with open(path) as f:
        return registry_from_source(f.read())


def test_p5_registry_parses_all_families(metric_registry):
    fams = {m.family for m in metric_registry}
    assert "vllm_request_total" in fams
    assert "tpuserve_requests_salvaged_total" in fams
    assert len(metric_registry) >= 30
    kinds = {m.kind for m in metric_registry}
    assert kinds == {"counter", "gauge", "histogram"}


def test_p5_flags_unused_and_undocumented_metric():
    reg = """
        from prometheus_client import Counter

        class ServerMetrics:
            def __init__(self):
                self.ghost = Counter("tpuserve_ghost_metric", "doc",
                                     registry=None)
    """
    findings = run_lint_sources(
        {"tpuserve/server/metrics.py": textwrap.dedent(reg)},
        Config(dict(DEFAULT_CONFIG)), repo_root=REPO, passes=["metrics"])
    got = rules(findings)
    assert "metric-never-updated" in got
    assert "metric-undocumented" in got


def test_p5_getattr_fed_metric_is_a_use():
    """A metric fed only via getattr(self.metrics, "attr") with a
    constant name is fed — it must not be flagged never-updated."""
    reg = """
        from prometheus_client import Counter

        class ServerMetrics:
            def __init__(self):
                self.spec_pauses = Counter(
                    "tpuserve_spec_adaptive_pauses_total", "doc",
                    registry=None)
    """
    feeder = """
        def publish(self):
            getattr(self.metrics, "spec_pauses").inc()
    """
    findings = run_lint_sources(
        {"tpuserve/server/metrics.py": textwrap.dedent(reg),
         "tpuserve/server/feeder.py": textwrap.dedent(feeder)},
        Config(dict(DEFAULT_CONFIG)), repo_root=REPO, passes=["metrics"])
    assert "metric-never-updated" not in rules(findings)


def test_p5_alert_drift_both_directions(tmp_path):
    """ISSUE 13 (P5 extended): an alert expr naming a ghost family is
    flagged, and an objectives-registry family no alert references is
    flagged in the other direction."""
    reg = """
        from prometheus_client import Counter

        class ServerMetrics:
            def __init__(self):
                self.shed = Counter("tpuserve_requests_shed", "d",
                                    registry=None)
    """
    feeder = """
        def run(self):
            self.metrics.shed.inc()
    """
    golden = tmp_path / "tests" / "golden"
    golden.mkdir(parents=True)
    (golden / "prometheus_rules.yaml").write_text(
        "spec:\n  groups:\n  - rules:\n"
        "    - expr: rate(tpuserve_ghost_series_total[5m]) > 1\n")
    findings = run_lint_sources(
        {"tpuserve/server/metrics.py": textwrap.dedent(reg),
         "tpuserve/server/feeder.py": textwrap.dedent(feeder)},
        Config(dict(DEFAULT_CONFIG)), repo_root=str(tmp_path),
        passes=["metrics"])
    got = rules(findings)
    # direction 1: the fake alerts file watches a ghost series
    assert "alert-unknown-metric" in got
    # direction 2: the real objectives registry's families (ttft
    # histograms, availability counters) appear in no alert expr
    assert "objective-unalerted" in got
    # no alerts file at all = nothing to check (fixture repos)
    clean = run_lint_sources(
        {"tpuserve/server/metrics.py": textwrap.dedent(reg),
         "tpuserve/server/feeder.py": textwrap.dedent(feeder)},
        Config(dict(DEFAULT_CONFIG)),
        repo_root=str(tmp_path / "elsewhere"), passes=["metrics"])
    assert "alert-unknown-metric" not in rules(clean)
    assert "objective-unalerted" not in rules(clean)


def test_p5_alert_families_normalises_series_suffixes():
    from tools.tpulint.metrics_consistency import alert_families
    fams = alert_families(
        "sum(rate(tpuserve_ttft_seconds_bucket{le=\"0.5\"}[1h])) / "
        "sum(rate(tpuserve_ttft_seconds_count[1h])) and "
        "vllm_request_total")
    assert fams == {"tpuserve_ttft_seconds", "vllm_request_total"}


def test_default_config_tracks_pyproject():
    """core.DEFAULT_CONFIG (fixture/no-pyproject fallback) must not
    drift WEAKER than the shipped [tool.tpulint] block: a dispatch path
    listed only in pyproject would silently go unchecked by any
    DEFAULT_CONFIG consumer."""
    cfg = load_config(REPO).data
    assert set(cfg["passes"]) == set(DEFAULT_CONFIG["passes"])
    assert set(cfg["suppression_allowlist"]) == \
        set(DEFAULT_CONFIG["suppression_allowlist"])
    assert set(cfg["host_sync"]["dispatch_paths"]) <= \
        set(DEFAULT_CONFIG["host_sync"]["dispatch_paths"])


def test_p5_counter_total_suffix_normalisation():
    m = registry_from_source(textwrap.dedent("""
        from prometheus_client import Counter, Gauge

        class ServerMetrics:
            def __init__(self):
                self.a = Counter("tpuserve_things", "d", registry=None)
                self.b = Counter("tpuserve_done_total", "d", registry=None)
                self.c = Gauge("tpuserve_level", "d", registry=None)
    """))
    assert [x.exported for x in m] == [
        "tpuserve_things_total", "tpuserve_done_total", "tpuserve_level"]


def test_readme_and_registry_cannot_drift(metric_registry):
    """The doc-sync satellite: every registered family is documented in
    README.md and every family named in a README table exists — consuming
    the same fixture as P5, so 'registry' can't mean two things."""
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    documented = documented_families(readme)
    for m in metric_registry:
        assert m.exported in documented or m.family in documented, \
            f"{m.exported} registered but not documented in README.md"
    real = {m.exported for m in metric_registry} | {
        m.family for m in metric_registry}
    for fam in table_families(readme):
        assert fam in real, f"README documents nonexistent metric {fam}"


# ---------------------------------------------------------------------
# suppression discipline
# ---------------------------------------------------------------------

def test_suppression_without_reason_is_an_error():
    findings = lint_snippet("""
        import jax

        @jax.jit
        def step(tokens):
            return jax.device_get(tokens)  # tpulint: sync-ok
    """, passes=["host-sync"])
    got = rules(findings)
    assert "suppression-missing-reason" in got
    assert "host-sync-in-jit" in got      # reasonless tag suppresses nothing


def test_unused_suppression_is_an_error():
    findings = lint_snippet("""
        x = 1  # tpulint: sync-ok(nothing here needs suppressing)
    """, passes=["host-sync"])
    assert rules(findings) == ["unused-suppression"]


def test_subset_run_skips_other_passes_suppressions():
    """--passes kv-leak must not condemn sync-ok comments the skipped
    host-sync pass would have consumed (they are unused only because
    their owner never ran)."""
    findings = lint_snippet("""
        import jax

        @jax.jit
        def step(tokens):
            # tpulint: sync-ok(designated sync point)
            return jax.device_get(tokens)
    """, passes=["kv-leak"])
    assert rules(findings) == []
    # but a malformed or off-allowlist tag is still an error in any run
    findings = lint_snippet("""
        x = 1  # tpulint: sync-ok
        y = 2  # tpulint: yolo-ok(fake)
    """, passes=["kv-leak"])
    assert sorted(rules(findings)) == ["suppression-missing-reason",
                                       "suppression-not-allowed"]


def test_cli_subset_run_exits_zero_on_tree():
    """The confirmed regression: a --passes subset over engine.py used to
    report every other pass's suppression as stale."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--passes", "kv-leak",
         "tpuserve/runtime"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr


def test_off_allowlist_suppression_is_an_error():
    findings = lint_snippet("""
        x = 1  # tpulint: yolo-ok(not a real tag)
    """, passes=["host-sync"])
    assert rules(findings) == ["suppression-not-allowed"]


def test_fault_site_registry_matches_engine():
    # the registry tpulint checks IS the one the engine parses specs with
    from tpuserve.runtime.faults import SITES
    assert tuple(FAULT_SITES) == tuple(SITES)


# ---------------------------------------------------------------------
# P6 protocol consistency — incl. the historical /debug/engine drift
# ---------------------------------------------------------------------

# a minimal /debug/engine producer half: the snapshot builder plus the
# engine's per-cycle note_control publication (whose KEYWORDS are the
# published control-scalar names)
P6_PRODUCER = """
    class FlightRecorder:
        def engine_snapshot(self):
            return {"engines": [], "sli": {},
                    "control": dict(self._control),
                    "cold_start_s": None,
                    "queue_delay_ewma": {}}

    class Engine:
        def _publish(self):
            self.flight.note_control(
                {SCALAR}=self._slo.level,
                waiting=self.scheduler.num_waiting,
                running=len(self.scheduler.running))
"""

P6_FIXTURE_ENDPOINTS = {
    "producer_files": [], "consumer_files": [], "header_files": [],
    "extra_paths": [],
    "endpoints": {"/debug/engine": {
        "producers": [
            "tpuserve/runtime/flight.py::FlightRecorder.engine_snapshot",
            "tpuserve/runtime/engine.py::call:note_control"],
        "consumers": [
            "tpuserve/autoscale/signals.py::_merge_engines",
            "tpuserve/autoscale/signals.py::signals_from_debug"],
    }},
}


def _p6_lint_with_real_signals(scalar: str):
    """Lint a fixture producer publishing ``scalar`` against the REAL
    autoscale/signals.py reader — the shipping consumer goes stale the
    moment the engine renames a control scalar."""
    with open(os.path.join(REPO, "tpuserve", "autoscale",
                           "signals.py")) as f:
        signals_src = f.read()
    producer = textwrap.dedent(P6_PRODUCER).replace("{SCALAR}", scalar)
    return run_lint_sources(
        {"tpuserve/runtime/flight.py": producer,
         "tpuserve/runtime/engine.py": producer,
         "tpuserve/autoscale/signals.py": signals_src},
        Config({**DEFAULT_CONFIG, "protocol": P6_FIXTURE_ENDPOINTS}),
        repo_root=REPO, passes=["protocol"])


def test_p6_catches_renamed_control_scalar_stale_signals_reader():
    """The re-introduced historical drift: the engine renames the
    brownout control scalar, the real signals.py reader still indexes
    the old name — json-key-unproduced on the stale read, and the new
    name surfaces as a write-only dead key."""
    findings = _p6_lint_with_real_signals("brownout_lvl")
    got = rules(findings)
    assert "json-key-unproduced" in got
    unproduced = [f for f in findings if f.rule == "json-key-unproduced"]
    assert {f.file for f in unproduced} == \
        {"tpuserve/autoscale/signals.py"}
    assert any("brownout_level" in f.message for f in unproduced)
    dead = [f for f in findings if f.rule == "json-key-dead"]
    assert any("brownout_lvl" in f.message for f in dead)
    assert all(f.severity == "warning" for f in dead)


def test_p6_matching_control_scalar_is_clean():
    findings = _p6_lint_with_real_signals("brownout_level")
    assert [f for f in findings if f.severity == "error"] == []


def test_p6_endpoint_unserved_and_dead_surface():
    producer = """
        class Handler:
            def do_GET(self):
                if self.path == "/metrics":
                    self._metrics()
                elif self.path == "/debug/extra":
                    self._extra()
    """
    consumer = """
        import urllib.request

        def scrape(base):
            with urllib.request.urlopen(base + "/debug/engine") as r:
                return r.read()
    """
    spec = {**P6_FIXTURE_ENDPOINTS,
            "producer_files": ["tpuserve/server/openai_api.py"],
            "consumer_files": ["tpuserve/autoscale/signals.py"],
            "endpoints": {}}
    findings = run_lint_sources(
        {"tpuserve/server/openai_api.py": textwrap.dedent(producer),
         "tpuserve/autoscale/signals.py": textwrap.dedent(consumer)},
        Config({**DEFAULT_CONFIG, "protocol": spec}),
        repo_root=REPO, passes=["protocol"])
    got = rules(findings)
    # /debug/engine dialed but only /metrics + /debug/extra served
    assert "endpoint-unserved" in got
    # /debug/extra served, never dialed, not operator surface
    dead = [f for f in findings if f.rule == "endpoint-dead"]
    assert any("/debug/extra" in f.message for f in dead)
    assert all(f.severity == "warning" for f in dead)
    # /metrics is dialed by the real deploy-layer... not here: also dead
    # but for the K8s scrape-annotation reason it's exercised on the
    # real tree (tree-clean test); this fixture only pins the warning


def test_p6_proto_ok_suppression_and_prefix_routes():
    producer = """
        class Handler:
            def do_GET(self):
                if self.path.startswith("/debug/requests/"):
                    self._req()
    """
    consumer = """
        import urllib.request

        def scrape(base, rid):
            url = base + "/debug/requests/" + rid      # prefix-served
            # tpulint: proto-ok(served by the out-of-repo peer)
            peer = base + "/peer-only/endpoint"
            return url, peer
    """
    spec = {**P6_FIXTURE_ENDPOINTS,
            "producer_files": ["tpuserve/server/openai_api.py"],
            "consumer_files": ["tpuserve/autoscale/signals.py"],
            "endpoints": {}}
    findings = run_lint_sources(
        {"tpuserve/server/openai_api.py": textwrap.dedent(producer),
         "tpuserve/autoscale/signals.py": textwrap.dedent(consumer)},
        Config({**DEFAULT_CONFIG, "protocol": spec}),
        repo_root=REPO, passes=["protocol"])
    # the prefix route serves the first dial; the peer-only dial is
    # suppressed with a reasoned proto-ok — nothing is left
    assert [f for f in findings if f.severity == "error"] == []


def test_p6_header_consistency_both_directions():
    reader = """
        class Handler:
            def do_POST(self):
                ghost = self.headers.get("X-Ghost-Header")
                canary = self.headers.get("X-Probe")
    """
    writer = """
        import urllib.request

        def probe(url):
            return urllib.request.Request(url, headers={
                "X-Probe": "1", "X-Write-Only": "1"})
    """
    spec = {**P6_FIXTURE_ENDPOINTS, "endpoints": {},
            "header_files": ["tpuserve/server/openai_api.py",
                             "tpuserve/obs/canary.py"]}
    findings = run_lint_sources(
        {"tpuserve/server/openai_api.py": textwrap.dedent(reader),
         "tpuserve/obs/canary.py": textwrap.dedent(writer)},
        Config({**DEFAULT_CONFIG, "protocol": spec}),
        repo_root=REPO, passes=["protocol"])
    unset = [f for f in findings if f.rule == "header-unset"]
    assert [f.severity for f in unset] == ["error"]
    assert "X-Ghost-Header" in unset[0].message
    unread = [f for f in findings if f.rule == "header-unread"]
    assert any("X-Write-Only" in f.message for f in unread)
    assert all(f.severity == "warning" for f in unread)


def test_p6_gateway_forward_loop_counts_as_read_and_set():
    """The gateway's ``for h in (...): fwd[h] = self.headers[h]``
    forwarding idiom must register every constant as both a read and a
    set — otherwise the real tree could never lint clean."""
    from tools.tpulint.interface import headers_in
    import ast as _ast
    src = textwrap.dedent("""
        def relay(self):
            fwd = {}
            for h in ("X-SLO-Class", "traceparent"):
                if self.headers.get(h):
                    fwd[h] = self.headers[h]
    """)
    reads, writes = headers_in(
        "f.py", _ast.parse(src),
        lambda n: n.startswith("X-") or n == "traceparent")
    assert {s.name for s in reads} == {"X-SLO-Class", "traceparent"}
    assert {s.name for s in writes} == {"X-SLO-Class", "traceparent"}


# ---------------------------------------------------------------------
# P7 config-surface drift
# ---------------------------------------------------------------------

#: fixture isolation for P7: no on-disk extra sources, and no real
#: README (whose tables would be judged against the fixture's empty
#: flag universe).  Fixtures that WANT the README override readme back.
P7_NO_EXTRAS = {"extra_paths": [], "readme": "_no_readme_.md"}


def test_p7_ghost_env_var_is_unreachable_and_undocumented():
    findings = lint_snippet("""
        import os

        KNOB = os.environ.get("TPUSERVE_GHOST_KNOB", "0")
    """, passes=["config-surface"],
        extra={"config_surface": {**P7_NO_EXTRAS, "readme": "README.md"}})
    got = rules(findings)
    # no DeployConfig field / manifest env reaches it, and README never
    # mentions it — both directions fire on the same read site
    assert "env-var-unreachable" in got
    assert "env-var-undocumented" in got


def test_p7_debug_only_registry_exempts_with_reason():
    findings = lint_snippet("""
        import os

        KNOB = os.environ.get("TPUSERVE_GHOST_KNOB", "0")
    """, passes=["config-surface"],
        extra={"config_surface": {
            **P7_NO_EXTRAS,
            "env_debug_only": {
                **DEFAULT_CONFIG["config_surface"]["env_debug_only"],
                "TPUSERVE_GHOST_KNOB": "fixture-only knob"}}})
    assert findings == []


def test_p7_config_ok_suppression():
    findings = lint_snippet("""
        import os

        # tpulint: config-ok(fixture: reachability demoed elsewhere)
        KNOB = os.environ.get("TPUSERVE_GHOST_KNOB", "0")
    """, passes=["config-surface"],
        extra={"config_surface": P7_NO_EXTRAS})
    assert findings == []


def test_p7_readme_doc_drift_both_kinds(tmp_path):
    """A README table row naming a removed env var or flag is drift —
    the P5 enforcement style applied to the config surface."""
    (tmp_path / "README.md").write_text(
        "| Key | Default |\n|---|---|\n"
        "| `TPUSERVE_REMOVED_KNOB` | gone |\n"
        "| `--removed-flag` | gone |\n")
    findings = run_lint_sources(
        {"tpuserve/x.py": "import os\n"},
        Config(dict(DEFAULT_CONFIG)), repo_root=str(tmp_path),
        passes=["config-surface"])
    got = rules(findings)
    assert "env-var-doc-drift" in got
    assert "flag-doc-drift" in got
    # README-anchored findings can't carry a Python suppression comment
    # — --json must not advertise one
    assert all(not f.as_dict()["suppressible"] for f in findings
               if f.file.endswith(".md"))


def test_p7_deploy_field_unused():
    config_py = """
        import dataclasses

        @dataclasses.dataclass
        class DeployConfig:
            namespace: str = "tpu-serve"
            ghost_field_nobody_reads: int = 0
    """
    manifests_py = """
        def build(cfg):
            return {"metadata": {"namespace": cfg.namespace}}
    """
    findings = run_lint_sources(
        {"tpuserve/provision/config.py": textwrap.dedent(config_py),
         "tpuserve/provision/manifests.py": textwrap.dedent(manifests_py)},
        Config(dict(DEFAULT_CONFIG)), repo_root=REPO,
        passes=["config-surface"])
    unused = [f for f in findings if f.rule == "deploy-field-unused"]
    assert len(unused) == 1
    assert "ghost_field_nobody_reads" in unused[0].message
    assert unused[0].file == "tpuserve/provision/config.py"


def test_p7_env_shell_registry_staleness():
    findings = lint_snippet("x = 1\n", passes=["config-surface"],
                            extra={"config_surface": {
                                **P7_NO_EXTRAS,
                                "env_shell": {"TPUSERVE_NOT_IN_SCRIPT":
                                              "tools/tpu_watch.sh"}}})
    assert rules(findings) == ["env-shell-stale"]


def test_p7_shipping_slo_burn_is_reachable():
    """The drift P7 found on landing, pinned fixed: TPUSERVE_SLO_BURN
    is now backed by DeployConfig.slo_burn and the manifests emit it."""
    import dataclasses as _dc
    from tpuserve.provision.config import DeployConfig
    from tpuserve.provision.manifests import _engine_container
    assert any(f.name == "slo_burn" for f in _dc.fields(DeployConfig))
    cfg = DeployConfig(provider="local", slo_burn=False)
    env = {e["name"]: e.get("value")
           for e in _engine_container(cfg)["env"]}
    assert env.get("TPUSERVE_SLO_BURN") == "0"
    cfg_on = DeployConfig(provider="local")
    env_on = {e["name"] for e in _engine_container(cfg_on)["env"]}
    assert "TPUSERVE_SLO_BURN" not in env_on


def test_p5_devprof_families_registered_and_documented(metric_registry):
    """ISSUE 16 (P5 both directions): the device-telemetry families are
    in the parsed registry with the right kinds AND in README's metric
    table under their exported (_total-suffixed) names."""
    fams = {m.family: m.kind for m in metric_registry}
    assert fams.get("tpuserve_hbm_bytes") == "gauge"
    assert fams.get("tpuserve_hbm_headroom_bytes") == "gauge"
    assert fams.get("tpuserve_device_seconds") == "counter"
    assert fams.get("tpuserve_executable_compiles") == "counter"
    assert fams.get("tpuserve_executables_retained") == "gauge"
    assert fams.get("tpuserve_profile_captures") == "counter"
    with open(os.path.join(REPO, "README.md")) as f:
        documented = documented_families(f.read())
    exported = {m.exported for m in metric_registry
                if m.family.startswith(("tpuserve_hbm", "tpuserve_device",
                                        "tpuserve_exec",
                                        "tpuserve_profile"))}
    assert exported <= documented, exported - documented


# ---------------------------------------------------------------------
# CLI surface: --explain, --json fields, and the shared AST cache
# ---------------------------------------------------------------------

def test_cli_explain_rule_and_pass(capsys):
    # in-process through the real CLI entry (subprocess start-up would
    # re-pay interpreter+import cost three times for the same coverage)
    from tools.tpulint.__main__ import main as cli_main
    for code, want in (("json-key-unproduced", "proto-ok"),
                       ("config-surface", "config-ok")):
        assert cli_main(["--explain", code]) == 0
        assert want in capsys.readouterr().out   # suppression syntax
    assert cli_main(["--explain", "bogus"]) == 2
    assert "unknown pass or rule" in capsys.readouterr().err


def test_json_findings_carry_pass_and_suppressible():
    findings = lint_snippet("""
        import os

        KNOB = os.environ.get("TPUSERVE_GHOST_KNOB", "0")
        y = 1  # tpulint: config-ok
    """, passes=["config-surface"],
        extra={"config_surface": P7_NO_EXTRAS})
    by_rule = {f.rule: f.as_dict() for f in findings}
    lint = by_rule["env-var-unreachable"]
    assert lint["pass"] == "config-surface" and lint["suppressible"]
    core = by_rule["suppression-missing-reason"]
    assert core["pass"] == "core" and not core["suppressible"]


def test_suppression_honored_in_disk_loaded_files(tmp_path):
    """P6/P7 anchor findings in files they load from disk (tools/)
    — a reasoned per-line tag there must suppress exactly like
    in the lint set, or the documented escape hatch is a lie."""
    tools = tmp_path / "tools"
    tools.mkdir()
    src = ("import os\n\n"
           "# tpulint: config-ok(fixture: documented in the tool's "
           "--help)\n"
           'X = os.environ.get("TPUSERVE_DISK_ONLY_KNOB")\n')
    (tools / "knob.py").write_text(src)
    (tmp_path / "README.md").write_text("no env vars documented here\n")
    cfg = Config({**DEFAULT_CONFIG, "config_surface": {
        **DEFAULT_CONFIG["config_surface"], "env_shell": {}}})
    findings = run_lint_sources({}, cfg, repo_root=str(tmp_path),
                                passes=["config-surface"])
    assert findings == []
    # negative control: the tag (not an extraction gap) does the work
    (tools / "knob.py").write_text(src.replace(
        "# tpulint: config-ok(fixture: documented in the tool's "
        "--help)\n", ""))
    from tools.tpulint.core import _AST_CACHE  # content-keyed: no stale
    assert _AST_CACHE is not None
    findings = run_lint_sources({}, cfg, repo_root=str(tmp_path),
                                passes=["config-surface"])
    assert "env-var-undocumented" in rules(findings)


def test_p7_tools_read_does_not_mask_engine_unreachability():
    """A var read in BOTH tools/ and tpuserve/ is judged by its
    engine-side site — a tools read (sorted first) must not swallow the
    reachability rule."""
    read = 'import os\nX = os.environ.get("TPUSERVE_GHOST_KNOB")\n'
    findings = run_lint_sources(
        {"tools/a.py": read, "tpuserve/b.py": read},
        Config({**DEFAULT_CONFIG, "config_surface": P7_NO_EXTRAS}),
        repo_root=REPO, passes=["config-surface"])
    unreach = [f for f in findings if f.rule == "env-var-unreachable"]
    assert [f.file for f in unreach] == ["tpuserve/b.py"]


def test_p6_keys_read_skips_environ_and_header_receivers():
    """A consumer function reading os.environ or request headers must
    not turn those constant keys into payload-contract reads."""
    from tools.tpulint.interface import keys_read
    import ast as _ast
    src = textwrap.dedent("""
        import os

        def consume(payload, self):
            a = payload.get("real_key")
            b = os.environ.get("TPUSERVE_NOT_A_PAYLOAD_KEY")
            c = self.headers.get("X-Not-A-Payload-Key")
            d = self.headers["X-Also-Not"]
            return a, b, c, d
    """)
    got = keys_read({"f.py": (src, _ast.parse(src))}, ["f.py::consume"])
    assert set(got) == {"real_key"}


def test_ast_cache_is_shared_across_runs():
    from tools.tpulint.core import cached_parse
    src = "x = 1\n"
    assert cached_parse(src) is cached_parse(src)
    # and the parse pipeline uses it: same source, same tree object
    from tools.tpulint.core import parse_sources
    t1 = parse_sources({"a.py": src})[0]["a.py"][1]
    t2 = parse_sources({"b.py": src})[0]["b.py"][1]
    assert t1 is t2


# ---------------------------------------------------------------------
# ISSUE 17: model-pool surface under all three machine checks
# ---------------------------------------------------------------------

def test_p1_clock_seam_covers_modelpool():
    """ISSUE 17 satellite: tpuserve/modelpool/ is clock_paths-covered —
    LRU recency and swap timing must come through the injected clock, so
    a direct wall-clock read in the tier bookkeeping is an error while
    the seamed form is clean."""
    findings = lint_snippet("""
        import time

        class WeightTiers:
            def touch(self, name):
                self._last[name] = time.monotonic()
    """, passes=["host-sync"], path="tpuserve/modelpool/tiers.py")
    assert "monotonic-outside-clock-seam" in rules(findings)
    assert lint_snippet("""
        class ModelPool:
            def touch(self, name):
                self._last[name] = self.clock.monotonic()
    """, passes=["host-sync"], path="tpuserve/modelpool/pool.py") == []


def test_p6_modelpool_protocol_surface_registered():
    """ISSUE 17 (P6): the catalog rows the gateway routes on are
    produced by ModelPool.catalog_status under /healthz, and the
    /debug/engine 'modelpool' block is operator surface — so a rename
    on either side of the gateway<->replica catalog contract breaks the
    protocol pass, not production."""
    proto = DEFAULT_CONFIG["protocol"]
    assert "modelpool" in proto["operator_keys"]
    healthz = proto["endpoints"]["/healthz"]["producers"]
    assert any("modelpool/pool.py::ModelPool.catalog_status" in p
               for p in healthz)


def test_p7_modelpool_kill_switch_is_operator_lever():
    """ISSUE 17 (P7): TPUSERVE_MODELPOOL is a registered operator lever
    — WITHOUT the allowlist entry the same read is flagged unreachable
    (no DeployConfig field backs it, by design: the deploy layer turns
    the pool on via model_catalog, the kill switch is per-pod)."""
    assert "TPUSERVE_MODELPOOL" in \
        DEFAULT_CONFIG["config_surface"]["env_operator"]
    findings = lint_snippet("""
        import os

        ENABLED = os.environ.get("TPUSERVE_MODELPOOL", "1")
    """, passes=["config-surface"],
        extra={"config_surface": {**P7_NO_EXTRAS, "env_operator": []}})
    assert "env-var-unreachable" in rules(findings)


def test_p7_shipping_model_catalog_is_reachable():
    """ISSUE 17 wiring pin (the P7 DeployConfig-legitimization path):
    TPUSERVE_MODEL_CATALOG is backed by DeployConfig.model_catalog and
    the manifests emit it in canonical JSON (plus the PVC spill dir, so
    demoted weights survive pod restarts); no catalog -> no env."""
    import dataclasses as _dc
    from tpuserve.provision.config import DeployConfig
    from tpuserve.provision.manifests import _engine_container
    assert any(f.name == "model_catalog"
               for f in _dc.fields(DeployConfig))
    cfg = DeployConfig(provider="local", model_catalog="tiny-b,tiny-a",
                       weight_host_bytes=1 << 30)
    env = {e["name"]: e.get("value")
           for e in _engine_container(cfg)["env"]}
    assert json.loads(env["TPUSERVE_MODEL_CATALOG"]) == \
        {"tiny-a": None, "tiny-b": None}
    assert env["TPUSERVE_WEIGHT_SPILL_DIR"] == "/models/.weight-spill"
    assert env["TPUSERVE_WEIGHT_HOST_BYTES"] == str(1 << 30)
    env_off = {e["name"] for e in _engine_container(
        DeployConfig(provider="local"))["env"]}
    assert not any(n.startswith(("TPUSERVE_MODEL_CATALOG",
                                 "TPUSERVE_WEIGHT_")) for n in env_off)
