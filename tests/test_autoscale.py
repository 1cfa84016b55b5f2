"""SLI-driven autoscaler (ISSUE 12, tpuserve/autoscale/).

Tier-1 keeps the policy-level tests engine-free (synthetic signal
streams under VirtualClock) and sizes the two engine-backed pool
replays small — the suite runs near the 870s driver budget.  The full
static-vs-autoscaled storm A/B (TTFT-improvement assertion included)
is ``slow``-marked.
"""

from __future__ import annotations

import json

import pytest

from tpuserve.autoscale import (AutoscalePolicy, PolicyConfig, PoolSignals,
                                PoolReplayOptions, Reconciler,
                                ReplicaSignals, decisions_digest,
                                make_storm_workload, pool_replay,
                                signals_from_debug, signals_from_metrics)
from tpuserve.runtime.clock import VirtualClock


def _sig(t, n=1, level=0, waiting=0, running=0, delay=None, booting=0,
         pending=0, ttft_p95=None):
    reps = []
    for i in range(n):
        reps.append(ReplicaSignals(
            name=f"r{i}", brownout_level=level, waiting=waiting,
            running=running,
            queue_delay_ewma=({"interactive": delay}
                              if delay is not None else {}),
            sli=({"interactive": {"ttft": {"n": 9, "p50": ttft_p95 / 2,
                                           "p95": ttft_p95}}}
                 if ttft_p95 is not None else {})))
    return PoolSignals(t=t, replicas=reps, booting=booting,
                       pending_demand=pending)


def _policy(clock, **kw):
    base = dict(min_replicas=0, max_replicas=4, brownout_out_level=1,
                queue_delay_out_s=0.5, scale_out_cooldown_s=5.0,
                scale_in_cooldown_s=10.0, idle_in_s=4.0)
    base.update(kw)
    return AutoscalePolicy(PolicyConfig(**base), clock=clock)


# ---------------------------------------------------------------------
# tier-1: policy unit tests (no engines)
# ---------------------------------------------------------------------

def test_scale_out_on_rising_brownout():
    """SATELLITE PIN: rising brownout level scales out BEFORE the
    ladder's shedding rungs — the trigger fires at L1, not L3."""
    clock = VirtualClock()
    pol = _policy(clock)
    assert pol.decide(_sig(0.0, n=1, running=2)).action == "hold"
    clock.advance(1.0)
    d = pol.decide(_sig(1.0, n=1, level=1, waiting=3, running=2))
    assert d.action == "scale_out" and d.target == 2
    assert "brownout level 1" in d.reason


def test_scale_out_on_queue_delay_and_ttft_breach():
    clock = VirtualClock()
    pol = _policy(clock)
    d = pol.decide(_sig(0.0, n=1, waiting=2, running=1, delay=0.6))
    assert d.action == "scale_out" and "queue-delay" in d.reason
    # TTFT trigger is opt-in (0 disables)
    clock2 = VirtualClock()
    pol2 = _policy(clock2, ttft_p95_out_s=2.0)
    d2 = pol2.decide(_sig(0.0, n=1, running=1, ttft_p95=3.5))
    assert d2.action == "scale_out" and "TTFT p95" in d2.reason
    assert _policy(VirtualClock()).decide(
        _sig(0.0, n=1, running=1, ttft_p95=3.5)).action == "hold"


def test_scale_out_on_canary_breach():
    """ISSUE 13: the gateway canary's black-box breach (consecutive
    probe failures) is a scale-out trigger — a replica that stopped
    answering emits no white-box queue-delay EWMA at all."""
    clock = VirtualClock()
    pol = _policy(clock)
    sig = _sig(0.0, n=1, running=1)
    sig.canary_breached = 2
    d = pol.decide(sig)
    assert d.action == "scale_out" and "canary breach" in d.reason
    # opt-out restores the old decision sequence
    clock2 = VirtualClock()
    pol2 = _policy(clock2, canary_out=False)
    sig2 = _sig(0.0, n=1, running=1)
    sig2.canary_breached = 2
    assert pol2.decide(sig2).action == "hold"


def test_no_flap_across_cooldown():
    """SATELLITE PIN: a sustained breach inside the cooldown produces
    exactly ONE scale-out, and the post-storm idle inside the scale-in
    cooldown produces no immediate scale-in."""
    clock = VirtualClock()
    pol = _policy(clock)
    hot = dict(n=1, level=2, waiting=5, running=2)
    assert pol.decide(_sig(0.0, **hot)).action == "scale_out"
    for dt in (0.5, 1.0, 2.0, 4.9):
        clock.advance_to(dt)
        assert pol.decide(_sig(dt, **hot)).action == "hold"
    # past the cooldown a still-breaching pool may step again
    clock.advance_to(5.1)
    assert pol.decide(_sig(5.1, n=2, level=1, waiting=4,
                           running=2)).action == "scale_out"
    # storm ends: idle, but within scale_in_cooldown_s of the last
    # scale event — and then within idle_in_s — still hold
    for dt in (5.6, 7.0, 9.0, 14.0):
        clock.advance_to(dt)
        assert pol.decide(_sig(dt, n=3)).action == "hold"
    # idle >= 4s since 5.6 AND >= 10s since the scale at 5.1: scale in
    clock.advance_to(16.0)
    d = pol.decide(_sig(16.0, n=3))
    assert d.action == "scale_in" and d.target == 2
    assert len(pol.decisions) == 3


def test_scale_in_only_when_idle_and_drained():
    clock = VirtualClock()
    # out-triggers parked high so this test isolates the scale-in arm
    pol = _policy(clock, scale_in_cooldown_s=0.0, brownout_out_level=9,
                  queue_delay_out_s=99.0)
    # anything non-idle resets the timer: queued work, running rows,
    # a lingering brownout level, booting capacity, pending demand
    for t, kw in ((0.0, dict(n=2, waiting=1)),
                  (5.0, dict(n=2, running=1)),
                  (10.0, dict(n=2, level=1)),
                  (15.0, dict(n=2, booting=1)),
                  (20.0, dict(n=2, pending=1, running=1))):
        clock.advance_to(t)
        assert pol.decide(_sig(t, **kw)).action == "hold"
    clock.advance_to(22.0)
    assert pol.decide(_sig(22.0, n=2)).action == "hold"   # timer restarts
    clock.advance_to(26.5)
    d = pol.decide(_sig(26.5, n=2))
    assert d.action == "scale_in" and d.target == 1
    # min_replicas floor: a 1-replica pool with min=1 never drops to 0
    clock2 = VirtualClock()
    pol2 = _policy(clock2, min_replicas=1, scale_in_cooldown_s=0.0)
    clock2.advance_to(100.0)
    pol2.decide(_sig(0.0, n=1))
    clock2.advance_to(200.0)
    assert pol2.decide(_sig(200.0, n=1)).action == "hold"


def test_scale_from_zero_on_pending_demand():
    """ACCEPTANCE (policy half): demand against an empty pool scales
    out immediately, cooldown notwithstanding."""
    clock = VirtualClock()
    pol = _policy(clock)
    assert pol.decide(_sig(0.0, n=0)).action == "hold"     # idle empty
    d = pol.decide(_sig(0.0, n=0, pending=3))
    assert d.action == "scale_out" and d.target == 1
    assert "scale-from-zero" in d.reason
    # a booting replica counts as capacity: no double-boot
    assert pol.decide(_sig(0.1, n=0, booting=1,
                           pending=3)).action == "hold"


def test_policy_decision_sequence_deterministic():
    """ACCEPTANCE: the same recorded signal stream + the same config
    produce the identical decision sequence (digest-compared)."""
    stream = [(t, _sig(t, n=1 + int(t > 6), level=(2 if 2 <= t <= 6
                                                   else 0),
                       waiting=(5 if 2 <= t <= 6 else 0),
                       running=(2 if t < 8 else 0)))
              for t in [x * 0.5 for x in range(40)]]

    def run():
        clock = VirtualClock()
        pol = _policy(clock, idle_in_s=2.0, scale_in_cooldown_s=3.0)
        for t, sig in stream:
            clock.advance_to(t)
            pol.decide(sig)
        return pol.decisions

    d1, d2 = run(), run()
    assert [d.as_tuple() for d in d1] == [d.as_tuple() for d in d2]
    assert decisions_digest(d1) == decisions_digest(d2)
    assert any(d.action == "scale_out" for d in d1)
    assert any(d.action == "scale_in" for d in d1)


# ---------------------------------------------------------------------
# tier-1: signal parsing + reconciler (no engines, no kubectl)
# ---------------------------------------------------------------------

def test_signals_from_debug_scalars():
    """SATELLITE PIN (small fix): /debug/engine carries the brownout
    level and per-class queue-delay EWMAs as plain scalars — the
    scrape needs no histogram-bucket reconstruction."""
    payload = {
        "control": {"brownout_level": 2,
                    "queue_delay_ewma": {"interactive": 0.8,
                                         "standard": None},
                    "waiting": 7, "running": 4},
        "sli": {"interactive": {"ttft": {"n": 5, "p50": 0.1,
                                         "p95": 0.9}}},
        "cold_start_s": 12.5,
    }
    sig = signals_from_debug("pod-1", payload)
    assert sig.brownout_level == 2
    assert sig.queue_delay_ewma == {"interactive": 0.8}
    assert sig.waiting == 7 and sig.running == 4
    assert sig.sli["interactive"]["ttft"]["p95"] == 0.9
    assert sig.cold_start_s == 12.5
    # disagg form: queue depths sum, worst engine's ladder wins
    multi = {"engines": [
        {"control": {"brownout_level": 0, "waiting": 1, "running": 2}},
        {"control": {"brownout_level": 3, "waiting": 4, "running": 0,
                     "queue_delay_ewma": {"interactive": 1.5}}}]}
    m = signals_from_debug("pod-2", multi)
    assert m.brownout_level == 3 and m.waiting == 5 and m.running == 2
    assert m.queue_delay_ewma == {"interactive": 1.5}


def test_signals_from_metrics_fallback():
    text = ('tpuserve_brownout_level{model_name="m"} 3.0\n'
            'vllm_num_requests_waiting{model_name="m"} 11\n'
            'vllm_num_requests_running{model_name="m"} 2\n')
    sig = signals_from_metrics("pod-1", text)
    assert sig.brownout_level == 3
    assert sig.waiting == 11 and sig.running == 2


class _FakePool:
    def __init__(self):
        self.scaled = []
        self.sig = _sig(0.0, n=1)
        self.urls = ["http://10.0.0.1:8000"]
        self.cold = [7.5]

    def signals(self):
        return self.sig

    def scale_to(self, n, reason):
        self.scaled.append(n)

    def ready_urls(self):
        return list(self.urls)

    def drain_cold_starts(self):
        out, self.cold = self.cold, []
        return out


def test_reconciler_reverts_failed_apply(tmp_path):
    """A kubectl blip must not burn the cooldown (or the decisions
    counter) on an action that never took effect: the decision is
    reverted and the very next tick retries."""
    from tpuserve.server.metrics import AutoscalerMetrics

    class _FailingPool(_FakePool):
        def __init__(self):
            super().__init__()
            self.fail_next = 1

        def scale_to(self, n, reason):
            if self.fail_next:
                self.fail_next -= 1
                raise RuntimeError("kubectl: connection refused")
            super().scale_to(n, reason)

    clock = VirtualClock()
    pool = _FailingPool()
    metrics = AutoscalerMetrics()
    rec = Reconciler(pool, _policy(clock), metrics=metrics)
    pool.sig = _sig(0.0, n=1, level=2, waiting=4, running=2)
    d1 = rec.run_once()
    assert d1.action == "scale_out" and pool.scaled == []
    assert rec.policy.decisions == []          # rolled back
    assert b'action="scale_out"} 1.0' not in metrics.render()
    clock.advance(0.5)                         # well inside the cooldown
    d2 = rec.run_once()                        # retry succeeds
    assert d2.action == "scale_out" and pool.scaled == [2]
    assert len(rec.policy.decisions) == 1


def test_reconciler_applies_decisions_and_exports(tmp_path):
    from tpuserve.server.metrics import AutoscalerMetrics
    clock = VirtualClock()
    pool = _FakePool()
    metrics = AutoscalerMetrics()
    backends = str(tmp_path / "backends.json")
    rec = Reconciler(pool, _policy(clock), metrics=metrics,
                     backends_file=backends, pool_name="tpuserve-engine")
    pool.sig = _sig(0.0, n=1, level=2, waiting=4, running=2)
    d = rec.run_once()
    assert d.action == "scale_out" and pool.scaled == [2]
    # backends file published for the gateway's poll loop
    assert json.loads(open(backends).read()) == pool.urls
    text = metrics.render().decode()
    assert 'tpuserve_autoscaler_decisions_total{action="scale_out"} 1.0' \
        in text
    assert "tpuserve_cold_start_seconds_count 1.0" in text
    assert 'tpuserve_autoscaler_replicas{pool="tpuserve-engine"} 2.0' \
        in text


# ---------------------------------------------------------------------
# tier-1: pool replay (engines; kept small for the 870s budget)
# ---------------------------------------------------------------------

STORM_OPTS = PoolReplayOptions(
    step_time_s=0.05, control_interval_s=0.25, cold_start_s=1.0,
    initial_replicas=1, max_num_seqs=2, max_waiting=12)
STORM_POLICY = PolicyConfig(min_replicas=1, max_replicas=3,
                            scale_out_cooldown_s=2.0,
                            scale_in_cooldown_s=20.0, idle_in_s=10.0)


def _storm(n=28):
    # sized down for the 870s tier-1 budget: still ~2x oversubscribes
    # one 2-seat replica (L3 reached without scaling); the full n=80
    # storm lives in the slow-marked A/B
    return make_storm_workload(n=n, ramp_s=3.0, span_s=6.0,
                               max_tokens=16)


def test_pool_replay_deterministic_and_scales_before_shed():
    """ACCEPTANCE: same recorded storm + same policy config => the
    identical decision sequence (and identical tokens), and the first
    scale-out fires BEFORE the ladder's first L3 entry / shed event."""
    wl = _storm()
    r1 = pool_replay(wl, STORM_OPTS, STORM_POLICY)
    r2 = pool_replay(wl, STORM_OPTS, STORM_POLICY)
    assert r1["decision_digest"] == r2["decision_digest"]
    assert [d["t"] for d in r1["decisions"]] == \
        [d["t"] for d in r2["decisions"]]
    assert r1["token_digest"] == r2["token_digest"]
    assert not r1["aborted"]
    # the policy actually scaled, and did so before any shedding rung
    assert r1["replicas_peak"] > 1
    assert r1["first_scale_out_t"] is not None
    for shed_t in (r1["first_l3_t"], r1["first_shed_t"]):
        if shed_t is not None:
            assert r1["first_scale_out_t"] < shed_t
    # scaled-out replicas report cold-pod-to-first-token
    assert r1["cold_starts_observed_s"]
    assert all(v >= STORM_OPTS.cold_start_s
               for v in r1["cold_starts_observed_s"])
    # everyone reached a terminal state
    assert set(r1["outcomes"]) == {r.request_id for r in wl.requests}
    assert r1["counters"]["completed"] >= len(wl.requests) - 2


def test_pool_replay_scale_from_zero_with_warm_prefix(tmp_path):
    """ACCEPTANCE: scale-from-zero end to end on CPU — a pool at ZERO
    replicas takes demand, the policy boots one, and the from-zero
    replica serves its first token with a warm-prefix hit restored from
    the KV spill tier; tpuserve_cold_start_seconds reports it."""
    from tpuserve.runtime import (CacheConfig, Engine, EngineConfig,
                                  SchedulerConfig)
    from tpuserve.runtime.request import SamplingParams
    from tpuserve.server.metrics import AutoscalerMetrics
    spill = str(tmp_path / "spill")
    shared = list(range(2, 26))
    # phase 1: a (past-life) replica serves the prefix; churn demotes
    # it through host DRAM onto the spill dir (on its second time cold:
    # the tier declines a first eviction); the pod "dies"
    eng = Engine(EngineConfig(
        model="tiny-qwen3",
        cache=CacheConfig(block_size=4, num_blocks=24,
                          max_blocks_per_seq=16),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  min_prefill_bucket=8,
                                  min_decode_bucket=2),
        enable_prefix_caching=True, kv_tiers=True, kv_host_bytes=3000,
        kv_spill_dir=spill))
    p = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    from tier_drive import cold_twice
    cold_twice(eng, [shared + [30]], p)
    eng._kv_tiers.flush()
    assert eng.stats.kv_spilled_blocks > 0
    del eng
    # phase 2: empty pool + demand over the same prefix
    from tpuserve.replay.workload import Workload, WorkloadRequest
    wl = Workload(requests=[WorkloadRequest(
        request_id=f"cold-{i}", arrival_s=0.2 * i,
        prompt_tokens=len(shared) + 1,
        prompt_token_ids=shared + [30 + i], max_tokens=4,
        slo_class="interactive", seed=i) for i in range(4)], seed=3)
    metrics = AutoscalerMetrics()
    rep = pool_replay(
        wl,
        PoolReplayOptions(initial_replicas=0, cold_start_s=1.0,
                          control_interval_s=0.1, kv_spill_dir=spill,
                          kv_host_bytes=3000),
        PolicyConfig(min_replicas=0, max_replicas=1),
        metrics=metrics)
    assert rep["replicas_peak"] == 1
    assert rep["decisions"] and \
        "scale-from-zero" in rep["decisions"][0]["reason"]
    assert rep["counters"]["completed"] == 4
    # the warm-prefix hit: blocks came back from the spill tier
    assert rep["counters"]["kv_restored_blocks"] > 0
    # cold-pod-to-first-token measured and exported
    assert len(rep["cold_starts_observed_s"]) == 1
    assert rep["cold_starts_observed_s"][0] >= 1.0
    text = metrics.render().decode()
    assert "tpuserve_cold_start_seconds_count 1.0" in text
    assert 'tpuserve_autoscaler_decisions_total{action="scale_out"} 1.0' \
        in text


# ---------------------------------------------------------------------
# slow: the full storm A/B
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_storm_ab_autoscaling_improves_interactive_ttft():
    """ACCEPTANCE (A/B half): replaying the storm with autoscaling
    enabled improves interactive p95 TTFT vs the static-topology
    replay of the SAME storm, with scale-out before any L3/L4 shed."""
    wl = make_storm_workload(n=80, ramp_s=5.0, span_s=16.0,
                             max_tokens=16)
    static = pool_replay(wl, STORM_OPTS)
    auto = pool_replay(wl, STORM_OPTS, STORM_POLICY)
    s95 = static["sli"]["interactive"]["ttft"]["p95"]
    a95 = auto["sli"]["interactive"]["ttft"]["p95"]
    assert a95 < s95, (s95, a95)
    assert auto["counters"]["shed"] < static["counters"]["shed"]
    assert auto["first_scale_out_t"] is not None
    for shed_t in (auto["first_l3_t"], auto["first_shed_t"]):
        if shed_t is not None:
            assert auto["first_scale_out_t"] < shed_t
    # and the static arm genuinely suffered (the storm is a storm)
    assert static["counters"]["shed"] > 0
    assert static["first_l3_t"] is not None
