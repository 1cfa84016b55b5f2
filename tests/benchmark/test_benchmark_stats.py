"""Metric arithmetic: percentiles, tpot, tokens in the window, failures,
lateness.  Pure Python, no device."""

import pytest

from benchmark.harness import stats


def rec(rid="r", phase="window", due=10.0, sent=10.001, status=200,
        events=((10.1, 1), (10.2, 4), (10.3, 3)), want=8, done=True,
        end=10.31, **extra):
    return {"id": rid, "phase": phase, "due": due, "sent": sent,
            "status": status, "events": [list(e) for e in events],
            "want": want, "done": done, "end": end, **extra}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([5, 1, 4, 2, 3], 100, 5.0),
    ([5, 1, 4, 2, 3], 0, 1.0),
    (list(range(101)), 95, 95.0),
    ([10, 20], 95, 19.5),
    ([7], 95, 7.0),
])
def test_percentile_matches_linear_interpolation(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_agrees_with_numpy():
    import numpy as np
    rng = np.random.default_rng(0)
    xs = rng.lognormal(size=257).tolist()
    for q in (5, 50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_runs_from_due_not_from_sent():
    r = rec(due=10.0, sent=10.05)
    assert stats.ttft_ms(r) == pytest.approx(100.0)


def test_tpot_is_span_over_tokens_minus_one():
    r = rec(events=((1.0, 1), (1.7, 7)), want=8)
    assert stats.tpot_ms(r) == pytest.approx(100.0)
    assert stats.tpot_ms(rec(events=((1.0, 1),), want=1)) is None


def test_max_gap():
    r = rec(events=((1.0, 1), (1.1, 1), (1.5, 1)))
    assert stats.max_gap_ms(r) == pytest.approx(400.0)


@pytest.mark.parametrize("change,is_failed", [
    ({}, False),
    ({"status": 503}, True),
    ({"status": 429}, True),
    ({"want": 9}, True),                       # one token short
    ({"want": 7}, True),                       # one token over
    ({"done": False}, True),                   # no [DONE]
    ({"error": "boom"}, True),
    ({"status": None, "events": []}, True),
])
def test_failure_counting(change, is_failed):
    assert stats.failed(rec(**change)) is is_failed


def test_open_loop_population_is_what_was_due_in_the_window():
    records = [rec("a", phase="preroll", due=5.0),
               rec("b"), rec("c", status=503, events=()),
               rec("d", want=99)]
    s = stats.summarize(records, "open", 10.0, 20.0)
    assert (s["attempted"], s["failed"]) == (3, 2)
    assert len(s["ttft_ms"]) == 1


def test_closed_loop_population_is_what_ended_in_the_window():
    records = [rec("a", end=9.0), rec("b", end=12.0),
               rec("c", end=25.0), rec("d", cut=True, end=20.0, done=False),
               rec("e", end=15.0, status=500, events=())]
    s = stats.summarize(records, "closed", 10.0, 20.0)
    assert (s["attempted"], s["failed"], s["cut"]) == (2, 1, 1)


def test_tokens_in_window_counts_by_arrival_time_of_each_event():
    records = [rec(events=((9.9, 5), (10.0, 3), (19.99, 2), (20.0, 7)),
                   want=17)]
    s = stats.summarize(records, "closed", 10.0, 20.0)
    assert s["tokens_in_window"] == 5
    assert stats.end_to_end("out_tok_s", s) == pytest.approx(0.5)


def test_lateness_is_sent_minus_due_for_the_windows_requests():
    records = [rec("a", due=10.0, sent=10.004),
               rec("b", due=11.0, sent=11.010),
               rec("c", due=5.0, sent=9.0, phase="preroll")]
    s = stats.summarize(records, "open", 10.0, 20.0)
    assert sorted(s["loadgen_late_ms"]) == pytest.approx([4.0, 10.0])


@pytest.mark.parametrize("name,want", [
    ("ttft_p50_ms", 150.0), ("ttft_p95_ms", 195.0), ("tpot_p95_ms", 100.0)])
def test_end_to_end_by_name(name, want):
    records = [rec("a", events=((10.1, 1), (10.8, 7))),
               rec("b", events=((10.2, 1), (10.9, 7)))]
    s = stats.summarize(records, "open", 10.0, 20.0)
    assert stats.end_to_end(name, s) == pytest.approx(want)


def test_unknown_end_to_end_metric_raises():
    with pytest.raises(KeyError):
        stats.end_to_end("goodput", {"seconds": 1})
