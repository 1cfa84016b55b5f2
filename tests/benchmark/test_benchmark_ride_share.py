"""``sched.ride_share``: the share of a window's answer tokens that rode a
prompt dispatch (the step records' ``ridden_tokens`` of kind ``mixed``
over the tokens the window streamed).  Hand-made step records, so every
number below can be worked out on paper.  No chip, and no number here is a
measurement.

The reader is IN PLACE and NOT LISTED in ``BENCHMARK.json`` (PR 58): seven
of the eight cells' reports are pinned from literals by the benchmark's own
tests (``test_benchmark_accepted.py`` and the ``set(cell.per_layer) ==``
line of the K-EXAONE, Olmo-Hybrid and openPangu metric tests), so listing
the riding cells is a ``benchmark`` PR's (PERF.md section 7)."""

import pytest

from benchmark.harness import plan
from tests.benchmark.test_benchmark_mixed_steps import mixed

NAME = "sched.ride_share"
WINDOW = {"kind": "window", "rows": 128, "actual_tokens": 128 * 8,
          "padded_tokens": 128 * 8}
PACKED = {"kind": "prefill", "rows": 2, "actual_tokens": 600,
          "padded_tokens": 640}


@pytest.mark.parametrize("steps,tokens,want", [
    # seven windows of 8 steps, then a prompt dispatch the 128 rows ride
    ([WINDOW] * 7 + [mixed(128, 256, 448)], 128 * 57, 100 * 128 / 7296),
    # two mixed steps in three dispatches, a seat empty in the second
    ([mixed(128, 300, 448), WINDOW, mixed(127, 520, 704)], 128 * 10,
     100 * 255 / 1280),
    # the phase split: the records hold no mixed step, and the share is 0
    ([WINDOW, PACKED, WINDOW], 2048, 0.0),
    # a program whose records never carried the count reads 0, not an error
    ([{"kind": "mixed", "rows": 3, "actual_tokens": 40,
       "padded_tokens": 64}], 100, 0.0),
    # no count of the window's tokens: nothing to divide by
    ([mixed(128, 256, 448)], 0, None),
    ([mixed(128, 256, 448)], None, None),
], ids=["one-in-eight", "two-of-three", "phase-split", "no-count",
        "no-tokens", "no-summary"])
def test_the_ride_share_is_the_ridden_tokens_over_the_windows(steps, tokens,
                                                              want):
    """In per cent, and never over 100: a ridden token is one of the
    window's."""
    run = {"steps": steps}
    if tokens is not None:
        run["summary"] = {"tokens_in_window": tokens}
    got = plan.discover_layer_metrics()[NAME].compute(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want) and 0 <= got <= 100


def test_the_reader_is_in_place_and_no_cell_is_asked_for_it():
    """It names the scheduler's layer as ``BENCHMARK.json`` has it and
    moves ``out_tok_s``, so an entry is its constants and a list of cells;
    until a ``benchmark`` PR writes one, no cell's traced run computes it
    and the file lints as it did."""
    bench = plan.load_benchmark()
    reader = plan.discover_layer_metrics()[NAME]
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.MOVES,
            reader.SOURCE) == ("scheduler", "%", "higher", "out_tok_s",
                               "program_span")
    assert reader.LAYER in {m["layer"] for m in bench["per_layer"]}
    assert NAME not in {m["name"] for m in bench["per_layer"]}
    assert plan.lint(bench) == []
    for cell in (w["name"] for w in bench["workloads"]):
        assert NAME not in plan.load_cell(cell, bench).per_layer
