"""The seven ``setup.*`` readers (``benchmark/layer_metrics/setup.*``,
PR 57) on made-up ``/metrics`` pages, and what PR 57 appended to
``BENCHMARK.json``, found by name: the first per-layer entries that move
``setup_s``.  Each reads the page the harness scrapes as the window opens
(``run["metrics_start"]``): the program's start-up spans and its compile
ledger.  No chip, and no number here is a measurement."""

import pytest

from benchmark.harness import plan

CELL = "ling-3.0-flash-vl-ep8-l12.reason"
OLDER = ["qwen3-0.6b.batch", "mistral-7b-l16.batch",
         "falcon-h1-34b-l6.reason", "mellum2-12b-l12.batch",
         "k-exaone-236b-ep8-l8.reason", "olmo-hybrid-7b-l16.reason",
         "openpangu-ultra-718b-ep16-l7.reason"]
# reader -> (unit, source, the series it reads)
READS = {
    "setup.build_s": ("s", "program_span",
                      "tpuserve_startup_build_seconds"),
    "setup.warmup_s": ("s", "program_span",
                       "tpuserve_startup_warmup_seconds"),
    "setup.jit_trace_s": ("s", "program_counter",
                          "tpuserve_jit_trace_seconds_total"),
    "setup.jit_lower_s": ("s", "program_counter",
                          "tpuserve_jit_lower_seconds_total"),
    "setup.backend_compile_s": ("s", "program_counter",
                                "tpuserve_backend_compile_seconds_total"),
    "setup.compile_requests": ("count", "program_counter",
                               "tpuserve_compile_requests_total"),
}
SHARE = "setup.cache_miss_share"
NAMES = (*READS, SHARE)
HITS = "tpuserve_compile_cache_hits_total"
MISSES = "tpuserve_compile_cache_misses_total"


@pytest.fixture(scope="module")
def readers():
    return plan.discover_layer_metrics()


@pytest.mark.parametrize("name", sorted(READS))
def test_a_reader_gives_the_series_of_the_opening_page(readers, name):
    """The number as the window opens, whatever the closing page holds;
    0.0 for a series that reads zero; None for a page without it (the
    parent's program) and for a run that scraped none."""
    series = READS[name][2]
    run = {"metrics_start": {series: 41.25, "vllm_request_total": 9.0},
           "metrics_end": {series: 999.0}}
    assert readers[name].compute(run) == 41.25
    run["metrics_start"][series] = 0.0
    got = readers[name].compute(run)
    assert got == 0.0 and got is not None
    del run["metrics_start"][series]
    assert readers[name].compute(run) is None
    assert readers[name].compute({"metrics_start": {}}) is None
    assert readers[name].compute({}) is None


@pytest.mark.parametrize("hits, misses, want", [
    (197.0, 0.0, 0.0),          # a warm start
    (0.0, 197.0, 100.0),        # a first run on an empty cache
    (150.0, 50.0, 25.0),        # an evicting cache
    (0.0, 0.0, 0.0),            # no request asked the cache
], ids=["warm", "first", "evicting", "none asked"])
def test_the_miss_share_says_which_start_a_line_is(readers, hits, misses,
                                                   want):
    run = {"metrics_start": {HITS: hits, MISSES: misses}}
    got = readers[SHARE].compute(run)
    assert got == pytest.approx(want) and isinstance(got, float)


@pytest.mark.parametrize("page", [{}, {HITS: 3.0}, {MISSES: 3.0}],
                         ids=["neither", "hits alone", "misses alone"])
def test_the_miss_share_of_a_program_without_the_ledger_is_none(readers,
                                                                page):
    assert readers[SHARE].compute({"metrics_start": page}) is None


def test_what_pr_57_appended_is_found_by_name():
    """Seven per-layer entries, together and after everything accepted
    before them, each listing the eighth cell ALONE (the benchmark's own
    tests pin what the first seven cells report), the first that move
    ``setup_s``; no configuration, no cell, no end-to-end metric."""
    bench = plan.load_benchmark()
    assert plan.lint(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in NAMES]
    assert at == list(range(at[0], at[0] + len(NAMES)))
    assert at[0] > names.index("moe.group_rows_share")
    readers = plan.discover_layer_metrics()
    for name in NAMES:
        entry = bench["per_layer"][names.index(name)]
        unit, source = READS[name][:2] if name in READS \
            else ("%", "program_counter")
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": "start-up",
                         "moves": "setup_s", "workloads": [CELL]}
        assert readers[name].MOVES == "setup_s"
    movers = {m["name"] for m in bench["per_layer"]
              if m["moves"] == "setup_s"}
    assert movers == set(NAMES)
    assert [w["name"] for w in bench["workloads"]] == [*OLDER, CELL]
    assert [m["name"] for m in bench["end_to_end"]] == ["out_tok_s",
                                                        "setup_s"]
    due = set(plan.load_cell(CELL, bench).per_layer)
    assert set(NAMES) <= due
    assert not {n for n in due if n.startswith(("lin.", "mla.", "ssm."))}


@pytest.mark.parametrize("cell", OLDER)
def test_an_older_cell_reports_what_it_reported(cell):
    """No ``setup.*`` name is asked of the seven older cells, and without
    PR 57's entries each reports exactly what it does with them."""
    bench = plan.load_benchmark()
    loaded = plan.load_cell(cell, bench)
    assert not [n for n in loaded.per_layer if n.startswith("setup.")]
    before = dict(bench, per_layer=[m for m in bench["per_layer"]
                                    if not m["name"].startswith("setup.")])
    assert plan.load_cell(cell, before).per_layer == loaded.per_layer
    assert "setup_s" in loaded.end_to_end
