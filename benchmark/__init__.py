"""The benchmark of record: see BENCHMARK.json and PERF.md."""
