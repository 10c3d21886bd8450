"""The benchmark: see PERF.md and BENCHMARK.json."""
