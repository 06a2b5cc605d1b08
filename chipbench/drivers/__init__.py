"""The benchmark: see BENCHMARK.json at the root and PERF.md."""
