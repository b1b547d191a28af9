"""The benchmark of the served webhook path (see PERF.md and BENCHMARK.json)."""
