"""On-chip benchmark of the serving engines (see BENCHMARK.json, PERF.md)."""
