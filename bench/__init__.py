"""On-chip benchmark of the UVM sweep (see PERF.md and BENCHMARK.json)."""
