"""On-chip serving benchmark of the STAR reproduction (see BENCHMARK.json)."""
