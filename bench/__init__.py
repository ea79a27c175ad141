"""On-chip benchmark of the routed serving path (see BENCHMARK.json)."""
