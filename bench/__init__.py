"""The on-chip benchmark of the DICE serving path (see BENCHMARK.json)."""
