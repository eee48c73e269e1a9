"""On-chip benchmark of CRUM's save and restore paths (see BENCHMARK.json)."""
