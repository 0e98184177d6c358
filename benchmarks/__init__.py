"""The benchmark: harness, yardstick and data files (see BENCHMARK.json)."""
