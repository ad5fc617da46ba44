"""The benchmark: harness, yardstick and data files (see PERF.md and
BENCHMARK.json). A regular package, so that `tests/benchmark/` on a test
run's path is never taken for part of it."""
