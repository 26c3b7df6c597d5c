"""The repo's benchmark: one command, driven by BENCHMARK.json and the
data files beside this module (see PERF.md). Nothing outside `benchmark/`
and `tests/benchmark/` belongs to it; it imports the program (`oryx_tpu`)
only as the system under test."""
