"""The benchmark of mythril-tpu: see BENCHMARK.json and PERF.md."""
