"""kfac-tpu's benchmark: see BENCHMARK.json and PERF.md."""
