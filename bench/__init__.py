"""The chip benchmark: one cell (a model configuration under a traffic
mix) run once per call of `bench/run.py`. See BENCHMARK.json and PERF.md."""
