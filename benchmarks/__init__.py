"""The stoix_tpu benchmark: `python3 benchmarks/run.py --workload <cell> ...`
runs one cell of BENCHMARK.json once. See PERF.md for what it measures."""
