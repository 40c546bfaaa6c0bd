"""The benchmark of plenum_tpu: see benchmarks/README.md and BENCHMARK.json."""
