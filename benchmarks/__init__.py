"""The benchmark: one command (run.py) that finds every configuration,
traffic mix, loop kind and per-layer metric by the name BENCHMARK.json
gives. Later PRs add files and manifest entries; they edit none."""
