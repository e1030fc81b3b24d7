"""The benchmark of spark-rapids-tpu: BENCHMARK.json names the cells, the
files of this package are found by those names (see harness/manifest.py)."""
