"""Median wall of every collect() of the window, on the host's clock."""
from benchmark.harness.stats import percentile_ms


def read(run):
    return percentile_ms(run.window, 50.0)
