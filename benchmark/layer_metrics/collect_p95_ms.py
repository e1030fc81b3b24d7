"""95th percentile of the wall of every collect() of the window."""
from benchmark.harness.stats import percentile_ms


def read(run):
    return percentile_ms(run.window, 95.0)
