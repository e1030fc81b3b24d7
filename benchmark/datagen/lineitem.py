"""TPC-H lineitem, the four columns Q6 reads (bench.make_lineitem's
ranges; DECIMAL(12,2) as unscaled int64 cents, DATE as int32 days)."""
import numpy as np

TYPES = ["decimal(12,2)", "decimal(12,2)", "decimal(12,2)", "date"]


def make(rows, rng, parent=None):
    return {
        "l_extendedprice": rng.integers(90_000, 10_500_000, rows),
        "l_discount": rng.integers(0, 11, rows),
        "l_quantity": rng.integers(100, 5100, rows),
        "l_shipdate_days": rng.integers(8400, 9500, rows).astype(np.int32),
    }
