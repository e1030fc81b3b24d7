"""TPC-DS store_sales, the seven columns of bench.make_store_sales."""
import numpy as np

N_STORES = 40
N_ITEMS = 100_000
N_DATES = 2555          # ~7 years of date_dim
DATE_SK0 = 2_450_000    # TPC-DS-style surrogate key base

TYPES = ["int", "int", "int", "long", "long", "decimal(7,2)", "decimal(7,2)"]


def make(rows, rng, parent=None):
    return {
        "date_sk": (DATE_SK0 + rng.integers(0, N_DATES, rows)).astype(np.int32),
        "store_sk": rng.integers(1, N_STORES + 1, rows).astype(np.int32),
        "item_sk": rng.integers(1, N_ITEMS + 1, rows).astype(np.int32),
        "ticket": rng.integers(0, max(rows // 8, 1), rows),
        "quantity": rng.integers(1, 100, rows),
        # DECIMAL(7,2) unscaled cents
        "ext_sales": rng.integers(100, 1_000_000, rows),
        "net_profit": rng.integers(-100_000, 400_000, rows),
    }
