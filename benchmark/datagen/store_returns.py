"""TPC-DS store_returns, the three columns of bench.make_store_returns:
returns reference a sample of sales rows with distinct (ticket, item)."""
import numpy as np

from benchmark.datagen.store_sales import N_ITEMS

TYPES = ["long", "int", "decimal(7,2)"]


def make(rows, rng, parent=None):
    key = parent["ticket"] * np.int64(2 * N_ITEMS) + parent["item_sk"]
    uniq, first_idx = np.unique(key, return_index=True)
    if len(uniq) < rows:
        # the row count, and with it every program's bucket, may not
        # depend on the seed
        raise ValueError(
            f"store_sales holds {len(uniq)} distinct (ticket, item) pairs, "
            f"fewer than the {rows} returns the configuration asks for")
    idx = first_idx[rng.choice(len(uniq), size=rows, replace=False)]
    return {
        "ticket": parent["ticket"][idx],
        "item_sk": parent["item_sk"][idx],
        "return_amt": rng.integers(50, 500_000, rows),
    }
