"""TPC-DS store_sales, the five columns of the star join: the date and
store foreign keys and the measure may be null, as dsdgen leaves a share
of every foreign key and measure null (4.5 % here, each column
independently); item and ticket never are.  Sales dates are uniform over
1998-01-02..2003-01-02, the five years dsdgen sells in; 12 stores and
18,000 items are SF1's (table 3-2)."""
import numpy as np

N_STORES = 12
N_ITEMS = 18_000
SOLD_SK0, SOLD_SK1 = 2_450_816, 2_452_642    # 1998-01-02 .. 2003-01-02
NULL_SHARE = 0.045

TYPES = ["int?", "int?", "int", "long", "decimal(7,2)?"]


def _nullable(values, rng):
    return np.ma.MaskedArray(values, mask=rng.random(len(values)) < NULL_SHARE)


def make(rows, rng, parent=None):
    return {
        "date_sk": _nullable(rng.integers(SOLD_SK0, SOLD_SK1 + 1, rows)
                             .astype(np.int32), rng),
        "store_sk": _nullable(rng.integers(1, N_STORES + 1, rows)
                              .astype(np.int32), rng),
        "item_sk": rng.integers(1, N_ITEMS + 1, rows).astype(np.int32),
        "ticket": rng.integers(0, max(rows // 8, 1), rows),
        # DECIMAL(7,2) unscaled cents, the old generator's range
        "ext_sales": _nullable(rng.integers(100, 1_000_000, rows), rng),
    }
