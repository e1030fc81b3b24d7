"""TPC-DS date_dim, the four columns of bench.make_date_dim.  The same
for every seed: a calendar."""
import numpy as np

from benchmark.datagen.store_sales import DATE_SK0, N_DATES

TYPES = ["int", "int", "int", "int"]


def make(rows, rng, parent=None):
    if rows != N_DATES:
        raise ValueError(f"date_dim has {N_DATES} rows, not {rows}")
    day = np.arange(N_DATES)
    doy = day % 365
    return {
        "date_sk": np.arange(DATE_SK0, DATE_SK0 + N_DATES, dtype=np.int32),
        "d_year": (1998 + day // 365).astype(np.int32),
        "d_qoy": (doy // 92 + 1).clip(1, 4).astype(np.int32),
        "d_moy": (doy // 31 + 1).clip(1, 12).astype(np.int32),
    }
