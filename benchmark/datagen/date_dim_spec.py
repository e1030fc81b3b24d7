"""TPC-DS date_dim at the specification's size, the four columns the
star join reads: 73,049 rows at every scale factor (table 3-2), one a day
from 1900-01-02 (``d_date_sk`` 2415022) to 2100-01-01 (2488070), year,
quarter and month from the real calendar, leap years included.  The same
for every seed: a calendar.  Never null."""
import numpy as np

DATE_SK0 = 2_415_022            # d_date_sk of 1900-01-02
N_DATES = 73_049
DAY0 = np.datetime64("1900-01-02", "D")

TYPES = ["int", "int", "int", "int"]


def make(rows, rng, parent=None):
    if rows != N_DATES:
        raise ValueError(f"date_dim has {N_DATES} rows, not {rows}")
    day = DAY0 + np.arange(N_DATES)
    month = day.astype("datetime64[M]")
    moy = (month - day.astype("datetime64[Y]")).astype(np.int32) + 1
    return {
        "date_sk": np.arange(DATE_SK0, DATE_SK0 + N_DATES, dtype=np.int32),
        "d_year": (month.astype("datetime64[Y]").astype(np.int32)
                   + 1970).astype(np.int32),
        "d_qoy": ((moy - 1) // 3 + 1).astype(np.int32),
        "d_moy": moy,
    }
