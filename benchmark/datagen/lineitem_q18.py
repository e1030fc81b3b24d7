"""TPC-H lineitem, the two columns Q18's IN-subquery reads, in dbgen's
shapes and order: ``l_orderkey`` (dbgen's sparse keys, 8 of every 32
values: order i gets ((i >> 3) << 5) | (i & 7)) repeated over its 1 to 7
lines, uniform, in non-decreasing order; ``l_quantity`` a whole number
from 1 to 50, uniform, as DECIMAL(12,2) unscaled cents.

The orders come from ``rows`` (15,000,000 at SF10's 59,986,052 rows, the
same ratio at any other count), and the last orders gain or lose lines,
within 1 to 7, until the row count is exact."""
import numpy as np

TYPES = ["long", "decimal(12,2)"]

SF10_ROWS = 59_986_052
SF10_ORDERS = 15_000_000
MAX_LINES = 7


def orders_of(rows: int) -> int:
    return max(1, rows * SF10_ORDERS // SF10_ROWS)


def sparse_key(i):
    """dbgen's mk_sparse with seq 0 for the 1-based order number ``i``."""
    return ((i >> 3) << 5) | (i & 7)


def lines_per_order(rows, rng):
    lines = rng.integers(1, MAX_LINES + 1, orders_of(rows))
    short = rows - int(lines.sum())
    # from the last order back: each takes what it can of the difference
    room = (MAX_LINES - lines) if short > 0 else (lines - 1)
    room = room[::-1]
    before = np.cumsum(room) - room
    take = np.clip(abs(short) - before, 0, room)
    lines[::-1] += np.sign(short) * take
    if lines.sum() != rows:
        raise ValueError(f"{rows} rows do not fit {len(lines)} orders "
                         f"of 1 to {MAX_LINES} lines")
    return lines


def make(rows, rng, parent=None):
    lines = lines_per_order(rows, rng)
    keys = sparse_key(np.arange(1, len(lines) + 1, dtype=np.int64))
    return {
        "l_orderkey": np.repeat(keys, lines),
        "l_quantity": rng.integers(1, 51, rows) * 100,
    }
