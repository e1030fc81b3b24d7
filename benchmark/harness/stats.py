"""The statistics of a window, and the end-to-end metrics.

Nothing is trimmed: a rate is all the rows of all completed collects over
all the time from the window's start to the last completion, and a
percentile is taken over every collect of the window."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Window:
    """What the closed loop measured."""
    latencies_s: np.ndarray     # wall of every completed collect, in order
    elapsed_s: float            # window's start to the last completion
    attempted: int
    failed: int
    rows_per_collect: int       # input rows of the cell's fact table

    @property
    def collects(self) -> int:
        return len(self.latencies_s)


def rows_per_s(w: Window) -> float:
    return w.rows_per_collect * w.collects / w.elapsed_s


def percentile_ms(w: Window, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) over every
    collect of the window, in milliseconds."""
    return float(np.percentile(w.latencies_s, q)) * 1e3


# name in BENCHMARK.json's end_to_end -> value from (window, setup_s)
END_TO_END = {
    "rows_per_s": lambda w, setup_s: rows_per_s(w),
    "setup_s": lambda w, setup_s: setup_s,
}
