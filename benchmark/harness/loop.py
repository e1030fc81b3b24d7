"""The closed loop: every client starts its next collect() when its last
one has returned its rows.  One client runs on the caller's thread; more
run on a thread each and start together."""
from __future__ import annotations

import contextlib
import threading
import time
import traceback

import numpy as np

from benchmark.harness.stats import Window

_MAX_FAILURES = 3
_MAX_COLLECTS = 1 << 20


class _Client:
    def __init__(self, df, span):
        self.df, self.span = df, span
        self.lat = np.zeros(_MAX_COLLECTS)      # no allocation in the window
        self.results = []
        self.attempted = self.failed = self.done = 0
        self.last = None

    def run(self, start: float, deadline: float) -> None:
        clock = time.perf_counter
        self.last = start
        while self.done < _MAX_COLLECTS:
            t0 = clock()
            if t0 >= deadline:
                break
            self.attempted += 1
            try:
                with self.span():
                    rows = self.df.collect()
            except Exception:            # the run goes on and reports it
                self.failed += 1
                traceback.print_exc()
                if self.failed >= _MAX_FAILURES:
                    break
                continue
            self.last = clock()
            self.lat[self.done] = self.last - t0
            self.results.append(rows)
            self.done += 1


def closed_loop(dfs, seconds: float, rows_per_collect: int, *, span=None):
    """Collect each client's DataFrame of ``dfs`` again and again for
    ``seconds``; a collect that has started before the deadline is waited
    for and counted.  ``span`` is a context-manager factory put around
    every collect (the traced run's host span).  Returns (Window, the
    rows of every completed collect)."""
    clients = [_Client(df, span or contextlib.nullcontext) for df in dfs]
    start = time.perf_counter()
    deadline = start + seconds
    if len(clients) == 1:
        clients[0].run(start, deadline)
    else:
        threads = [threading.Thread(target=c.run, args=(start, deadline),
                                    name=f"benchmark-client-{i}")
                   for i, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    window = Window(
        latencies_s=np.concatenate([c.lat[:c.done] for c in clients]),
        elapsed_s=max(c.last for c in clients) - start,
        attempted=sum(c.attempted for c in clients),
        failed=sum(c.failed for c in clients),
        rows_per_collect=rows_per_collect)
    return window, [rows for c in clients for rows in c.results]
