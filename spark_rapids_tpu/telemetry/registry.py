"""Time-series metrics registry — gauges, counters, histograms with
bounded ring-buffer retention.

Reference analog: the scheduler/data-movement telemetry Theseus
(arXiv:2508.05029) treats as the substrate an accelerated SQL service is
operated on, and the metrics surface Presto's accelerator integration
exports to its fleet dashboards (arXiv:2606.24647).  The registry is
deliberately dependency-free (no prometheus_client): series live in
plain dicts, each keeping a bounded ring of ``(unix_ts, value)`` samples
(``spark.rapids.tpu.telemetry.retention`` points) so a long-running
process holds a sliding window, never an unbounded history.

Three series kinds:

* **gauge**   — instantaneous level (queue depth, HBM bytes in use);
  each sample overwrites "current" and appends to the ring.
* **counter** — monotonic cumulative count mirrored from
  ``perfcounters`` (bytes moved, cache hits); consumers diff samples
  for rates.
* **histogram** — fixed-bucket latency distribution with per-label
  (plan-signature) sub-series; p50/p95 are estimated by linear
  interpolation inside the winning bucket, which is exact enough for
  SLO tracking and requires no per-observation storage.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

# latency histogram upper bounds, milliseconds (the +Inf bucket is
# implicit); spans sub-ms cached-plan replays through minute-long
# cold compiles
DEFAULT_LATENCY_BUCKETS_MS: Tuple[float, ...] = (
    1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0, 300000.0)


class Series:
    """One gauge/counter time series with a bounded sample ring.

    ``labels`` (ISSUE 15): an optional sorted tuple of ``(key, value)``
    pairs — per-worker federated series (``worker="w0"``) export as
    Prometheus-labeled samples of one family instead of name-mangled
    singletons, so a fleet dashboard can aggregate across workers."""

    __slots__ = ("name", "kind", "help", "value", "ring", "labels")

    def __init__(self, name: str, kind: str, help_: str, retention: int,
                 labels: Optional[Tuple[Tuple[str, str], ...]] = None):
        self.name = name
        self.kind = kind            # "gauge" | "counter"
        self.help = help_
        self.value: float = 0.0
        self.ring: deque = deque(maxlen=max(int(retention), 1))
        self.labels = labels

    def record(self, value: float, ts: Optional[float] = None) -> None:
        self.value = float(value)
        self.ring.append((ts if ts is not None else time.time(),
                          float(value)))


class _HistShard:
    """Per-label bucket counts for one histogram."""

    __slots__ = ("counts", "sum", "count", "max")

    def __init__(self, n_buckets: int):
        self.counts = [0] * (n_buckets + 1)   # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0
        self.max = 0.0


class Histogram:
    """Fixed-bucket histogram with optional per-label sub-series (the
    label is the plan signature for query-latency SLOs).  Thread-safe on
    its own leaf lock: observers (collect exits) and readers (SLO
    summaries, Prometheus scrapes) arrive under DIFFERENT outer locks,
    and a scrape must never see a shard whose bucket cumsum disagrees
    with its count."""

    def __init__(self, name: str, help_: str,
                 buckets: Tuple[float, ...] = DEFAULT_LATENCY_BUCKETS_MS,
                 label_name: str = ""):
        self.name = name
        self.help = help_
        self.buckets = tuple(sorted(buckets))
        self.label_name = label_name
        self._lock = threading.Lock()
        self._shards: Dict[str, _HistShard] = {}

    def observe(self, value: float, label: str = "") -> None:
        with self._lock:
            sh = self._shards.get(label)
            if sh is None:
                sh = self._shards[label] = _HistShard(len(self.buckets))
            i = 0
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    break
            else:
                i = len(self.buckets)
            sh.counts[i] += 1
            sh.sum += value
            sh.count += 1
            if value > sh.max:
                sh.max = value

    def labels(self) -> List[str]:
        with self._lock:
            return list(self._shards)

    def snapshot_shards(self) -> Dict[str, Dict[str, object]]:
        """Consistent per-label copies for the exporter: counts list,
        sum, count, max captured under one lock acquisition."""
        with self._lock:
            return {lbl: {"counts": list(sh.counts), "sum": sh.sum,
                          "count": sh.count, "max": sh.max}
                    for lbl, sh in self._shards.items()}

    def _quantile_locked(self, q: float, sh: _HistShard) -> float:
        if sh.count == 0:
            return 0.0
        target = q * sh.count
        cum = 0
        lo = 0.0
        for i, ub in enumerate(self.buckets):
            c = sh.counts[i]
            if cum + c >= target and c:
                frac = (target - cum) / c
                # clamp to the observed max: interpolation inside the
                # winning bucket must not report a latency no query had
                return min(lo + frac * (ub - lo), sh.max)
            cum += c
            lo = ub
        return sh.max                          # landed in the +Inf bucket

    def quantile(self, q: float, label: str = "") -> float:
        """Bucket-interpolated quantile estimate (0 when empty)."""
        with self._lock:
            sh = self._shards.get(label)
            return 0.0 if sh is None else self._quantile_locked(q, sh)

    def stats(self, label: str = "") -> Dict[str, float]:
        with self._lock:
            sh = self._shards.get(label)
            if sh is None:
                return {"count": 0, "sum": 0.0, "max": 0.0,
                        "p50": 0.0, "p95": 0.0}
            return {"count": sh.count, "sum": sh.sum, "max": sh.max,
                    "p50": self._quantile_locked(0.50, sh),
                    "p95": self._quantile_locked(0.95, sh)}


class MetricsRegistry:
    """Process-global registry: get-or-create series by name, record
    samples, and expose snapshots to the exporter / JSONL sink /
    timeline consumers.  All mutation is under one lock — the sampler
    ticks at 100s-of-ms cadence and observations are per-query, so
    contention is negligible."""

    def __init__(self, retention: int = 720):
        self.retention = max(int(retention), 1)
        self._lock = threading.Lock()
        self._series: Dict[str, Series] = {}
        # labeled sub-series keyed (family name, sorted label tuple) —
        # the per-worker federated metrics (ISSUE 15)
        self._labeled: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                            Series] = {}
        self._hists: Dict[str, Histogram] = {}

    # -- series ----------------------------------------------------------
    def gauge(self, name: str, help_: str = "") -> Series:
        return self._get(name, "gauge", help_)

    def counter(self, name: str, help_: str = "") -> Series:
        return self._get(name, "counter", help_)

    def _get(self, name: str, kind: str, help_: str) -> Series:
        with self._lock:
            s = self._series.get(name)
            if s is None:
                s = self._series[name] = Series(name, kind, help_,
                                                self.retention)
            return s

    def record(self, name: str, value: float, kind: str = "gauge",
               help_: str = "", ts: Optional[float] = None) -> None:
        s = self._get(name, kind, help_)
        with self._lock:
            s.record(value, ts)

    def record_many(self, kind: str, values: Dict[str, float],
                    ts: Optional[float] = None) -> None:
        """One lock acquisition for a whole sampler tick."""
        ts = ts if ts is not None else time.time()
        with self._lock:
            for name, v in values.items():
                s = self._series.get(name)
                if s is None:
                    s = self._series[name] = Series(name, kind, "",
                                                    self.retention)
                s.record(v, ts)

    def record_labeled(self, name: str, value: float,
                       labels: Dict[str, str], kind: str = "gauge",
                       ts: Optional[float] = None) -> None:
        """Record one sample of a LABELED sub-series (get-or-create).
        One family may hold many label sets; the exporter emits them as
        ``srt_<name>{k="v",...}`` samples under one TYPE header."""
        key = (name, tuple(sorted((str(k), str(v))
                                  for k, v in labels.items())))
        self.record_labeled_many(kind, {key: float(value)}, ts)

    def record_labeled_many(self, kind: str,
                            values: Dict[Tuple[str,
                                               Tuple[Tuple[str, str],
                                                     ...]], float],
                            ts: Optional[float] = None) -> None:
        """One lock acquisition for a whole sampler tick's worth of
        labeled samples (keys are (family, sorted label tuple))."""
        ts = ts if ts is not None else time.time()
        with self._lock:
            for key, v in values.items():
                s = self._labeled.get(key)
                if s is None:
                    s = self._labeled[key] = Series(
                        key[0], kind, "", self.retention, labels=key[1])
                s.record(float(v), ts)

    def labeled_items(self) -> List[Series]:
        with self._lock:
            return list(self._labeled.values())

    def histogram(self, name: str, help_: str = "",
                  buckets: Tuple[float, ...] = DEFAULT_LATENCY_BUCKETS_MS,
                  label_name: str = "") -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(name, help_, buckets,
                                                  label_name)
            return h

    def observe(self, name: str, value: float, label: str = "") -> None:
        # the histogram carries its own leaf lock
        self.histogram(name).observe(value, label)

    # -- snapshots -------------------------------------------------------
    def snapshot(self) -> Dict[str, Dict]:
        """Current values of every series (no rings) + histogram stats —
        the JSONL sink's per-tick record shape."""
        with self._lock:
            out = {"gauges": {}, "counters": {}, "histograms": {},
                   "labeled": {}}
            for s in self._series.values():
                out["gauges" if s.kind == "gauge"
                    else "counters"][s.name] = s.value
            for s in self._labeled.values():
                lbl = ",".join(f'{k}="{v}"' for k, v in (s.labels or ()))
                out["labeled"].setdefault(s.name, {})[lbl] = s.value
            for h in self._hists.values():
                out["histograms"][h.name] = {
                    (lbl or ""): h.stats(lbl) for lbl in h.labels()}
            return out

    def series_items(self) -> List[Series]:
        with self._lock:
            return list(self._series.values())

    def hist_items(self) -> List[Histogram]:
        with self._lock:
            return list(self._hists.values())
