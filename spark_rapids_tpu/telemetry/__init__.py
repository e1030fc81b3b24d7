"""Always-on telemetry tier (ISSUE 7): a process-global time-series
metrics registry, a low-overhead sampler thread, per-plan-signature SLO
latency histograms, a Prometheus exporter, and an always-on failure
flight recorder with post-mortem bundles.

Reference analog: PR 3's diagnostics layer observes ONE query at a time
and is off by default; an always-on multi-tenant serving tier (ROADMAP
north star) is tuned and operated on *continuous, process-level*
signals — queue depth, HBM occupancy, cache hit rates, tail latency per
plan shape (Theseus, arXiv:2508.05029; Presto+GPU, arXiv:2606.24647).
This package is that substrate:

  context.py   — the active-hub slot (ONE ambient check on hot paths)
  registry.py  — gauges / counters / histograms, bounded sample rings
  sampler.py   — the daemon sampler thread + timeline + JSONL sink
  slo.py       — per-plan-signature latency histograms, p50/p95
  flight.py    — the always-on event ring + post-mortem bundles
  prometheus.py — Prometheus text exporter + localhost scrape endpoint

The hub is created by the first ``TpuSession`` whose conf leaves
``spark.rapids.tpu.telemetry.enabled`` true (the default) and lives for
the process; per-batch hot paths are NEVER instrumented — the flight
recorder records a handful of events per QUERY and the sampler reads
peek-only singletons on its own thread.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from spark_rapids_tpu.telemetry import context as CTX
from spark_rapids_tpu.telemetry.flight import (
    FlightRecorder,
    build_bundle,
    write_bundle,
)
from spark_rapids_tpu.telemetry.registry import MetricsRegistry
from spark_rapids_tpu.telemetry.sampler import Sampler
from spark_rapids_tpu.telemetry.slo import SloTracker, plan_signature

_LOCK = threading.Lock()

# per-reason minimum interval between post-mortem dumps: failure storms
# (a chaos sweep, a flapping stage) must not turn every error into a
# thread-stack capture
_DUMP_MIN_INTERVAL_S = 1.0


class TelemetryHub:
    """Everything the telemetry tier owns, wired together."""

    def __init__(self, conf):
        from spark_rapids_tpu.config import (
            TELEMETRY_FLIGHT_CAPACITY,
            TELEMETRY_FLIGHT_DUMP_DIR,
            TELEMETRY_FLIGHT_ENABLED,
            TELEMETRY_JSONL_DIR,
            TELEMETRY_RETENTION,
            TELEMETRY_SAMPLE_PERIOD_MS,
        )

        retention = int(conf.get(TELEMETRY_RETENTION))
        self.registry = MetricsRegistry(retention)
        self.slo = SloTracker(self.registry)
        self.flight_enabled = bool(conf.get(TELEMETRY_FLIGHT_ENABLED))
        self.flight = FlightRecorder(
            int(conf.get(TELEMETRY_FLIGHT_CAPACITY)))
        self.dump_dir: Optional[str] = conf.get(TELEMETRY_FLIGHT_DUMP_DIR)
        self.postmortems: deque = deque(maxlen=8)
        self._dumped_qids: "OrderedDict[str, float]" = OrderedDict()
        self._last_dump_ts: Dict[str, float] = {}
        self._dump_lock = threading.Lock()
        self.sampler = Sampler(
            self,
            period_s=float(conf.get(TELEMETRY_SAMPLE_PERIOD_MS)) / 1000.0,
            retention=retention,
            jsonl_dir=conf.get(TELEMETRY_JSONL_DIR))
        if float(conf.get(TELEMETRY_SAMPLE_PERIOD_MS)) > 0:
            self.sampler.start()
        self._http_server = None
        self.http_port: Optional[int] = None
        self.ensure_http(conf)

    # -- endpoint --------------------------------------------------------
    def ensure_http(self, conf) -> None:
        from spark_rapids_tpu.config import TELEMETRY_PORT

        port = int(conf.get(TELEMETRY_PORT))
        if port <= 0 or self._http_server is not None:
            return
        from spark_rapids_tpu.telemetry.prometheus import start_http

        self._http_server, self.http_port = start_http(self, port)

    # -- the per-query observation (session.DataFrame.collect) ----------
    def observed_collect(self, df, qctx):
        """Run ``df._collect_impl`` under flight/SLO observation.  Only
        lifecycle-managed top-level queries land here (``qctx`` is not
        None); the cost is a handful of dict appends + one plan walk per
        QUERY — nothing per batch."""
        from spark_rapids_tpu.config import TELEMETRY_SLO_TARGET_P95_MS
        from spark_rapids_tpu.lifecycle.context import (
            QueryCancelled,
            QueryDeadlineExceeded,
        )

        from spark_rapids_tpu import perfcounters as PC

        # srt.observe: this wrapper's own work, not the collect it wraps
        qid = qctx.query_id
        with PC.span("srt.observe"):
            self.record_event("query_start", query_id=qid,
                              thread=threading.get_ident())
        t0 = time.perf_counter_ns()
        try:
            rows = df._collect_impl(qctx)
        except BaseException as e:
            wall = time.perf_counter_ns() - t0
            status = type(e).__name__
            with PC.span("srt.observe"):
                self._finish(df, qid, wall, status,
                             float(df.session.conf.get(
                                 TELEMETRY_SLO_TARGET_P95_MS)),
                             tenant=getattr(qctx, "tenant", ""))
            # QueryRejected never lands here: admission raises inside
            # query_lifecycle.__enter__, before this wrapper runs — the
            # lifecycle layer records the query_rejected flight event
            if isinstance(e, QueryDeadlineExceeded):
                self.postmortem("deadline_trip", query_id=qid,
                                detail=str(e))
            elif isinstance(e, QueryCancelled):
                self.postmortem("query_cancelled", query_id=qid,
                                detail=str(e))
            else:
                self.postmortem("collect_error", query_id=qid,
                                detail=f"{type(e).__name__}: {e}")
            raise
        wall = time.perf_counter_ns() - t0
        with PC.span("srt.observe"):
            self._finish(df, qid, wall, "ok",
                         float(df.session.conf.get(
                             TELEMETRY_SLO_TARGET_P95_MS)),
                         tenant=getattr(qctx, "tenant", ""))
        return rows

    def _finish(self, df, qid: str, wall_ns: int, status: str,
                target_p95_ms: float, tenant: str = "") -> None:
        sig = ""
        cached = getattr(df, "_plan_cache", None)
        if cached is not None:
            from spark_rapids_tpu.exec.base import TpuExec

            root = cached[1]
            if isinstance(root, TpuExec):
                sig = plan_signature(root)
        # per-tenant SLO sub-series (ISSUE 19): the serving tier's
        # starved-tenant pin reads hub.slo.p95_ms(tenant_label(t))
        violated = self.slo.observe(sig, wall_ns, status, target_p95_ms,
                                    tenant=tenant)
        if violated:
            from spark_rapids_tpu import perfcounters as PC

            PC.bump("slo_violations")
            self.record_event("slo_violation", query_id=qid,
                              wall_ms=round(wall_ns / 1e6, 3),
                              target_p95_ms=target_p95_ms, plan_sig=sig)
        self.record_event("query_end", query_id=qid, status=status,
                          wall_ms=round(wall_ns / 1e6, 3), plan_sig=sig)

    # -- flight ring -----------------------------------------------------
    def record_event(self, kind: str, **fields) -> None:
        if self.flight_enabled:
            self.flight.record(kind, **fields)

    # -- failure hooks ---------------------------------------------------
    def deadline_tripped(self, ctx) -> None:
        """Watchdog hook: dump WHILE the offending query's thread is
        still blocked, so the bundle's stack shows where it is stuck
        (at collect-raise time the stack has already unwound)."""
        self.record_event("deadline_trip", query_id=ctx.query_id)
        self.postmortem("deadline_trip", query_id=ctx.query_id,
                        offender_ident=ctx.owner_thread,
                        detail="watchdog tripped "
                               "spark.rapids.tpu.query.timeoutMs")

    def breaker_opened(self, key, reason: str) -> None:
        self.record_event("breaker_open", op=key[0], fingerprint=key[1],
                          reason=str(reason)[:300])
        self.postmortem("breaker_open",
                        detail=f"{key[0]}[{key[1]}]: {reason}")

    def postmortem(self, reason: str, query_id: str = "",
                   detail: str = "",
                   offender_ident: Optional[int] = None,
                   force: bool = False,
                   claim_query: bool = True,
                   extra: Optional[Dict[str, Any]] = None
                   ) -> Optional[Dict[str, Any]]:
        """Build (and optionally persist) one post-mortem bundle.
        Deduped per query (a deadline trip dumps from the watchdog; the
        same query's collect unwinding must not dump again) and
        rate-limited per reason against failure storms.

        ``claim_query=False`` (the stall detector, ISSUE 12): the dump
        neither consumes nor honors the per-query dedup slot — a stall
        bundle must not suppress the later deadline-trip bundle for the
        same query (nor be suppressed by it), and a re-armed second
        stall episode may dump again; the per-reason rate limit is the
        storm guard on this path."""
        if not self.flight_enabled:
            return None
        now = time.monotonic()
        with self._dump_lock:
            if not force:
                if claim_query and query_id \
                        and query_id in self._dumped_qids:
                    return None
                last = self._last_dump_ts.get(reason, 0.0)
                if now - last < _DUMP_MIN_INTERVAL_S:
                    return None
            self._last_dump_ts[reason] = now
            if query_id and claim_query:
                self._dumped_qids[query_id] = now
                while len(self._dumped_qids) > 256:
                    self._dumped_qids.popitem(last=False)
        bundle = build_bundle(self.flight, reason, query_id=query_id,
                              detail=detail,
                              offender_ident=offender_ident,
                              extra=extra)
        if self.dump_dir:
            bundle["path"] = write_bundle(bundle, self.dump_dir)
        self.postmortems.append(bundle)
        from spark_rapids_tpu import perfcounters as PC

        PC.bump("postmortem_dumps")
        return bundle

    def reset_dump_limits(self) -> None:
        """Test hook: forget dedupe/rate-limit state."""
        with self._dump_lock:
            self._dumped_qids.clear()
            self._last_dump_ts.clear()

    # -- surfaces --------------------------------------------------------
    def export(self) -> str:
        from spark_rapids_tpu.telemetry.prometheus import render_prometheus

        return render_prometheus(self)

    def timeline_snapshot(self) -> List[Dict]:
        return self.sampler.timeline_snapshot()

    def slo_summary(self) -> Dict[str, Dict[str, float]]:
        return self.slo.summary()

    def shutdown(self) -> None:
        self.sampler.stop()
        if self._http_server is not None:
            try:
                self._http_server.shutdown()
                self._http_server.server_close()
            except Exception:
                pass
            self._http_server = None
            self.http_port = None


# ---------------------------------------------------------------------------
# module-level lifecycle
# ---------------------------------------------------------------------------

def maybe_configure(conf) -> Optional[TelemetryHub]:
    """Idempotent process-global start (called by TpuSession.__init__):
    the FIRST enabling conf builds the hub; later sessions reuse it (a
    later conf can still add the HTTP endpoint).  Returns None when the
    conf disables telemetry."""
    from spark_rapids_tpu.config import TELEMETRY_ENABLED

    if not conf.get(TELEMETRY_ENABLED):
        return None
    with _LOCK:
        if CTX.HUB is None:
            CTX.HUB = TelemetryHub(conf)
        else:
            CTX.HUB.ensure_http(conf)
        return CTX.HUB


def get_hub() -> Optional[TelemetryHub]:
    return CTX.HUB


def export() -> str:
    """Prometheus text of the active hub ('' when telemetry is off)."""
    hub = CTX.HUB
    return hub.export() if hub is not None else ""


def timeline() -> List[Dict]:
    hub = CTX.HUB
    return hub.timeline_snapshot() if hub is not None else []


def slo_summary() -> Dict[str, Dict[str, float]]:
    hub = CTX.HUB
    return hub.slo_summary() if hub is not None else {}


def last_postmortem() -> Optional[Dict[str, Any]]:
    hub = CTX.HUB
    if hub is None or not hub.postmortems:
        return None
    return hub.postmortems[-1]


def flush() -> None:
    """Flush the JSONL sink (TpuSession.close)."""
    hub = CTX.HUB
    if hub is not None:
        hub.sampler.flush()


def shutdown() -> None:
    """Stop the sampler + endpoint and clear the hub slot (tests /
    process teardown); the next enabling TpuSession rebuilds."""
    with _LOCK:
        hub = CTX.HUB
        CTX.HUB = None
    if hub is not None:
        hub.shutdown()


__all__ = [
    "TelemetryHub", "export", "flush", "get_hub", "last_postmortem",
    "maybe_configure", "plan_signature", "shutdown", "slo_summary",
    "timeline",
]
