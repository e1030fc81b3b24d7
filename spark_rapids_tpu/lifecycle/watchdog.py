"""The deadline watchdog — ONE daemon thread trips expired queries
(alive only while a query is registered).

Every lifecycle-managed query registers here for the duration of its
collect(); the watchdog scans the registry every
``spark.rapids.tpu.query.watchdogPeriodMs`` (the minimum across active
queries) and trips the CancelToken of any query past its deadline with
:class:`QueryDeadlineExceeded`.  Trip + event-based backoff wakeups +
50ms wait-slice polling together bound the abort latency of a blocked
query at roughly 2x the watchdog period.

The registry is also the process's view of in-flight queries
(:func:`active_queries`) — what a stress harness or an operator console
uses to find and cancel a wedged query.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

from spark_rapids_tpu.lifecycle.context import (
    QueryContext,
    QueryDeadlineExceeded,
)

_COND = threading.Condition()
_ACTIVE: "set[QueryContext]" = set()
_THREAD: Optional[threading.Thread] = None


def register(ctx: QueryContext) -> None:
    global _THREAD
    with _COND:
        _ACTIVE.add(ctx)
        if _THREAD is None or not _THREAD.is_alive():
            _THREAD = threading.Thread(
                target=_run, name="srt-query-watchdog", daemon=True)
            _THREAD.start()
        _COND.notify_all()


def unregister(ctx: QueryContext) -> None:
    with _COND:
        _ACTIVE.discard(ctx)
        _COND.notify_all()


def active_queries() -> List[QueryContext]:
    """Snapshot of in-flight lifecycle-managed queries."""
    with _COND:
        return list(_ACTIVE)


def _run() -> None:
    global _THREAD
    from spark_rapids_tpu import perfcounters as PC

    while True:
        with _COND:
            targets = list(_ACTIVE)
            if not targets:
                # nothing to watch: the thread ends, and the next
                # register() (same lock) starts a fresh one — an idle
                # process keeps no watchdog thread alive
                _THREAD = None
                return
            period = min(c.watchdog_period_s for c in targets)
        now = time.monotonic_ns()
        for ctx in targets:
            if ctx.deadline_expired(now) and not ctx.token.cancelled:
                over_ms = (now - ctx.deadline_ns) / 1e6
                if ctx.token.trip(
                        QueryDeadlineExceeded,
                        f"{ctx.query_id} exceeded "
                        f"spark.rapids.tpu.query.timeoutMs "
                        f"(deadline passed {over_ms:.0f}ms ago)"):
                    PC.bump("deadline_trips")
                    # Flight recorder (ISSUE 7): dump the post-mortem
                    # NOW, while the offending query's thread is still
                    # blocked wherever it is stuck — its stack is the
                    # bundle's whole point, and it unwinds as soon as
                    # the cooperative cancel is noticed
                    from spark_rapids_tpu.telemetry import context as TEL

                    hub = TEL.HUB
                    if hub is not None:
                        try:
                            hub.deadline_tripped(ctx)
                        # tpulint: disable=cancel-swallow (telemetry
                        # isolation: a flight-recorder failure must not
                        # break the watchdog loop)
                        except Exception:
                            pass
        # Progress stall scan (ISSUE 12): one ambient attribute read
        # per period; with a live tracker installed, flag every query
        # whose progress.stallMs elapsed with no operator advance —
        # query_stall event + stalls_detected + a post-mortem naming
        # the stuck operator.  scan_stalls never raises.
        from spark_rapids_tpu.progress import context as _PROG

        trk = _PROG.TRACKER
        if trk is not None:
            trk.scan_stalls(time.monotonic_ns())
        with _COND:
            _COND.wait(max(period, 0.005))
