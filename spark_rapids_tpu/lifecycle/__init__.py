"""Query lifecycle layer (ISSUE 4): admission control, deadlines, and
cooperative cancellation — what makes N concurrent ``collect()`` calls
safe, bounded, and killable.

Reference analog: the reference plugin leans on Spark's task framework
for admission (GpuSemaphore), task kill, and resource release on task
completion (SURVEY.md §2.3); Theseus (arXiv:2508.05029) and "Rethinking
Analytical Processing in the GPU Era" (arXiv:2508.04701) both argue an
accelerator engine lives or dies on controlled concurrency and bounded
device-memory occupancy under load.  This standalone engine has no task
framework, so the lifecycle layer supplies the missing pieces:

  * context.py   — QueryContext (one per collect, in a contextvar) +
                   CancelToken, the one object every blocking layer
                   observes; QueryCancelled / QueryDeadlineExceeded /
                   QueryRejected.
  * admission.py — FIFO admission gate (spark.rapids.tpu.
                   concurrentQueries) with a bounded wait queue and
                   queue-full fast-reject.
  * watchdog.py  — one daemon thread trips queries past
                   spark.rapids.tpu.query.timeoutMs.

``query_lifecycle`` (used by ``DataFrame.collect``) ties them together:
admission BEFORE planning, deadline armed at entry, and on exit —
success, error, or mid-batch unwind — guaranteed cleanup: residual
semaphore permits released, the query's tracked spillables closed, its
shuffle registrations dropped, and the admission slot returned.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

from spark_rapids_tpu.lifecycle.context import (
    CURRENT,
    CancelToken,
    QueryCancelled,
    QueryContext,
    QueryDeadlineExceeded,
    QueryRejected,
    check_cancel,
    current,
    current_token,
)
from spark_rapids_tpu.lifecycle.admission import (
    AdmissionController,
    get_admission,
    reset_admission,
)
from spark_rapids_tpu.lifecycle import watchdog as _watchdog

active_queries = _watchdog.active_queries

_tls = threading.local()


def last_query_stats() -> Optional[dict]:
    """Lifecycle stats of the calling thread's most recent collect()
    (bench/stress harness hook): query_id, admission_wait_ns, wall_ns,
    status."""
    return getattr(_tls, "last", None)


class query_lifecycle:
    """Context manager around one ``collect()``.

    Yields the new :class:`QueryContext`, or None when the lifecycle
    layer does not apply: sql disabled (oracle runs need no admission)
    or a nested collect (the inner one shares the outer query's context,
    token, and admission slot)."""

    def __init__(self, conf):
        self._conf = conf
        self._ctx: Optional[QueryContext] = None
        self._ctl: Optional[AdmissionController] = None
        self._cv_token = None
        self._journaled = False

    def __enter__(self) -> Optional[QueryContext]:
        from spark_rapids_tpu import perfcounters as PC

        with PC.span("srt.admit"):
            return self._admit()

    def _admit(self) -> Optional[QueryContext]:
        from spark_rapids_tpu.config import (
            ADMISSION_MAX_QUEUE,
            ADMISSION_QUEUE_TIMEOUT_MS,
            CONCURRENT_QUERIES,
            QUERY_TIMEOUT_MS,
            QUERY_WATCHDOG_PERIOD_MS,
        )

        conf = self._conf
        if not conf.sql_enabled or current() is not None:
            return None
        period_s = max(float(conf.get(QUERY_WATCHDOG_PERIOD_MS)), 1.0) / 1000.0
        ctx = QueryContext(watchdog_period_s=period_s)
        # multi-tenant serving (ISSUE 19): stamp the owning tenant from
        # the session conf — a plain conf read, no serving-module call
        from spark_rapids_tpu.config import SERVING_TENANT

        ctx.tenant = str(conf.get(SERVING_TENANT) or "")
        # deadline armed and watchdog registered BEFORE the admission
        # wait: a query stuck in the queue must be deadline-trippable and
        # visible to active_queries() cancel tooling (the acquire loop
        # polls ctx.token), not just once it starts running
        timeout_ms = int(conf.get(QUERY_TIMEOUT_MS))
        if timeout_ms > 0:
            ctx.deadline_ns = time.monotonic_ns() + timeout_ms * 1_000_000
        _watchdog.register(ctx)
        limit = int(conf.get(CONCURRENT_QUERIES))
        if limit > 0:
            ctl = get_admission(limit, int(conf.get(ADMISSION_MAX_QUEUE)))
            try:
                # admission BEFORE planning: a rejected query must cost
                # the process nothing, and a queued one must not pin
                # plan state
                ctx.admission_wait_ns = ctl.acquire(
                    ctx, int(conf.get(ADMISSION_QUEUE_TIMEOUT_MS)))
            except BaseException as e:
                from spark_rapids_tpu import perfcounters as PC

                _watchdog.unregister(ctx)
                if isinstance(e, QueryCancelled):
                    PC.bump("queries_cancelled")
                # rejection raises HERE, before the telemetry collect
                # wrapper ever runs — record the overload event at the
                # only site that sees it (ISSUE 7)
                if isinstance(e, QueryRejected):
                    from spark_rapids_tpu.telemetry import context as TEL

                    hub = TEL.HUB
                    if hub is not None:
                        try:
                            hub.record_event(
                                "query_rejected",
                                query_id=ctx.query_id,
                                detail=str(e)[:300])
                        # tpulint: disable=cancel-swallow (telemetry
                        # isolation; QueryRejected re-raised below)
                        except Exception:
                            pass
                raise
            self._ctl = ctl
        self._cv_token = CURRENT.set(ctx)
        self._ctx = ctx
        # crash-consistent recovery (ISSUE 16): journal the admission so
        # a dead driver's successor can classify this query.  One
        # ambient conf check — with recovery off the journal module is
        # never imported (cProfile-pinned)
        from spark_rapids_tpu.config import RECOVERY_ENABLED

        if bool(conf.get(RECOVERY_ENABLED)):
            from spark_rapids_tpu.lifecycle import journal as _journal

            try:
                _journal.journal_admit(ctx, conf)
                self._journaled = True
            # tpulint: disable=cancel-swallow (durability isolation: a
            # journal that cannot append voids the recovery guarantee
            # for this query but must not fail its admission)
            except Exception:
                pass
        return ctx

    def __exit__(self, exc_type, exc, tb):
        ctx = self._ctx
        if ctx is None:
            return False
        from spark_rapids_tpu import perfcounters as PC

        try:
            CURRENT.reset(self._cv_token)
            _watchdog.unregister(ctx)
            if exc is not None and isinstance(exc, QueryCancelled):
                PC.bump("queries_cancelled")
            _cleanup_query(ctx)
            if self._journaled:
                from spark_rapids_tpu.lifecycle import journal as _journal

                status = ("ok" if exc_type is None else
                          "cancelled" if isinstance(exc, QueryCancelled)
                          else getattr(exc_type, "__name__", "error"))
                try:
                    _journal.journal_end(ctx, status)
                # tpulint: disable=cancel-swallow (durability isolation:
                # the end record is a GC optimization — replay treats a
                # missing one as a crash, which is the safe default)
                except Exception:
                    pass
        finally:
            if self._ctl is not None:
                self._ctl.release(ctx.tenant)
            wall_ns = time.monotonic_ns() - ctx.started_ns
            # fair-share usage feedback (ISSUE 19): charge the tenant's
            # consumed wall so long-running queries weigh against its
            # share (one module-attribute check; None when serving off)
            from spark_rapids_tpu.lifecycle import admission as _adm

            if _adm.SCHEDULER is not None:
                _adm.SCHEDULER.note_query_end(ctx.tenant, wall_ns)
            # overload governor (ISSUE 13): feed the wall EWMA the shed
            # predictor falls back on, and clear this query's
            # predicted-wall backlog entry (one ambient check)
            from spark_rapids_tpu.governor import context as _GOV

            gov = _GOV.GOVERNOR
            if gov is not None:
                gov.note_query_end(ctx.query_id, wall_ns)
            _tls.last = {
                "query_id": ctx.query_id,
                "admission_wait_ns": ctx.admission_wait_ns,
                "wall_ns": wall_ns,
                "status": ("ok" if exc_type is None else
                           getattr(exc_type, "__name__", "error")),
            }
        return False


def _cleanup_query(ctx: QueryContext) -> None:
    """Release everything the query may still hold after its exec tree
    unwound (possibly mid-batch).  Every step peeks the singleton —
    nothing is created during cleanup — and every step is idempotent."""
    # 0. query-registered cleanup hooks (ISSUE 5: the writer's staging
    #    -dir abort) — run FIRST so a cancelled mid-write query deletes
    #    its _temporary dir before anything else is torn down
    while ctx.cleanup_hooks:
        fn = ctx.cleanup_hooks.pop()
        try:
            fn()
        # tpulint: disable=cancel-swallow (cleanup-hook contract: hooks
        # are idempotent + best-effort; the query's own exception — incl.
        # a tripped token's — is re-raised by the main unwind path)
        except Exception:
            pass
    # 1. residual semaphore permit: the collect-level scope released one
    #    depth; exec code that failed between acquire and its finally can
    #    leave extra depth, which would starve every other query
    from spark_rapids_tpu.memory import semaphore as _sem

    sem = _sem.peek_semaphore()
    if sem is not None:
        sem.force_release_current_thread()
    # 2. spillable handles tracked (and not yet closed) by this query —
    #    cache handles are marked persistent and survive
    from spark_rapids_tpu.memory import spill as _spill

    fw = _spill.peek_spill_framework()
    if fw is not None:
        fw.close_owned_by(ctx.query_id)
    # 3. shuffle registrations this query's exchanges left behind
    from spark_rapids_tpu.shuffle import manager as _shuffle

    mgr = _shuffle.peek_shuffle_manager()
    if mgr is not None:
        mgr.unregister_owned(ctx.query_id)
    # 4. settle the query's resource bill (ISSUE 18) — AFTER
    #    close_owned_by swept leftover handles, so their releases land
    #    on the bill and a nonzero residual means truly-unreleased
    #    charged bytes (persistent df.cache handles excluded)
    from spark_rapids_tpu.accounting import context as _acct

    if _acct.LEDGERS is not None:
        _acct.LEDGERS.settle(ctx.query_id)


# ---------------------------------------------------------------------------
# leak reporting (conftest gate + TpuSession.close)
# ---------------------------------------------------------------------------

def leak_report_all() -> List[str]:
    """Aggregate leak report across the process singletons: unclosed
    non-persistent spillables, held/lost semaphore permits, and live
    shuffle registrations.  Empty after a well-behaved query (pinned by
    the autouse tests/conftest.py gate and the stress harness)."""
    out: List[str] = []
    from spark_rapids_tpu.memory import spill as _spill

    fw = _spill.peek_spill_framework()
    if fw is not None:
        out.extend(fw.leak_report())
    from spark_rapids_tpu.memory import semaphore as _sem

    sem = _sem.peek_semaphore()
    if sem is not None:
        out.extend(sem.leak_report())
    from spark_rapids_tpu.shuffle import manager as _shuffle

    mgr = _shuffle.peek_shuffle_manager()
    if mgr is not None:
        for sid in mgr.active_shuffles():
            out.append(f"LEAK: shuffle {sid} still registered")
    # 3b. partitions still PLACED on remote workers (ISSUE 14): a
    #     distributed exchange that unwound without its release
    #     broadcast leaves blocks pinned in another process's store
    from spark_rapids_tpu import distributed as _dist

    out.extend(_dist.leak_report())
    # 4. writer staging dirs never committed nor aborted (ISSUE 5): a
    #    leftover _temporary/<uuid> means a write unwound without its
    #    commit protocol running — visible-partial-output risk
    from spark_rapids_tpu.io import writer as _writer

    out.extend(_writer.staging_leak_report())
    # 5. recovery journal hygiene (ISSUE 16): a journaled query that
    #    never ended, or a checkpoint dir left on disk, means a real
    #    run would mis-classify at the next restart — fail the test
    from spark_rapids_tpu.lifecycle import journal as _journal

    out.extend(_journal.journal_leak_report())
    # 6. resource-bill residuals (ISSUE 18): a settled bill whose
    #    charged device bytes were never released (persistent handles
    #    excluded) is the accounting-side view of a handle leak
    from spark_rapids_tpu.accounting import context as _acct

    if _acct.LEDGERS is not None:
        out.extend(_acct.LEDGERS.leak_report())
    # 7. serving-tier hygiene (ISSUE 19): unclosed tenant sessions and
    #    result-cache fragments that outlived their session — a
    #    sys.modules peek, so a process that never enabled serving
    #    makes zero serving-module calls (the cProfile-pinned
    #    disabled-path contract)
    import sys as _sys

    srv = _sys.modules.get("spark_rapids_tpu.serving")
    if srv is not None:
        out.extend(srv.leak_report())
    return out


def reset_leaked_state() -> None:
    """Best-effort recovery after a detected leak so ONE leaky test/query
    cannot poison everything after it: close leaked handles, rebuild the
    semaphore, drop orphaned shuffle registrations."""
    from spark_rapids_tpu.memory import semaphore as _sem
    from spark_rapids_tpu.memory import spill as _spill
    from spark_rapids_tpu.shuffle import manager as _shuffle

    fw = _spill.peek_spill_framework()
    if fw is not None:
        fw.close_all(include_persistent=False)
    sem = _sem.peek_semaphore()
    if sem is not None and sem.leak_report():
        _sem.reset_semaphore()
    mgr = _shuffle.peek_shuffle_manager()
    if mgr is not None:
        for sid in mgr.active_shuffles():
            try:
                mgr.unregister_shuffle(sid)
            # tpulint: disable=cancel-swallow (leaked-state recovery in
            # tests; no query is running when this sweeps)
            except Exception:
                pass
    from spark_rapids_tpu.io import writer as _writer

    _writer.reset_leaked_staging()
    # remote placements an unregistered/leaked exchange left behind
    # (ISSUE 14) — release everywhere so one leaky test cannot pin
    # blocks in worker stores for the rest of the session
    from spark_rapids_tpu import distributed as _dist

    coord = _dist.peek_coordinator()
    if coord is not None:
        try:
            coord.release_all()
        # tpulint: disable=cancel-swallow (leaked-state recovery in
        # tests; no query is running when this sweeps)
        except Exception:
            pass
    from spark_rapids_tpu.accounting import context as _acct

    if _acct.LEDGERS is not None:
        _acct.LEDGERS.reset_residuals()
    # journal + checkpoint artifacts (ISSUE 16): purge every recovery
    # root this process touched so one leaky test's WAL cannot seed a
    # bogus "resumable" classification in the next test's replay
    from spark_rapids_tpu.lifecycle import journal as _journal

    _journal.reset_journal(purge=True)
    # serving tier (ISSUE 19): tear down leaked tenant sessions so one
    # test's unclosed session cannot hold cached batches, temp views,
    # or result fragments across the rest of the run
    import sys as _sys

    srv = _sys.modules.get("spark_rapids_tpu.serving")
    if srv is not None and srv.peek_serving() is not None:
        try:
            srv.shutdown_serving()
        # tpulint: disable=cancel-swallow (leaked-state recovery in
        # tests; no query is running when this sweeps)
        except Exception:
            pass


__all__ = [
    "CancelToken", "QueryCancelled", "QueryContext",
    "QueryDeadlineExceeded", "QueryRejected",
    "active_queries", "check_cancel", "current", "current_token",
    "get_admission", "reset_admission", "last_query_stats",
    "leak_report_all", "reset_leaked_state", "query_lifecycle",
]
