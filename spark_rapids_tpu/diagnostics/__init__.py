"""Query diagnostics layer (ISSUE 3): per-operator spans, a structured
JSONL event log, a Chrome-trace/Perfetto exporter, and offline report
tooling.

Reference analog: the reference plugin's telemetry stack — GpuExec
metrics in the SQL UI (``spark.rapids.sql.metrics.level``),
GpuTaskMetrics per task, and the spark-rapids-tools profiler over event
logs (SURVEY.md §5.5, L8).  The *counts* (launches, host syncs, D2H
bytes) are identical on every backend and say where a query's wall time
can go, so the recorder's core invariant is exact counter attribution:
per-operator deltas (+ the query-level bucket) sum to the process-global
``perfcounters.since()`` deltas over the query window.

This ``__init__`` is deliberately lazy: the hot paths import only
``diagnostics.context`` (one ambient check on the disabled path), and
everything heavier loads on first enabled query.

Layout:
  context.py   — the active-recorder slot + contextvar current operator
  recorder.py  — QueryDiagnostics (spans, events, attribution)
  sinks.py     — JSONL event log + Chrome-trace/Perfetto export
  report.py    — offline aggregation (tools/profile_report.py) and
                 explain("analyze") rendering
"""
from __future__ import annotations

import sys
import threading
from typing import Optional

_SCOPE_LOCK = threading.Lock()
_WARNED = [False]


def _stamp_unrecorded(root, keep_qid=None) -> None:
    """Mark a tree that is about to execute WITHOUT a recorder while some
    OTHER query's recorder is (or may become) active: ``begin_op`` sees
    the foreign ownership stamp and runs the span unrecorded, instead of
    lazily registering the tree as ``+N`` runtime ops and interleaving a
    concurrent query's spans into the active query's log/trace (ISSUE 8
    satellite: per-query span trees must not interleave).

    ``keep_qid``: nodes already stamped with the ACTIVE recorder's query
    id are left untouched — two threads collecting the SAME DataFrame
    share one cached exec tree, and the losing collect must not evict
    the winner's registration (that would silently truncate the
    recorded query's attribution mid-flight)."""
    from spark_rapids_tpu.exec.base import TpuExec

    def walk(node):
        if not (keep_qid is not None
                and getattr(node, "_diag_qid", None) == keep_qid):
            node._diag_qid = "(unrecorded)"
            node._diag_path = None
            for inner in node.inner_execs():
                inner._diag_qid = "(unrecorded)"
                inner._diag_path = None
        for c in node.children:
            if isinstance(c, TpuExec):
                walk(c)

    walk(root)


class query_scope:
    """Context manager installing a QueryDiagnostics recorder around one
    query execution (used by ``DataFrame.collect``).  Yields the recorder
    or None when diagnostics are disabled — or when another query's
    recorder is already active (one recorder per process; the concurrent
    query runs unrecorded rather than corrupting the first's log).

    ``on_finish`` (optional): called with the finished recorder after
    ``finish()`` computed the operator summaries but BEFORE the sinks
    flush (so it may still append, e.g. the profiling layer's
    ``cost_model`` record) — its failures never fail the query."""

    def __init__(self, conf, root, plan_text: str = "", on_finish=None):
        self._conf = conf
        self._root = root
        self._plan_text = plan_text
        self._on_finish = on_finish
        self.diag = None

    def __enter__(self):
        from spark_rapids_tpu.config import (
            DIAGNOSTICS_ENABLED,
            DIAGNOSTICS_MAX_EVENTS,
            METRICS_LEVEL,
        )
        from spark_rapids_tpu.diagnostics import context as CTX

        if not self._conf.get(DIAGNOSTICS_ENABLED):
            # another session's recorder is live: this undiagnosed
            # query's spans must not land in its log as +N ops.  Only
            # then — the disabled-path contract stays one conf read +
            # one ambient check per collect (a recorder installed AFTER
            # this check can still briefly absorb spans; the common
            # overlap, recorder-first, is covered)
            rec = CTX.RECORDER
            if rec is not None:
                _stamp_unrecorded(self._root, keep_qid=rec.query_id)
            return None
        with _SCOPE_LOCK:
            if CTX.RECORDER is not None:
                if not _WARNED[0]:
                    _WARNED[0] = True
                    print("spark_rapids_tpu.diagnostics: a recorder is "
                          "already active; concurrent query runs "
                          "unrecorded", file=sys.stderr)
                # under _SCOPE_LOCK the active recorder cannot change:
                # keep_qid exactly protects a concurrently-recorded
                # collect of the SAME DataFrame's shared exec tree
                _stamp_unrecorded(self._root,
                                  keep_qid=CTX.RECORDER.query_id)
                return None
            from spark_rapids_tpu.diagnostics.recorder import (
                QueryDiagnostics,
                next_query_id,
            )

            # adopt the lifecycle-minted cluster trace id (ISSUE 15) so
            # the event-log header, the TKD1 frame stamps, and the
            # worker-span merge below all share one key
            from spark_rapids_tpu.lifecycle.context import current

            ctx = current()
            diag = QueryDiagnostics(
                next_query_id(),
                metrics_level=self._conf.get(METRICS_LEVEL),
                plan_text=self._plan_text,
                max_events=int(self._conf.get(DIAGNOSTICS_MAX_EVENTS)),
                trace_id=getattr(ctx, "trace_id", "") if ctx is not None
                else "")
            diag.register_root(self._root)
            # install + baseline snapshot atomically under the counter
            # lock (counter writes attribute under the same lock), so no
            # bump can land in the global window without also reaching
            # the recorder — the exact-sum invariant's other half; see
            # QueryDiagnostics.finish
            from spark_rapids_tpu import perfcounters as PC

            with PC._LOCK:
                diag.snap0 = dict(PC.COUNTERS)
                CTX.RECORDER = diag
            self.diag = diag
        return diag

    def __exit__(self, exc_type, exc, tb):
        if self.diag is None:
            return False
        from spark_rapids_tpu.diagnostics import context as CTX

        try:
            self.diag.finish(self._root,
                             status="ok" if exc_type is None else
                             f"error:{getattr(exc_type, '__name__', '?')}")
        finally:
            with _SCOPE_LOCK:
                if CTX.RECORDER is self.diag:
                    CTX.RECORDER = None
        if self._on_finish is not None:
            try:
                self._on_finish(self.diag)
            except Exception as e:
                print("spark_rapids_tpu.diagnostics: finish hook "
                      f"failed: {e}", file=sys.stderr)
        self._merge_worker_spans()
        self._write_sinks()
        return False

    def _merge_worker_spans(self) -> None:
        """Fold worker-side spans for this query's trace id into the
        finished log (ISSUE 15) so the event log and Chrome trace are
        the MERGED cross-process record.  The coordinator is peeked via
        sys.modules — the in-process path (distributed never imported
        or never built) makes zero calls into distributed modules, the
        cProfile pin in tests/test_cluster_observability.py holds this.
        ALIVE workers are DUMPed live first so the merge does not stop
        at the last heartbeat; failures never fail the query."""
        dist_mod = sys.modules.get("spark_rapids_tpu.distributed")
        coord = getattr(dist_mod, "_coordinator", None) \
            if dist_mod is not None else None
        if coord is None or not self.diag.trace_id \
                or not getattr(coord, "trace_enabled", False):
            return
        if not self.diag.total.get("dist_blocks_shipped"):
            return   # this query never touched the worker tier
        try:
            views = coord.collect_trace(self.diag.trace_id,
                                        pull_live=True)
            merged = self.diag.record_worker_spans(views)
            if merged:
                from spark_rapids_tpu import perfcounters as PC

                PC.bump_unattributed("dist_worker_spans_merged", merged)
        except Exception as e:   # observability must never fail a query
            print("spark_rapids_tpu.diagnostics: worker-span merge "
                  f"failed: {e}", file=sys.stderr)

    def _write_sinks(self) -> None:
        """Atomic per-query flush of the configured sinks; sink I/O
        failures never fail the query."""
        from spark_rapids_tpu.config import (
            DIAGNOSTICS_EVENT_LOG_DIR,
            DIAGNOSTICS_MAX_FILES,
            DIAGNOSTICS_TRACE_DIR,
        )

        max_files = int(self._conf.get(DIAGNOSTICS_MAX_FILES))
        log_dir = self._conf.get(DIAGNOSTICS_EVENT_LOG_DIR)
        trace_dir = self._conf.get(DIAGNOSTICS_TRACE_DIR)
        try:
            if log_dir:
                from spark_rapids_tpu.diagnostics.sinks import write_event_log

                write_event_log(self.diag, log_dir, max_files)
            if trace_dir:
                from spark_rapids_tpu.diagnostics.sinks import (
                    write_chrome_trace,
                )

                write_chrome_trace(self.diag, trace_dir, max_files)
        except Exception as e:   # a sink failure must never fail the query
            print(f"spark_rapids_tpu.diagnostics: sink write failed: {e}",
                  file=sys.stderr)
            return
        if self.diag.event_log_path or self.diag.trace_path:
            # the flushed file is now the authoritative copy; dropping
            # the in-memory duplicate keeps a bench sweep's retained
            # _last_diag recorders from pinning up to maxEvents dicts
            # each (explain("analyze") reads ops/n_events, not events)
            with self.diag._lock:
                self.diag.events = []
