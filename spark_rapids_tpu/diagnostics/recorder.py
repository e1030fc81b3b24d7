"""QueryDiagnostics — the per-query span/event recorder.

Reference analog: GpuTaskMetrics + the Spark event log (SURVEY.md §5.5):
the reference surfaces per-operator metrics in the SQL UI and writes an
event log the spark-rapids-tools profiler mines offline.  Here one
recorder is active per query (installed by ``diagnostics.query_scope``
around ``DataFrame.collect``); every instrumented site — jit launches
(``perfcounters.tpu_jit``), logical host syncs (``sync_event`` and the
scalar dunders), compile-cache hits/misses (``compilecache.registry``),
inline/AOT compiles, and resilience events (``resilience/domain.py``) —
records an event tagged with the contextvar-scoped current operator, and
every perf-counter bump is attributed to that operator's delta bucket.

The invariant the event log is built around: for any counter key, the
per-operator deltas (including the ``""`` query-level bucket for work no
operator claimed — plan-time compiles, background pool work, shuffle
helper threads) sum EXACTLY to the process-global ``perfcounters.since``
delta over the recorder's window.  tests/test_diagnostics.py pins this.

Event levels honor ``spark.rapids.sql.metrics.level``:

* ESSENTIAL — operator summaries, resilience events, query_start/end.
* MODERATE  — + launches, logical host syncs, compiles, cache hits/misses.
* DEBUG     — + one span per operator batch pull (``op_batch``).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu.diagnostics import context as CTX

ESSENTIAL, MODERATE, DEBUG = 0, 1, 2
_LEVELS = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE, "DEBUG": DEBUG}

# Event schema (golden — tests/test_diagnostics.py validates recorded
# logs against it and docs/diagnostics.md must document every type).
# Every event also carries: ev, ts_ns, op (the attributed operator path,
# "" when no operator context was active).
EVENT_SCHEMA: Dict[str, List[str]] = {
    "query_start": ["query_id", "trace_id", "started_at",
                    "metrics_level", "plan"],
    "launch": ["dur_ns", "compiled"],
    "compile": ["mode", "dur_ns", "label"],
    "sync": ["kind", "dur_ns", "bytes"],
    "cache": ["hit", "label"],
    "resilience": ["kind", "op_name", "detail"],
    "lifecycle": ["kind", "detail", "dur_ns"],
    "io_fault": ["kind", "path", "fmt", "detail"],
    "scan_prefetch": ["depth", "batches", "overlapped_bytes", "stall_ns"],
    "ici_shuffle": ["stage", "n_dev", "rows", "bytes", "dur_ns"],
    "governor": ["action", "state", "prev", "pressure", "detail"],
    "distributed": ["kind", "worker_id", "detail", "n_workers",
                    "n_partitions"],
    "worker_telemetry": ["worker_id", "blocks", "bytes", "mem_used",
                         "counters"],
    "recovery": ["kind", "fp", "detail", "n"],
    "worker_span": ["worker_id", "kind", "trace", "span", "exch",
                    "pid", "seq", "bytes", "dur_ns"],
    "query_stall": ["query_id", "path", "name", "stalled_ms", "detail"],
    "progress": ["query_id", "pct", "eta_ns", "stalls", "background"],
    "op_batch": ["path", "batch", "rows", "dur_ns"],
    "operator": ["path", "name", "describe", "op_class", "fp", "wall_ns",
                 "self_wall_ns", "batches", "rows", "counters", "metrics",
                 "fallback"],
    "cost_model": ["hits", "misses", "predicted_wall_ns",
                   "actual_wall_ns", "matched_actual_wall_ns"],
    "resource_bill": ["query_id", "signature", "wall_ns",
                      "device_peak_bytes", "device_byte_seconds",
                      "device_bytes_charged", "device_bytes_released",
                      "residual_bytes", "persistent_bytes", "spill",
                      "partitions", "background_wall_ns", "worker_bytes",
                      "counters"],
    "regression": ["query_id", "signature", "dimension", "observed",
                   "baseline", "ratio", "z", "op_path", "op_name",
                   "detail"],
    "query_end": ["wall_ns", "status", "counters"],
}

_QUERY_SEQ = [0]
_SEQ_LOCK = threading.Lock()


def next_query_id() -> str:
    with _SEQ_LOCK:
        _QUERY_SEQ[0] += 1
        seq = _QUERY_SEQ[0]
    return f"{int(time.time() * 1000):013d}-{os.getpid()}-{seq:04d}"


class _OpStat:
    """Per-operator accumulation: inclusive wall, batch/row counts, and
    the counter deltas attributed while this operator was current."""

    __slots__ = ("path", "name", "describe", "wall_ns", "batches", "rows",
                 "t_first_ns", "t_last_ns", "counters", "metrics",
                 "fallback", "cal_op", "cal_fp")

    def __init__(self, path: str, name: str, describe: str):
        self.path = path
        self.name = name
        self.describe = describe
        self.wall_ns = 0
        self.batches = 0
        self.rows = 0
        self.t_first_ns: Optional[int] = None
        self.t_last_ns: Optional[int] = None
        self.counters: Dict[str, int] = {}
        self.metrics: Dict[str, int] = {}
        self.fallback = False
        # calibration identity (ISSUE 8): the breaker/tagging plan key —
        # (plan-node class, expr fingerprint) — so the operator summary
        # event carries the key the profiling store and the plan-time
        # cost model match on; None when the exec has no plan twin
        self.cal_op: Optional[str] = None
        self.cal_fp: Optional[str] = None


def _cal_key_of(node):
    """The exec's (plan class, expr fingerprint) via its plan twin —
    cached on the exec by resilience.domain, so this is a dict hit on
    every collect after the first."""
    try:
        from spark_rapids_tpu.resilience.domain import _breaker_key_of

        return _breaker_key_of(node)
    except Exception:
        return None


class QueryDiagnostics:
    """One query's diagnostics: spans, events, per-operator counter
    deltas.  Thread-safe; installed as ``diagnostics.context.RECORDER``
    for the duration of the query by ``diagnostics.query_scope``."""

    def __init__(self, query_id: str, metrics_level: str = "MODERATE",
                 plan_text: str = "", max_events: int = 200_000,
                 trace_id: str = ""):
        self._lock = threading.Lock()
        self.query_id = query_id
        # the cluster-wide trace id (ISSUE 15): adopted from the
        # lifecycle QueryContext by query_scope, stamped on every TKD1
        # frame, and the key worker-side spans merge back under
        self.trace_id = trace_id
        self.max_events = int(max_events)
        self.dropped_events = 0
        self.level = _LEVELS.get(str(metrics_level).upper(), MODERATE)
        self.metrics_level = str(metrics_level).upper()
        self.plan_text = plan_text
        self.started_at = time.time()
        self._t0 = time.perf_counter_ns()
        self.events: List[Dict[str, Any]] = []
        self.ops: Dict[str, _OpStat] = {"": _OpStat("", "(query)", "(query)")}
        self._op_order: List[str] = [""]
        self._extra_seq = 0
        # TpuMetric values are CUMULATIVE across collects of a cached
        # plan (the Spark-UI semantics metrics_report documents); this
        # log is per-query, so baselines captured at registration turn
        # them into per-query deltas at finish()
        self._metric_base: Dict[str, Dict[str, int]] = {}
        self.snap0 = PC.snapshot()
        self.total: Dict[str, int] = {}
        self.wall_ns = 0
        self.status = "running"
        self.closed = False
        self.event_log_path: Optional[str] = None
        self.trace_path: Optional[str] = None
        self.n_events = 0          # final count, survives the post-flush
                                   # drop of the in-memory events list

    # -- time ----------------------------------------------------------
    def _now(self) -> int:
        return time.perf_counter_ns() - self._t0

    # -- plan registration ---------------------------------------------
    def register_root(self, root) -> None:
        """Assign a plan-node path ("0", "0.1", ...) to every TpuExec in
        the tree and create its stat bucket.  Idempotent per recorder;
        overwrites stale paths a previous query's recorder left behind."""
        from spark_rapids_tpu.exec.base import TpuExec

        def walk(node, path, descend=True):
            node._diag_path = path
            node._diag_qid = self.query_id
            cal = _cal_key_of(node)
            with self._lock:
                if path not in self.ops:
                    self.ops[path] = _OpStat(path, node.node_name,
                                             node.describe())
                    self._op_order.append(path)
                if cal is not None:
                    self.ops[path].cal_op, self.ops[path].cal_fp = cal
                self._metric_base[path] = {
                    m.name: m.value for m in node.metrics.values()}
            if not descend:
                return
            for i, c in enumerate(node.children):
                if isinstance(c, TpuExec):
                    walk(c, f"{path}.{i}")
            # an exec the node runs without holding it as a child gets a
            # stable path too, not a +N of the run; its children are the
            # node's own and are walked above
            for k, inner in enumerate(node.inner_execs()):
                walk(inner, f"{path}.i{k}", descend=False)

        walk(root, "0")

    def _register_runtime_op(self, op) -> str:
        """An exec created after planning (adaptive re-plan, runtime CPU
        fallback shim) registers lazily under a ``+N`` path."""
        cal = _cal_key_of(op)
        with self._lock:
            self._extra_seq += 1
            path = f"+{self._extra_seq}"
            self.ops[path] = _OpStat(path, op.node_name, op.describe())
            if cal is not None:
                self.ops[path].cal_op, self.ops[path].cal_fp = cal
            self._op_order.append(path)
            self._metric_base[path] = {
                m.name: m.value for m in op.metrics.values()}
        op._diag_path = path
        op._diag_qid = self.query_id
        return path

    # -- operator span driving (called from exec/base._diag) -----------
    def begin_op(self, op):
        """Returns (path, token, t0) — or None when ``op`` belongs to a
        DIFFERENT query's registered tree (a concurrent collect whose
        query_scope lost the one-recorder slot): its spans/counters must
        not corrupt this recorder's log, so it runs unrecorded.  (A
        never-diagnosed concurrent tree carries no ownership stamp and
        still lands here as a ``+N`` op — the one-recorder-per-process
        design's residual ambiguity.)"""
        qid = getattr(op, "_diag_qid", None)
        if qid is not None and qid != self.query_id:
            return None
        path = getattr(op, "_diag_path", None)
        if path is None or path not in self.ops:
            path = self._register_runtime_op(op)
        token = CTX.CURRENT_OP.set(path)
        return path, token, self._now()

    def end_op(self, path: str, token, t0_ns: int,
               rows: Optional[int]) -> None:
        CTX.CURRENT_OP.reset(token)
        t1 = self._now()
        dur = t1 - t0_ns
        with self._lock:
            if self.closed:
                return
            st = self.ops.get(path)
            if st is None:       # another query's stale path (see attribute)
                return
            st.wall_ns += dur
            if st.t_first_ns is None:
                st.t_first_ns = t0_ns
            st.t_last_ns = t1
            if rows is not None:
                batch_idx = st.batches
                st.batches += 1
                st.rows += rows
                if self.level >= DEBUG:
                    self._append_event_locked({
                        "ev": "op_batch", "ts_ns": t0_ns, "op": path,
                        "path": path, "batch": batch_idx, "rows": rows,
                        "dur_ns": dur})

    # -- counter attribution (called from perfcounters.bump) -----------
    def attribute(self, key: str, n: int) -> None:
        path = CTX.CURRENT_OP.get() or ""
        with self._lock:
            if self.closed:
                return
            # a path this recorder never registered (a thread still
            # carrying another query's CURRENT_OP token) lands in the
            # query-level bucket instead of KeyError-ing the hot path
            st = self.ops.get(path) or self.ops[""]
            c = st.counters
            c[key] = c.get(key, 0) + n

    def _attr_many(self, path: str, deltas) -> None:
        st = self.ops.get(path) or self.ops[""]
        c = st.counters
        for key, n in deltas:
            c[key] = c.get(key, 0) + n

    def _append_event_locked(self, e) -> None:
        """Caller holds self._lock (the ``_locked`` suffix is the
        caller-holds-lock contract tpulint's lockset rules recognize).
        The in-memory list is bounded (a
        launch-per-row pathological query must not hold GBs of event
        dicts until flush); overflow counts into ``events_dropped`` on
        query_end instead of growing without limit."""
        if len(self.events) >= self.max_events:
            self.dropped_events += 1
            return
        self.events.append(e)

    def _event(self, min_level: int, ev: str, **fields) -> None:
        if self.level < min_level:
            return
        e = {"ev": ev, "ts_ns": self._now(),
             "op": CTX.CURRENT_OP.get() or ""}
        e.update(fields)
        with self._lock:
            if not self.closed:
                self._append_event_locked(e)

    # -- instrumentation entry points ----------------------------------
    def launch(self, dur_ns: int, compiled: int) -> None:
        """One jitted program dispatch (perfcounters._CountingJit).
        Mirrors the counter writes the jit wrapper just made so the
        per-operator sums reconcile exactly with the globals."""
        path = CTX.CURRENT_OP.get() or ""
        deltas = [("programs_launched", 1), ("launch_wall_ns", dur_ns)]
        if compiled:
            deltas += [("compiles", compiled), ("compile_wall_ns", dur_ns)]
        with self._lock:
            if self.closed:
                return
            self._attr_many(path, deltas)
            if self.level >= MODERATE:
                ts = self._now()
                self._append_event_locked({
                    "ev": "launch", "ts_ns": ts - dur_ns, "op": path,
                    "dur_ns": dur_ns, "compiled": int(compiled)})
                if compiled:
                    self._append_event_locked({
                        "ev": "compile", "ts_ns": ts - dur_ns, "op": path,
                        "mode": "inline", "dur_ns": dur_ns, "label": ""})

    def d2h(self, nbytes: int, counted_sync: bool) -> None:
        """One device->host materialization (ArrayImpl dunder patch)."""
        path = CTX.CURRENT_OP.get() or ""
        deltas = [("bytes_d2h", nbytes)]
        if counted_sync:
            deltas.append(("host_syncs", 1))
        with self._lock:
            if self.closed:
                return
            self._attr_many(path, deltas)
            if counted_sync and self.level >= MODERATE:
                self._append_event_locked({
                    "ev": "sync", "ts_ns": self._now(), "op": path,
                    "kind": "scalar", "dur_ns": 0, "bytes": int(nbytes)})

    def sync_batched(self, dur_ns: int) -> None:
        """One LOGICAL batched round trip (perfcounters.sync_event exit;
        the host_syncs counter was attributed at entry via bump).
        Back-dated to the sync's START like launch events, so the trace
        span occupies the interval the round trip actually covered."""
        if self.level < MODERATE:
            return
        e = {"ev": "sync", "ts_ns": self._now() - dur_ns,
             "op": CTX.CURRENT_OP.get() or "", "kind": "batched",
             "dur_ns": dur_ns, "bytes": 0}
        with self._lock:
            if not self.closed:
                self._append_event_locked(e)

    def cache_event(self, hit: bool, label: str) -> None:
        """Compile-registry hit/miss (counter attributed via bump)."""
        self._event(MODERATE, "cache", hit=bool(hit), label=label or "")

    def aot_compile(self, label: str, dur_ns: int) -> None:
        """One background-pool AOT compile (counters via bump, which the
        pool thread attributes to the query-level bucket)."""
        self._event(MODERATE, "compile", mode="aot", dur_ns=dur_ns,
                    label=label or "")

    def resilience(self, kind: str, op_name: str, detail: str = "") -> None:
        """A fault-domain event: transient_retry, oom_restart,
        runtime_fallback, breaker_trip, or query_fallback."""
        self._event(ESSENTIAL, "resilience", kind=kind, op_name=op_name,
                    detail=str(detail)[:500])

    def io_fault(self, kind: str, path: str, fmt: str = "",
                 detail: str = "") -> None:
        """A per-file scan fault tolerated away (ISSUE 5): kind is the
        quarantine class (corrupt, truncated, missing, schema_mismatch)."""
        self._event(ESSENTIAL, "io_fault", kind=kind, path=path,
                    fmt=fmt or "", detail=str(detail)[:500])

    def lifecycle(self, kind: str, detail: str = "",
                  dur_ns: int = 0) -> None:
        """A query-lifecycle event (ISSUE 4): ``admitted`` (dur_ns = the
        admission queue wait), ``cancelled``, ``deadline_trip``, or
        ``rejected``."""
        self._event(ESSENTIAL, "lifecycle", kind=kind,
                    detail=str(detail)[:500], dur_ns=int(dur_ns))

    def governor(self, action: str, state: str, prev: str = "",
                 pressure: float = 0.0, detail: str = "") -> None:
        """An overload-governor event (ISSUE 13): ``transition`` (the
        pressure state machine moved; ``prev`` names the old state) or
        ``preempt_pause`` (this query took a cooperative pause-and-
        spill at a batch-pull boundary)."""
        self._event(ESSENTIAL, "governor", action=action, state=state,
                    prev=prev, pressure=float(pressure),
                    detail=str(detail)[:500])

    def distributed(self, kind: str, worker_id: str, detail: str,
                    n_workers: int, n_partitions: int) -> None:
        """A cross-host tier event (ISSUE 14): ``worker_joined`` /
        ``worker_quarantined`` / ``worker_probed`` / ``worker_left`` /
        ``worker_lost`` (membership + liveness, with the live-worker
        and placed-partition counts at the time) or
        ``partition_replayed`` (one reduce partition re-driven from
        the producer-side spilled queues after a loss)."""
        self._event(ESSENTIAL, "distributed", kind=kind,
                    worker_id=str(worker_id),
                    detail=str(detail)[:500],
                    n_workers=int(n_workers),
                    n_partitions=int(n_partitions))

    def recovery(self, kind: str, fp: str, detail: str,
                 n: int = 0) -> None:
        """A crash-recovery event (ISSUE 16, docs/recovery.md):
        ``stage_committed`` (one exchange's materialized output became
        durable — local checkpoint renamed or distributed lease
        journaled), ``stage_recovered`` (a committed stage served
        instead of re-executing; ``n`` counts partitions),
        ``checkpoint_discarded`` (a damaged/expired artifact degraded
        to full re-execution), or ``query_resumed`` (this query
        adopted at least one prior-incarnation stage)."""
        self._event(ESSENTIAL, "recovery", kind=kind, fp=str(fp),
                    detail=str(detail)[:500], n=int(n))

    def worker_telemetry(self, worker_id: str, blocks: int, bytes_: int,
                         mem_used: int, counters: Dict[str, int]) -> None:
        """One federated heartbeat payload from a worker (ISSUE 15):
        its store occupancy + cumulative worker-local counters at
        receipt time — the per-query record of what the cluster's
        workers were doing while this query ran."""
        self._event(MODERATE, "worker_telemetry",
                    worker_id=str(worker_id), blocks=int(blocks),
                    bytes=int(bytes_), mem_used=int(mem_used),
                    counters=dict(counters))

    def record_worker_spans(self, views: List[Dict]) -> int:
        """Merge worker-side span events (ISSUE 15) into this FINISHED
        query's log: each view is one worker's federated telemetry
        (``Coordinator.collect_trace`` shape — ring already filtered to
        this query's trace id, plus the handshake clock offset).  Ring
        timestamps are worker wall-clock; alignment onto the driver
        timeline is ``(ts_wall + offset - started_at)`` clamped into
        the query window.  Runs after ``finish()`` closed the window
        (like ``record_cost_model``) and keeps query_end last.  Returns
        the number of spans merged."""
        events = []
        for view in views:
            wid = str(view.get("worker_id", "?"))
            off = float(view.get("clock_offset_s") or 0.0)
            for e in view.get("ring", ()):
                ts_ns = int(((float(e.get("ts_wall", 0.0)) + off)
                             - self.started_at) * 1e9)
                events.append({
                    "ev": "worker_span",
                    "ts_ns": max(min(ts_ns, self.wall_ns), 0),
                    "op": e.get("span", "") or "",
                    "worker_id": wid,
                    "kind": e.get("kind", "?"),
                    "trace": e.get("trace", ""),
                    "span": e.get("span", "") or "",
                    "exch": int(e.get("exch", -1)),
                    "pid": int(e.get("pid", -1)),
                    "seq": int(e.get("seq", -1)),
                    "bytes": int(e.get("bytes", 0)),
                    "dur_ns": int(e.get("dur_ns", 0))})
        if not events:
            return 0
        with self._lock:
            # honor the in-memory bound like every other event: a
            # many-worker merge must not blow past max_events just
            # because it lands after finish() (overflow counts into
            # events_dropped, same as _append_event_locked)
            room = max(self.max_events - len(self.events), 0)
            if len(events) > room:
                self.dropped_events += len(events) - room
                events = events[:room]
            at = len(self.events)
            if self.events and self.events[-1].get("ev") == "query_end":
                at -= 1
                # finish() already stamped events_dropped into the
                # trailing query_end — keep the flushed log's count true
                self.events[-1]["events_dropped"] = self.dropped_events
            if not events:
                return 0
            self.events[at:at] = events
            self.n_events = len(self.events)
        return len(events)

    def query_stall(self, query_id: str, path: str, name: str,
                    stalled_ms: float, detail: str = "") -> None:
        """The watchdog's stall scan found no operator advance for
        progress.stallMs (ISSUE 12): names the stuck operator — the
        innermost in-flight batch pull — not just thread stacks."""
        self._event(ESSENTIAL, "query_stall", query_id=query_id,
                    path=path, name=name,
                    stalled_ms=round(float(stalled_ms), 1),
                    detail=str(detail)[:500])

    def progress_summary(self, query_id: str, pct, eta_ns, stalls: int,
                         background: Dict[str, Dict[str, int]]) -> None:
        """The query's final live-progress record (ISSUE 12): overall
        percent at finish, last ETA, stall episodes, and the background
        wall (AOT/prefetch/shuffle pools) attributed to this query."""
        self._event(ESSENTIAL, "progress", query_id=query_id, pct=pct,
                    eta_ns=eta_ns, stalls=int(stalls),
                    background=background)

    def scan_prefetch(self, depth: int, batches: int,
                      overlapped_bytes: int, stall_ns: int) -> None:
        """One scan's H2D prefetch-ring summary (ISSUE 6): how many
        batches the ring produced, how many uploaded bytes fully
        overlapped query compute, and how long the consumer stalled
        waiting on an in-flight prefetch — profile_report derives
        overlap efficiency from these."""
        self._event(MODERATE, "scan_prefetch", depth=int(depth),
                    batches=int(batches),
                    overlapped_bytes=int(overlapped_bytes),
                    stall_ns=int(stall_ns))

    def ici_shuffle(self, stage: str, n_dev: int, rows: int,
                    bytes_: int, dur_ns: int) -> None:
        """One ICI collective-exchange epoch (ISSUE 10): which mesh
        stage ran it, how many devices participated, and the rows/bytes
        exchanged device-to-device (zero host traffic on this path)."""
        self._event(MODERATE, "ici_shuffle", stage=stage, n_dev=int(n_dev),
                    rows=int(rows), bytes=int(bytes_), dur_ns=int(dur_ns))

    # -- finalization --------------------------------------------------
    def finish(self, root=None, status: str = "ok") -> None:
        """Close the window: snapshot the global deltas, harvest each
        registered operator's TpuMetrics, and append the operator
        summaries + query_end events."""
        from spark_rapids_tpu.exec.base import TpuExec

        if self.closed:
            return
        self.wall_ns = self._now()
        self.status = status
        # Snapshot the globals and stop attribution ATOMICALLY: counter
        # writes hold PC._LOCK across (global increment + attribution),
        # so every bump — including one from an AOT pool thread racing
        # the end of collect() — lands either fully inside the window or
        # fully outside; the per-operator sums stay exactly equal to the
        # global deltas.  Lock order everywhere: PC._LOCK -> self._lock.
        with PC._LOCK:
            cur = dict(PC.COUNTERS)
            with self._lock:
                self.closed = True
        self.total = {k: cur[k] - self.snap0.get(k, 0) for k in cur}
        if root is not None:
            def walk(node, descend=True):
                path = getattr(node, "_diag_path", None)
                st = self.ops.get(path)
                if st is not None \
                        and getattr(node, "_diag_qid", None) == self.query_id:
                    base = self._metric_base.get(path, {})
                    st.metrics = {
                        m.name: m.value - base.get(m.name, 0)
                        for m in node.metrics.values()
                        if m.value - base.get(m.name, 0)}
                    st.fallback = bool(st.metrics.get("runtimeFallbacks"))
                if not descend:
                    return
                for c in node.children:
                    if isinstance(c, TpuExec):
                        walk(c)
                for inner in node.inner_execs():
                    walk(inner, descend=False)

            walk(root)
        with self._lock:
            # exclusive (self) wall: an operator's pull span contains all
            # descendant pulls, so ranking by inclusive wall would just
            # rank by plan depth — subtract the DIRECT children's wall
            child_wall: Dict[str, int] = {}
            for path, st in self.ops.items():
                dot = path.rfind(".")
                if dot > 0:
                    parent = path[:dot]
                    inner = self.ops.get(parent + ".i0")
                    if path[dot + 1] != "i" and inner is not None \
                            and inner.wall_ns:
                        # the node's children were pulled by the exec
                        # that ran inside it (register_root); where that
                        # one never ran (the adaptive join's broadcast
                        # branch) they stay the node's own
                        parent += ".i0"
                    child_wall[parent] = child_wall.get(parent, 0) \
                        + st.wall_ns
            for path in self._op_order:
                st = self.ops[path]
                if path == "" and not st.counters:
                    continue
                self.events.append({
                    "ev": "operator", "ts_ns": self.wall_ns, "op": path,
                    "path": path, "name": st.name,
                    "describe": st.describe,
                    "op_class": st.cal_op, "fp": st.cal_fp,
                    "wall_ns": st.wall_ns,
                    "self_wall_ns": max(
                        st.wall_ns - child_wall.get(path, 0), 0),
                    "batches": st.batches, "rows": st.rows,
                    "counters": dict(st.counters),
                    "metrics": dict(st.metrics),
                    "fallback": st.fallback,
                    "t_first_ns": st.t_first_ns, "t_last_ns": st.t_last_ns})
            self.events.append({
                "ev": "query_end", "ts_ns": self.wall_ns, "op": "",
                "wall_ns": self.wall_ns, "status": status,
                "events_dropped": self.dropped_events,
                "counters": dict(self.total)})
            self.n_events = len(self.events)

    def record_cost_model(self, hits: int, misses: int,
                          predicted_wall_ns: int, actual_wall_ns: int,
                          matched_actual_wall_ns: int) -> None:
        """The per-query predicted-vs-actual record (ISSUE 8).  The
        profiling finish hook runs after ``finish()`` closed the window
        but before the sinks flush, so this appends past the closed
        flag — inserted BEFORE the trailing query_end to keep the
        query_end-last log invariant."""
        e = {"ev": "cost_model", "ts_ns": self.wall_ns, "op": "",
             "hits": int(hits), "misses": int(misses),
             "predicted_wall_ns": int(predicted_wall_ns),
             "actual_wall_ns": int(actual_wall_ns),
             "matched_actual_wall_ns": int(matched_actual_wall_ns)}
        with self._lock:
            if self.events and self.events[-1].get("ev") == "query_end":
                self.events.insert(len(self.events) - 1, e)
            else:
                self.events.append(e)
            self.n_events = len(self.events)

    def _append_post_finish(self, e: Dict[str, Any]) -> None:
        """Insert a finish-hook event BEFORE the trailing query_end
        (same pattern as record_cost_model: the hooks run after
        ``finish()`` closed the window, before the sinks flush)."""
        with self._lock:
            if self.events and self.events[-1].get("ev") == "query_end":
                self.events.insert(len(self.events) - 1, e)
            else:
                self.events.append(e)
            self.n_events = len(self.events)

    def record_resource_bill(self, **fields: Any) -> None:
        """The per-query resource bill (ISSUE 18): the ledger joined
        with the window's counter deltas, progress background wall, and
        federated worker bytes — appended by the accounting finish
        hook."""
        self._append_post_finish(
            {"ev": "resource_bill", "ts_ns": self.wall_ns, "op": "",
             **fields})

    def record_regression(self, **fields: Any) -> None:
        """A sentinel-flagged excursion past this plan signature's
        baseline (ISSUE 18) — at most one per query, worst dimension."""
        self._append_post_finish(
            {"ev": "regression", "ts_ns": self.wall_ns, "op": "",
             **fields})

    def header(self) -> Dict[str, Any]:
        return {
            "ev": "query_start", "ts_ns": 0, "op": "",
            "query_id": self.query_id, "trace_id": self.trace_id,
            "started_at": self.started_at,
            "metrics_level": self.metrics_level,
            "plan": [{"path": p, "name": self.ops[p].name,
                      "describe": self.ops[p].describe}
                     for p in self._op_order if p != ""],
        }

    def operator_stats(self) -> List[_OpStat]:
        with self._lock:
            return [self.ops[p] for p in self._op_order]
