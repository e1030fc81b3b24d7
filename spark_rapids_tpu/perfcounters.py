"""Backend-independent performance accounting.

Reference analog: the reference tracks per-task GPU time / semaphore wait
(GpuTaskMetrics, SURVEY.md §5.5) but has no notion of *how many* kernel
launches or host round-trips a query costs, because on a local PCIe GPU
those are ~10µs.  Here a launch is microseconds too, but every program
boundary materializes an intermediate in HBM and every device->host sync
drains the device, so the counts say where a query's wall time can go.
These counters are identical on any backend; only per-event latency
differs.

Counters (process-global, reset per query via ``snapshot``/``since``):

- ``programs_launched`` — calls into a jitted stage function (every XLA
  executable dispatch the framework makes).
- ``compiles``          — launches that triggered a fresh XLA compile
  (jit cache miss), detected via the jit function's cache-size delta.
- ``host_syncs``        — device->host materializations: ``np.asarray`` /
  ``jax.device_get`` / ``int()``/``bool()``/``float()`` on device arrays.
  Counted by patching ``ArrayImpl.__array__``/``__index__``/scalar dunders.
- ``bytes_d2h`` / ``bytes_h2d`` — transfer volume in each direction.
- ``launch_wall_ns``    — wall time inside jitted calls (dispatch +, when
  the result is consumed synchronously, device compute).

Timing is :class:`span`'s: one primitive at every layer boundary of a
collect, folded into these counters as ``span_n|<path>``,
``span_ns|<path>``, ``span_self_ns|<path>`` (docs/diagnostics.md
"Spans"); the ``*_ns`` counters above and below that a span ``feeds``
keep their names and values.

Use :func:`tpu_jit` instead of ``jax.jit`` inside exec nodes; it is a
drop-in wrapper.  The dunder patches are installed at import and cost one
Python increment per event (~100ns) — negligible beside the 10µs-to-300ms
events they count.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

import jax

from spark_rapids_tpu.diagnostics import context as _DIAG

_LOCK = threading.Lock()

COUNTERS: Dict[str, int] = {
    "programs_launched": 0,
    "compiles": 0,
    "host_syncs": 0,
    "bytes_d2h": 0,
    "bytes_h2d": 0,
    "launch_wall_ns": 0,
    # compile cache (compilecache/): registry-level program reuse + wall
    # time spent inside fresh XLA compiles (inline or AOT-pool)
    "compile_cache_hits": 0,
    "compile_cache_misses": 0,
    "compile_wall_ns": 0,        # inline (critical-path) compile wall
    "aot_compiles": 0,
    "aot_compile_wall_ns": 0,    # background-pool compile wall
    "aot_compile_errors": 0,
    # resilience (stage-level fault domains, resilience/domain.py)
    "transient_retries": 0,
    "oom_restarts": 0,
    "runtime_fallbacks": 0,
    "breaker_trips": 0,
    "breaker_plan_fallbacks": 0,
    "query_fallbacks": 0,
    # I/O fault domain (io/faults.py, ISSUE 5): per-file scan tolerance
    # and the per-file device->native decoder fallback
    "files_skipped_corrupt": 0,
    "files_skipped_missing": 0,
    "file_decoder_fallbacks": 0,
    # query lifecycle (admission control / deadlines / cancellation,
    # lifecycle/ package)
    "queries_admitted": 0,
    "queries_rejected": 0,
    "queries_cancelled": 0,
    "deadline_trips": 0,
    "admission_wait_ns": 0,
    # transport-aware scan pipeline (ISSUE 6): bytes_h2d counts PHYSICAL
    # link bytes (compressed payloads count their compressed size);
    # bytes_h2d_logical counts the decoded/useful bytes those transfers
    # represent — the ratio is the transport win
    "bytes_h2d_logical": 0,
    "scan_transfer_ns": 0,        # wall inside scan H2D upload sites
    "pages_device_decompressed": 0,
    "chunk_decode_fallbacks": 0,  # compressed->decoded per-chunk falls
    # the scan's stage threads (io/scan.py _prefetched): bytes of the
    # batches found ready when the consumer asked, and wall the consumer
    # stalled waiting for one
    "bytes_h2d_overlapped": 0,
    "prefetch_stall_ns": 0,
    # units the scan's host reader handed on (a parquet file is read by
    # runs of whole row groups, one unit a run; any other file is one
    # unit), and files cut into more than one unit
    "scan_units": 0,
    "scan_files_streamed": 0,
    # device-resident hot-table cache (io/hot_cache.py)
    "hot_cache_hits": 0,
    "hot_cache_misses": 0,
    "hot_cache_evictions": 0,
    # telemetry tier (ISSUE 7, telemetry/): per-query SLO-target misses
    # and flight-recorder post-mortem bundles produced
    "slo_violations": 0,
    "postmortem_dumps": 0,
    # profile-driven cost model (ISSUE 8, profiling/): plan nodes the
    # calibration store matched / missed at plan time, the summed
    # predicted self-wall of the matched nodes, the measured self-wall
    # of those same nodes (the apples-to-apples denominator for
    # prediction error), and operator classes the qualification
    # advisory routed off the device at plan time
    "cost_model_hits": 0,
    "cost_model_misses": 0,
    "cost_model_predicted_wall_ns": 0,
    "cost_model_matched_actual_wall_ns": 0,
    "advisor_plan_fallbacks": 0,
    # out-of-core partitioned exchange (ISSUE 10): plan-time partition
    # sizing, wall inside partition-id/slice programs vs wall inside the
    # spill-backed queue (serialize/track/materialize), host-boundary
    # CRC blocks the queues produced, and AQE shuffle-read coalescing
    "exchange_partitions_planned": 0,
    "exchange_partition_ns": 0,
    "exchange_spill_ns": 0,
    "exchange_host_blocks": 0,
    "exchange_host_block_bytes": 0,
    "partitions_coalesced": 0,
    # whole-plan fusion (ISSUE 17, exec/fusion.py): pipeline-able
    # subtrees compiled as ONE jitted program at plan time, and collect
    # -boundary shrink programs elided because the padded transfer waste
    # stayed under fusion.collectShrinkMaxWasteBytes
    "subtrees_fused": 0,
    "collect_shrinks_elided": 0,
    # live progress tracking (ISSUE 12, progress/): watchdog-detected
    # query stalls (no operator advanced for progress.stallMs) and live
    # snapshots served (session.progress() + the /progress endpoint)
    "stalls_detected": 0,
    "progress_snapshots": 0,
    # overload governor (ISSUE 13, governor/): pressure state machine
    # transitions, deadline-aware queries shed at admission under RED,
    # cooperative pause-and-spill preemptions taken at batch-pull
    # boundaries, batch-size-goal shrinks applied under YELLOW/RED, and
    # the OOM-retry outcome split — a RED preemption pass taken instead
    # of halving vs a batch actually split
    "governor_transitions": 0,
    "queries_shed": 0,
    "preempt_pauses": 0,
    "degraded_batches": 0,
    "oom_retry_preempts": 0,
    "oom_retry_splits": 0,
    # ICI multi-chip shuffle (ISSUE 10): per-query collective-exchange
    # accounting — epochs through the mesh all-to-all stages, rows/bytes
    # that left their chip (device-to-device, never through the host), and
    # the wall of the collective steps; the grouped mesh aggregate's quota
    # (rows a device reserves a peer) and the input bytes a mesh aggregate
    # lays out as row shards (a resident table's once: the stage keeps them)
    "ici_epochs": 0,
    "ici_rows_exchanged": 0,
    "ici_bytes_moved": 0,
    "ici_shuffle_ns": 0,
    "ici_quota_rows": 0,
    "mesh_reshard_bytes": 0,
    # distributed cross-host tier (ISSUE 14, distributed/): elastic
    # membership (every worker join, incl. quarantined rejoins), LOST
    # declarations (missed heartbeats past workerLostMs or a dead
    # socket past the transient budget), monitor ticks that caught a
    # late heartbeat, reduce partitions re-placed + re-driven from the
    # producer-side spilled partition queues after a loss, and the
    # block traffic shipped to workers
    "workers_joined": 0,
    "worker_lost": 0,
    "worker_heartbeat_misses": 0,
    "partitions_replayed": 0,
    "dist_blocks_shipped": 0,
    "dist_block_bytes": 0,
    # gray-failure resilience (ISSUE 20, docs/distributed.md): hedged
    # page fetches launched after a soft-deadline miss, hedges the
    # producer-side lineage buffer won (first-complete-wins against
    # the slow remote), DEGRADED declarations (straggler demotion, not
    # loss), and pending partitions speculatively re-driven off a
    # DEGRADED worker onto healthy survivors
    "fetch_hedges": 0,
    "hedges_won": 0,
    "workers_degraded": 0,
    "speculative_redrives": 0,
    # cluster observability (ISSUE 15, docs/cluster_observability.md):
    # on-demand DUMP pulls of a worker's telemetry (ring + counters)
    # by the coordinator, and worker-side span events merged into
    # driver query event logs by trace id at collect end
    "dist_worker_dumps": 0,
    "dist_worker_spans_merged": 0,
    # crash-consistent driver recovery (ISSUE 16, docs/recovery.md):
    # journal WAL appends, exchange stages served from a prior
    # incarnation's committed checkpoint instead of re-executing,
    # queries that recovered at least one stage, damaged/unreadable
    # journal or checkpoint artifacts discarded during replay (each a
    # clean degrade to full re-execution), and checkpoint leases
    # retired past recovery.leaseTtlMs
    "journal_records_written": 0,
    "stages_recovered": 0,
    "queries_resumed": 0,
    "journal_recovery_discards": 0,
    "recovery_leases_expired": 0,
    # per-query resource accounting (ISSUE 18, accounting/): the global
    # halves of the bill exact-sum invariant — every spill-framework
    # charge site bumps the acct_* counter AND the owning query's bill
    # by the same amount, so summing bills reconciles against these
    # since() deltas exactly — plus bills retired at lifecycle exit and
    # regressions the sentinel flagged against signature baselines
    "acct_device_bytes_charged": 0,
    "acct_device_bytes_released": 0,
    "acct_spill_bytes_host": 0,
    "acct_spill_bytes_disk": 0,
    "acct_bytes_restored": 0,
    "bills_settled": 0,
    "perf_regressions_flagged": 0,
    # multi-tenant serving tier (ISSUE 19, serving/): fair-share
    # admissions granted by the weighted scheduler (vs plain FIFO),
    # result-fragment cache traffic, tenant-aware governor actions
    # (sheds targeting an over-quota tenant, preemptions targeting the
    # most over-share runner), and serving-session lifecycle
    "fair_share_admissions": 0,
    "serving_sessions_opened": 0,
    "serving_sessions_closed": 0,
    "result_cache_hits": 0,
    "result_cache_misses": 0,
    "result_cache_evictions": 0,
    "tenant_sheds": 0,
    "tenant_preempts": 0,
    # column pruning (plan/pruning.py): columns dropped, summed over the
    # nodes narrowed, once per planning (never per collect)
    "plan_columns_pruned": 0,
    # join -> aggregate fusion (exec/fused.py): probe batches through the
    # unique-build one-program path / the general three-program path;
    # calls of the one-program path by the dimension lookup its build
    # capacity chose (MXU one-hot contraction up to
    # ops/mxugather.MAX_TABLE_ROWS, VPU gathers beyond) and by where the
    # key match came from (the probe's merge sort itself, join.py
    # _merge_lookup, or the binary search's gathered key words for small
    # inputs); and re-runs of an aggregate on a wider rung of its
    # groups-cap ladder (fused.py, aggregate.py); the dimension lookups
    # done inside a fused program (one a join of the star it fused, each
    # call), and the rows a join wrote out as a joined batch outside one
    # (exec/join.py _materialize); and the probe batches an unfused LEFT
    # OUTER join looked up in a build side of unique keys instead of
    # expanding pairs (exec/join.py _lookup_unique)
    "joinagg_unique_probes": 0,
    "joinagg_general_probes": 0,
    "join_lookups_mxu": 0,
    "join_lookups_vpu": 0,
    "join_matches_merge": 0,
    "join_matches_gather": 0,
    "agg_groups_cap_regrows": 0,
    "joinagg_fused_lookups": 0,
    "join_rows_materialized": 0,
    "join_lookups_unique": 0,
    # launches of a full-width grouped aggregate program that formed its
    # groups at their segments' end rows and compacted them with one sort
    # instead of scattering (exec/aggregate.py _ends_form; describe()
    # ends in seg=ends)
    "agg_segment_compactions": 0,
}


def bump(key: str, n: int = 1) -> None:
    """Thread-safe increment.  ``COUNTERS[k] += n`` is three bytecodes
    (load / add / store) and CPython may switch threads between them, so
    concurrent unguarded increments lose updates; every write in this
    module routes through ``_LOCK``."""
    # attribution happens INSIDE the counter lock so a bump is atomic
    # with respect to the diagnostics window: the recorder installs /
    # snapshots / closes under this same lock, so every bump lands
    # either fully inside the window (global delta AND per-op bucket) or
    # fully outside (neither) — the exact-sum invariant survives racing
    # background threads (lock order: _LOCK -> recorder._lock)
    with _LOCK:
        COUNTERS[key] = COUNTERS.get(key, 0) + n
        rec = _DIAG.RECORDER
        if rec is not None:
            rec.attribute(key, n)


def bump_unattributed(key: str, n: int = 1) -> None:
    """Global-only increment that deliberately BYPASSES recorder
    attribution: for values produced OUTSIDE any query window (e.g. a
    finish hook running after its own recorder already closed), where
    routing through ``bump`` would attribute them to a concurrently
    installed OTHER query's recorder and contaminate that query's log.
    The global delta of such a key can therefore exceed a window's
    attributed per-op sums.  Users: the profiling finish hook's
    matched-actual bump and an UNRECORDED collect's cost_model_*
    prediction bumps (docs/profiling.md)."""
    with _LOCK:
        COUNTERS[key] = COUNTERS.get(key, 0) + n


def snapshot() -> Dict[str, int]:
    with _LOCK:
        return dict(COUNTERS)


def since(snap: Dict[str, int]) -> Dict[str, int]:
    cur = snapshot()
    return {k: cur[k] - snap.get(k, 0) for k in cur}


def reset() -> None:
    with _LOCK:
        for k in [k for k in COUNTERS if k.startswith(SPAN_KEYS)]:
            del COUNTERS[k]
        for k in COUNTERS:
            COUNTERS[k] = 0


# ---------------------------------------------------------------------------
# spans: the one timing primitive of a collect (docs/diagnostics.md)
# ---------------------------------------------------------------------------

SPAN_KEYS = ("span_n|", "span_ns|", "span_self_ns|")
_clock = time.perf_counter_ns       # the tests put a fake clock here
_TraceAnnotation = jax.profiler.TraceAnnotation
_tracing = _TraceAnnotation.is_enabled      # is a profiler session on?
_tls = threading.local()


class span:
    """``with span("srt.<layer>.<what>"):`` around one layer boundary of
    a collect.  Always on, no conf.  It

    * opens a ``jax.profiler.TraceAnnotation`` while a profiler session
      is on: the event lies on the profiler's own clock, in the host
      plane of the same trace as the device ops, on this thread
      (``trace=False`` where jax opens an event of its own);
    * folds into a thread-local table ``path -> [count, inclusive ns,
      self ns]``, ``path`` being the names of the thread's open spans
      from the outermost joined by ``/``; self time is inclusive minus
      the children's inclusive;
    * when the thread's outermost span closes, merges the table into
      ``COUNTERS`` under one lock as ``span_n|<path>``,
      ``span_ns|<path>``, ``span_self_ns|<path>``: ``since()`` carries
      them, ``reset()`` drops them, and the recorder's per-operator
      buckets never see them.

    ``feeds`` names an old counter that keeps its value: the span's
    inclusive time is bumped into it at exit.  After exit ``ns`` holds
    the inclusive time.  Names are low-cardinality and never
    ``collect`` (the benchmark counts host events of that name).

    One object may be opened again once it is closed (the runtime loop
    keeps one per operator iterator), never while it is open.

    Open a span around a call, never across a ``yield``: a generator
    suspended inside one would leave it on the stack of a thread that
    goes on.  Should that happen all the same, or a span be closed by
    another thread, nothing is corrupted: the spans left above it are
    dropped unrecorded and so is a span closed off its thread."""

    __slots__ = ("name", "ns", "ids", "_feeds", "_trace", "_ann", "_t0",
                 "_kids_ns", "_path", "_parent")

    def __init__(self, name: str, *, feeds: Optional[str] = None,
                 trace: bool = True, **ids):
        self.name = name
        self.ids = ids
        self._feeds = feeds
        self._trace = trace

    def __enter__(self):
        tls = _tls
        try:
            parent = tls.top
            while parent is not None and parent._path is None:  # dropped
                parent = tls.top = parent._parent
        except AttributeError:      # this thread's first span
            parent = tls.top = None
            tls.table = {}
        if parent is None:
            self._path = self.name
            # is a profiler session on?  Asked once, by the outermost
            # span, for all that nest in it
            tls.tracing = _tracing()
            if not self.ids:        # a pool job: the owner's ids
                self.ids = getattr(tls, "ids", None) or self.ids
        else:
            self._path = parent._path + "/" + self.name
        self._parent = parent
        self._kids_ns = 0
        if self._trace and tls.tracing:
            ann = self._ann = _TraceAnnotation(self.name, **self.ids)
            ann.__enter__()
        else:
            self._ann = None        # no profiler session: no event
        tls.top = self
        self._t0 = _clock()
        return self

    def annotate(self, **ids) -> None:
        """Ids learnt after the span opened (the query id of
        ``srt.collect``); pool jobs bound with :func:`bind_owner`
        carry them too."""
        self.ids = ids
        if self._ann:
            self._ann.set_metadata(**ids)

    def __exit__(self, et, ev, tb):
        dt = self.ns = _clock() - self._t0
        if self._ann:
            self._ann.__exit__(et, ev, tb)
        tls = _tls
        try:
            top = tls.top
        except AttributeError:      # a thread that never opened one
            top = None
        if top is not self:
            # out of order: spans above this one were left open by a
            # suspended generator (dropped), or this is another thread
            while top is not None and top is not self:
                top = top._parent
            if top is None:
                self._path = None       # its own thread drops it
                return False
            top = tls.top
            while top is not self:
                top._path = None
                top = top._parent
        parent = tls.top = self._parent
        table = tls.table
        try:
            e = table[self._path]
        except KeyError:
            # once a path and thread: the entry stays, merged and zeroed
            e = table[self._path] = [0, 0, 0] + [
                k + self._path for k in SPAN_KEYS]
        e[0] += 1
        e[1] += dt
        e[2] += dt - self._kids_ns
        if self._feeds is not None:
            bump(self._feeds, dt)
        if parent is None:
            _merge_spans(table)
        else:
            parent._kids_ns += dt
        return False


# a thread's table keeps its entries between merges; one that has seen
# more paths than any plan opens (a pool thread over many plans) is dropped
_MAX_TABLE = 512


def _merge_spans(table) -> None:
    with _LOCK:
        c = COUNTERS
        for e in table.values():
            n, ns, self_ns, k_n, k_ns, k_self = e
            if n:
                try:
                    c[k_n] += n
                    c[k_ns] += ns
                    c[k_self] += self_ns
                except KeyError:    # the path's first merge, or after reset()
                    c[k_n], c[k_ns], c[k_self] = n, ns, self_ns
                e[0] = e[1] = e[2] = 0
    if len(table) > _MAX_TABLE:
        table.clear()


def bind_owner(fn):
    """``fn`` for a pool: the spans it opens on the pool's thread are
    roots of their own, and carry the ids of the submitter's outermost
    open span (captured here, at submit, as progress/ does)."""
    top = getattr(_tls, "top", None)
    while top is not None and top._parent is not None:
        top = top._parent
    ids = top.ids if top is not None else None
    if not ids:
        return fn

    def owned(*a, **kw):
        _tls.ids = ids
        try:
            return fn(*a, **kw)
        finally:
            _tls.ids = None

    return owned


class _CountingJit:
    """Wraps a ``jax.jit``-ed callable; counts launches and compiles.

    Compile detection is serialized per wrapper: the monotonic
    ``_seen`` high-water mark of the jit cache size is advanced under
    ``_detect_lock``, taken only on the miss path (cache size grew), so
    two threads racing the same uncompiled program attribute exactly one
    compile between them instead of two (or zero).  The compile COUNT is
    exact; ``compile_wall_ns`` attribution is approximate under
    concurrent mixed-shape calls on one wrapper (a cached call landing
    right after another thread's cache insertion can claim the compile
    and contribute its own small wall) — the count, not the wall, is the
    portable signal (module docstring)."""

    __slots__ = ("_jitted", "_detect_lock", "_seen")

    def __init__(self, jitted):
        self._jitted = jitted
        self._detect_lock = threading.Lock()
        try:
            self._seen = jitted._cache_size()
        except Exception:
            self._seen = 0

    def __call__(self, *args, **kwargs):
        jitted = self._jitted
        # table only: jax opens PjitFunction(<name>) in the trace itself
        with span("srt.launch", trace=False) as sp:
            out = jitted(*args, **kwargs)
        dt = sp.ns
        compiled = 0
        n1 = jitted._cache_size()
        if n1 != self._seen:         # miss path only: serialize detection
            with self._detect_lock:
                if n1 > self._seen:
                    compiled = n1 - self._seen
                    self._seen = n1
                elif n1 < self._seen:
                    # the jit cache SHRANK (jax.clear_caches): this call
                    # re-traced, so count one compile and re-anchor the
                    # high-water mark instead of going silent until the
                    # cache regrows past the stale value
                    compiled = 1
                    self._seen = n1
        with _LOCK:
            COUNTERS["programs_launched"] += 1
            COUNTERS["launch_wall_ns"] += dt
            if compiled:
                COUNTERS["compiles"] += compiled
                # the compiling call's wall is ~all trace+XLA-compile time
                # (dispatch+execute are orders of magnitude smaller); this
                # is the inline twin of the AOT pool's measured wall
                COUNTERS["compile_wall_ns"] += dt
            # inside _LOCK: atomic with the diagnostics window (see bump)
            rec = _DIAG.RECORDER
            if rec is not None:
                rec.launch(dt, compiled)
        return out

    def __getattr__(self, name):  # lower/trace/eval_shape passthrough
        return getattr(self._jitted, name)


def tpu_jit(fn, name: Optional[str] = None, **jit_kwargs):
    """Drop-in ``jax.jit`` replacement that feeds the perf counters.
    ``name`` is what jax calls the program: ``PjitFunction(<name>)`` in
    the host trace, ``jit_<name>`` on the device's module line.  It is
    part of XLA's persistent-cache key, not of the registry's.  A named
    ``fn`` is a plain function made for this call (a local ``def``): it
    is renamed in place."""
    if name is not None:
        fn.__name__ = fn.__qualname__ = name
    return _CountingJit(jax.jit(fn, **jit_kwargs))


# ---------------------------------------------------------------------------
# host-sync counting: patch the device array's host-materialization dunders
# ---------------------------------------------------------------------------

def _install_sync_counters() -> bool:
    """Wrap ``ArrayImpl``'s host-materialization dunders (jax 0.9 layout:
    ``jax._src.array.ArrayImpl`` defines all five).  A jax where any is
    missing raises at import: silently uncounted syncs would make every
    ``nHostSyncs`` read 0."""
    from jax._src import array as _jarray

    impl = _jarray.ArrayImpl

    def _count(self):
        try:
            nbytes = self.nbytes
        except Exception:
            nbytes = 0
        counted_sync = not _in_sync_event()
        with _LOCK:
            if counted_sync:
                COUNTERS["host_syncs"] += 1
            COUNTERS["bytes_d2h"] += nbytes
            # inside _LOCK: atomic with the diagnostics window (see bump)
            rec = _DIAG.RECORDER
            if rec is not None:
                rec.d2h(nbytes, counted_sync)
        return counted_sync

    def make(real):
        def counted(self, *a, **kw):
            if not _count(self):
                # a leaf of a batched fetch: sync_get holds the span
                return real(self, *a, **kw)
            # a sync of its own.  One span around the real read, which
            # waits for the device and copies in one await: a
            # block_until_ready() ahead of it, to time the wait apart
            # from the copy, is a second wake-up of this thread and cost
            # 0.3 ms a collect (my chip runs, PR 26)
            with span("srt.sync"):
                return real(self, *a, **kw)

        return counted

    for dunder in ("__array__", "__int__", "__float__", "__bool__",
                   "__index__"):
        setattr(impl, dunder, make(getattr(impl, dunder)))
    return True


SYNC_COUNTING = _install_sync_counters()


def count_h2d(nbytes: int, logical: Optional[int] = None) -> None:
    """Host->device transfer accounting (called from upload sites).

    ``nbytes`` is the PHYSICAL byte count crossing the link (for a
    compressed-transfer payload: the compressed size + descriptor
    arrays); ``logical`` is the decoded/useful size those bytes
    represent (defaults to ``nbytes`` for plain uploads)."""
    bump("bytes_h2d", int(nbytes))
    bump("bytes_h2d_logical", int(nbytes if logical is None else logical))


class sync_event:
    """Count one LOGICAL host round trip for a batched fetch.

    ``jax.device_get`` over a pytree materializes every leaf; counting each
    leaf's ``__array__`` as a separate sync would overstate the round trips
    the engine design costs.  Inside this context the per-buffer patch
    still accounts bytes_d2h but not host_syncs.

    Nested events count ONCE: a ``sync_get`` issued from inside another
    ``sync_event`` is part of the same logical round trip, so only the
    depth-0 entry bumps ``host_syncs`` (ISSUE 3 satellite — the old code
    double-counted every nested batched fetch).

    It holds no timer of its own: the recorder's batched-sync event gets
    the time of the ``srt.sync`` span that ``sync_get`` opened inside
    the event."""

    def __enter__(self):
        depth = getattr(_tls, "in_sync_event", 0)
        _tls.in_sync_event = depth + 1
        if depth == 0:
            _tls.sync_ns = 0
            bump("host_syncs")
        return self

    def __exit__(self, *a):
        _tls.in_sync_event -= 1
        if _tls.in_sync_event == 0:
            rec = _DIAG.RECORDER
            if rec is not None:
                rec.sync_batched(_tls.sync_ns)


def _in_sync_event() -> bool:
    return getattr(_tls, "in_sync_event", 0) > 0


def sync_get(tree):
    """Fetch a pytree of device arrays as ONE logical host sync, under
    one ``srt.sync`` span (the wait for the device and the copy of every
    leaf, as ``device_get`` does them)."""
    with sync_event():
        with span("srt.sync") as sp:
            out = jax.device_get(tree)
        _tls.sync_ns += sp.ns
        return out
