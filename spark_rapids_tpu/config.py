"""TpuConf — the typed configuration registry.

Reference analog: com/nvidia/spark/rapids/RapidsConf.scala (~3k LoC, ~200+
``spark.rapids.*`` configs built with a typed-builder DSL and auto-documented
into docs/configs.md).  We reproduce the same pattern: every knob is declared
once with ``conf("spark.rapids.x").doc(...).boolean_conf().create_with_default``
-style builders, every expression/exec gets a per-op kill switch
(``spark.rapids.sql.expression.<Name>`` / ``spark.rapids.sql.exec.<Name>``),
and docs/gen_configs.py walks the registry to emit the config reference.

Config keys keep the ``spark.rapids.`` prefix so a user of the reference finds
the same names; TPU-specific knobs live under ``spark.rapids.tpu.*``.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional

_REGISTRY: "Dict[str, ConfEntry]" = {}


class ConfEntry:
    def __init__(self, key: str, doc: str, conv: Callable[[str], Any],
                 default: Any, typ: str, internal: bool = False,
                 checker: Optional[Callable[[Any], None]] = None):
        self.key = key
        self.doc = doc
        self.conv = conv
        self.default = default
        self.typ = typ
        self.internal = internal
        self.checker = checker

    def get(self, settings: Dict[str, str]) -> Any:
        raw = settings.get(self.key)
        if raw is None:
            raw = os.environ.get("SRT_" + self.key.replace(".", "_").upper())
        if raw is None:
            return self.default
        v = self.conv(raw) if isinstance(raw, str) else raw
        if self.checker is not None:
            self.checker(v)
        return v


class _Builder:
    def __init__(self, key: str):
        self.key = key
        self._doc = ""
        self._internal = False
        self._checker = None

    def doc(self, d: str) -> "_Builder":
        self._doc = d
        return self

    def internal(self) -> "_Builder":
        self._internal = True
        return self

    def check(self, fn: Callable[[Any], None]) -> "_Builder":
        self._checker = fn
        return self

    def _register(self, conv, default, typ):
        e = ConfEntry(self.key, self._doc, conv, default, typ,
                      self._internal, self._checker)
        _REGISTRY[self.key] = e
        return e

    def boolean_conf(self, default: bool) -> ConfEntry:
        return self._register(lambda s: s.strip().lower() in ("true", "1", "yes"),
                              default, "boolean")

    def integer_conf(self, default: int) -> ConfEntry:
        return self._register(lambda s: int(s), default, "integer")

    def long_conf(self, default: int) -> ConfEntry:
        return self._register(lambda s: int(s), default, "long")

    def double_conf(self, default: float) -> ConfEntry:
        return self._register(lambda s: float(s), default, "double")

    def string_conf(self, default: Optional[str]) -> ConfEntry:
        return self._register(lambda s: s, default, "string")

    def bytes_conf(self, default: int) -> ConfEntry:
        return self._register(_parse_bytes, default, "bytes")


def conf(key: str) -> _Builder:
    return _Builder(key)


_UNITS = {"b": 1, "k": 1 << 10, "kb": 1 << 10, "m": 1 << 20, "mb": 1 << 20,
          "g": 1 << 30, "gb": 1 << 30, "t": 1 << 40, "tb": 1 << 40}


def _parse_bytes(s: str) -> int:
    s = s.strip().lower()
    for suffix in sorted(_UNITS, key=len, reverse=True):
        if s.endswith(suffix):
            num = s[: -len(suffix)].strip()
            if num:
                return int(float(num) * _UNITS[suffix])
    return int(s)

# ---------------------------------------------------------------------------
# The registry (RapidsConf.scala analog).  Grouped as the reference groups its
# docs: general / memory / sql / io / shuffle / tpu runtime / testing.
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("spark.rapids.sql.enabled").doc(
    "Master enable for plan rewriting onto the TPU.").boolean_conf(True)

EXPLAIN = conf("spark.rapids.sql.explain").doc(
    "NONE, NOT_ON_GPU, or ALL: log why (parts of) a plan did or did not run "
    "on the TPU. NOT_ON_GPU prints only fallback reasons.").string_conf("NONE")

ANSI_ENABLED = conf("spark.sql.ansi.enabled").doc(
    "Spark ANSI mode: overflow/invalid-cast raise instead of null/wrap."
).boolean_conf(False)

# --- memory / runtime (GpuDeviceManager / RapidsConf memory group) ---------

CONCURRENT_TPU_TASKS = conf("spark.rapids.sql.concurrentGpuTasks").doc(
    "How many tasks may hold the TPU concurrently (admission semaphore; "
    "reference: GpuSemaphore).").integer_conf(2)

BATCH_SIZE_BYTES = conf("spark.rapids.sql.batchSizeBytes").doc(
    "Target columnar batch size; coalescing goal (reference: "
    "GpuCoalesceBatches).").bytes_conf(1 << 30)

MAX_READER_BATCH_SIZE_ROWS = conf(
    "spark.rapids.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per batch produced by readers.").integer_conf(2147483647)

HBM_POOL_FRACTION = conf("spark.rapids.memory.gpu.allocFraction").doc(
    "Fraction of HBM the arena may use for batches.").double_conf(0.9)

HBM_RESERVE = conf("spark.rapids.memory.gpu.reserve").doc(
    "HBM bytes reserved for XLA temporaries outside the arena."
).bytes_conf(640 << 20)

HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.memory.host.spillStorageSize").doc(
    "Host memory for spilled device batches before disk.").bytes_conf(1 << 31)

SPILL_DIR = conf("spark.rapids.memory.spillDir").doc(
    "Directory for disk spill (reference: RapidsDiskStore).").string_conf(None)

RETRY_MAX_ATTEMPTS = conf("spark.rapids.tpu.retry.maxAttempts").doc(
    "Max OOM-retry attempts per batch before giving up (reference: "
    "RmmRapidsRetryIterator).").integer_conf(8)

# --- query lifecycle (admission control / deadlines / cancellation) --------

CONCURRENT_QUERIES = conf("spark.rapids.tpu.concurrentQueries").doc(
    "How many queries may be admitted (planning + executing) at once; "
    "further collect() calls wait in a FIFO admission queue "
    "(lifecycle/admission.py — the query-level analog of "
    "spark.rapids.sql.concurrentGpuTasks, which gates device access "
    "*within* an admitted query).  0 disables admission control."
).integer_conf(4)

ADMISSION_MAX_QUEUE = conf("spark.rapids.tpu.admission.maxQueueDepth").doc(
    "Bound on queries waiting for admission; a collect() arriving at a "
    "full queue fast-rejects with QueryRejected instead of piling an "
    "unbounded convoy onto the process (load-shedding beats collapse)."
).integer_conf(16)

ADMISSION_QUEUE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.admission.queueTimeoutMs").doc(
    "Max time a query waits in the admission queue before rejecting "
    "with QueryRejected.  0 waits indefinitely (still cancellable and "
    "deadline-trippable).").long_conf(0)

QUERY_TIMEOUT_MS = conf("spark.rapids.tpu.query.timeoutMs").doc(
    "Per-query deadline armed at collect(): a daemon watchdog thread "
    "trips the query's CancelToken once the deadline passes, and every "
    "blocking site (batch pulls, semaphore/admission waits, retry "
    "backoffs, shuffle pool tasks, AOT compile waits) raises "
    "QueryDeadlineExceeded cooperatively.  0 disables.").long_conf(0)

QUERY_WATCHDOG_PERIOD_MS = conf(
    "spark.rapids.tpu.query.watchdogPeriodMs").doc(
    "Scan period of the deadline watchdog thread; an expired query is "
    "tripped within one period and blocked waits notice within one "
    "more (the 2x-period abort bound).").double_conf(50.0)

SEMAPHORE_ACQUIRE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.semaphore.acquireTimeoutMs").doc(
    "Max time a task waits for a TPU semaphore permit before raising "
    "SemaphoreTimeout (classified transient: the fault domain retries "
    "with backoff, by which time the convoy may have drained).  "
    "0 waits indefinitely.").long_conf(0)

# --- overload governor (graceful degradation under sustained pressure) -----

GOVERNOR_ENABLED = conf("spark.rapids.tpu.governor.enabled").doc(
    "Enable the process-global overload governor (governor/): an "
    "EWMA-smoothed GREEN/YELLOW/RED pressure state machine fused from "
    "HBM-pool occupancy, admission queue depth, the active-query "
    "table, the rolling p95, and cost-model predicted walls.  YELLOW "
    "shrinks batch-size goals and exchange partition budgets, pauses "
    "scan-prefetch run-ahead, and defers background AOT compiles; RED "
    "adds deadline-aware load shedding at admission, hot-table-cache "
    "eviction, and cooperative pause-and-spill preemption of the "
    "newest-admitted running query.  Disabled (the default): one "
    "ambient check per site, zero governor calls.").boolean_conf(False)

GOVERNOR_UPDATE_PERIOD_MS = conf(
    "spark.rapids.tpu.governor.updatePeriodMs").doc(
    "Minimum interval between pressure recomputations.  The governor "
    "has no thread of its own: every consult site (admission, batch "
    "pulls, the telemetry sampler) triggers an update at most this "
    "often — a consult inside the window reads the cached state."
).double_conf(50.0)

GOVERNOR_EWMA_ALPHA = conf("spark.rapids.tpu.governor.ewmaAlpha").doc(
    "EWMA smoothing weight for the fused pressure signal (higher = "
    "reacts faster, flaps easier).  Smoothing plus the separate "
    "up/down thresholds is what keeps an oscillating signal from "
    "flapping the state machine.").double_conf(0.4)

GOVERNOR_YELLOW_UP = conf(
    "spark.rapids.tpu.governor.yellowUpThreshold").doc(
    "Smoothed pressure at (or above) which GREEN enters YELLOW."
).double_conf(0.65)

GOVERNOR_YELLOW_DOWN = conf(
    "spark.rapids.tpu.governor.yellowDownThreshold").doc(
    "Smoothed pressure at (or below) which YELLOW re-enters GREEN.  "
    "Must sit below yellowUpThreshold — the gap is the hysteresis band "
    "that prevents flapping.").double_conf(0.45)

GOVERNOR_RED_UP = conf("spark.rapids.tpu.governor.redUpThreshold").doc(
    "Smoothed pressure at (or above) which the governor enters RED."
).double_conf(0.85)

GOVERNOR_RED_DOWN = conf("spark.rapids.tpu.governor.redDownThreshold").doc(
    "Smoothed pressure at (or below) which RED de-escalates (to YELLOW, "
    "or straight to GREEN when also at or below yellowDownThreshold)."
).double_conf(0.60)

GOVERNOR_DEGRADE_FRACTION = conf(
    "spark.rapids.tpu.governor.degradeBatchFraction").doc(
    "Under YELLOW/RED, batch-size goals (coalesce targets, exchange "
    "drain chunks) and exchange partition budgets shrink to this "
    "fraction of their configured value — smaller working sets per "
    "step trade throughput for bounded residency.").double_conf(0.5)

GOVERNOR_MAX_PAUSE_MS = conf("spark.rapids.tpu.governor.maxPauseMs").doc(
    "Upper bound on one cooperative pause-and-spill preemption: the "
    "preempted query spills its unpinned device batches at its next "
    "batch-pull boundary and waits until pressure leaves RED or this "
    "many ms pass, then resumes — it is never cancelled."
).long_conf(2000)

GOVERNOR_SHED_MIN_RETRY_MS = conf(
    "spark.rapids.tpu.governor.shedMinRetryMs").doc(
    "Floor for the retry_after_ms hint carried by a shed "
    "QueryRejected — clients backing off sooner than this would "
    "re-arrive before any pressure could drain.").long_conf(100)

GOVERNOR_HOT_CACHE_EVICT_FRACTION = conf(
    "spark.rapids.tpu.governor.hotCacheEvictFraction").doc(
    "Fraction of hot-table-cache bytes evicted (LRU-first) on each "
    "entry into RED — cached convenience data is the first ballast "
    "overboard.").double_conf(0.5)

GOVERNOR_BACKLOG_TARGET_MS = conf(
    "spark.rapids.tpu.governor.backlogTargetMs").doc(
    "Normalization for the cost-model backlog signal: the summed "
    "PR 8 predicted walls of admitted queries, divided by the "
    "admission limit, reads as pressure 1.0 at this many ms.  0 "
    "disables the predicted-wall component (the memory/queue/latency "
    "signals still drive the state machine).").long_conf(0)

# --- multi-tenant serving tier (ISSUE 19) ----------------------------------

SERVING_ENABLED = conf("spark.rapids.tpu.serving.enabled").doc(
    "Enable the multi-tenant serving tier (serving/): named tenant "
    "sessions with hard-isolated conf / temp views / cache handles / "
    "result fragments, a weighted fair-share scheduler replacing the "
    "FIFO admission order, tenant-aware governor shed/preempt "
    "decisions, and a per-tenant result-fragment cache.  Disabled (the "
    "default): one ambient check per site, zero serving-module calls."
).boolean_conf(False)

SERVING_TENANT = conf("spark.rapids.tpu.serving.tenant").doc(
    "Tenant identity of queries run under this conf.  Serving sessions "
    "set it automatically; it rides the QueryContext so admission "
    "fair-share, per-tenant SLO series, and governor shed/preempt "
    "decisions all attribute the query to its tenant.  Empty = "
    "untenanted (weight 1, no quota).").string_conf("")

SERVING_WEIGHTS = conf("spark.rapids.tpu.serving.weights").doc(
    "Per-tenant fair-share weights as 'tenantA:4,tenantB:1'.  The "
    "scheduler admits the eligible waiter with the lowest "
    "usage/weight — a tenant with weight 4 earns 4x the admission "
    "throughput of a weight-1 tenant under contention.  Unlisted "
    "tenants get weight 1.").string_conf("")

SERVING_QUOTAS = conf("spark.rapids.tpu.serving.quotas").doc(
    "Per-tenant concurrent-running quotas as 'tenantA:2,tenantB:1'.  A "
    "tenant at its quota is ineligible for the next admission slot "
    "while any under-quota tenant waits (work-conserving: with only "
    "over-quota waiters the slot is still granted).  Under RED "
    "pressure the governor sheds over-quota tenants' queries first.  "
    "Unlisted tenants are unbounded.").string_conf("")

SERVING_USAGE_HALFLIFE_S = conf(
    "spark.rapids.tpu.serving.usageHalflifeS").doc(
    "Half-life of the per-tenant fair-share usage EWMA: charged usage "
    "(admissions + query wall seconds) decays by half every this many "
    "seconds, so an idle tenant's past consumption fades and it "
    "re-approaches its full share instead of being punished forever."
).double_conf(30.0)

SERVING_RESULT_CACHE_ENABLED = conf(
    "spark.rapids.tpu.serving.resultCache.enabled").doc(
    "Cache collected result rows per (plan signature, conf "
    "fingerprint, tenant) inside serving sessions — a repeated "
    "dashboard query returns without planning, compiling, or touching "
    "the device.  Entries are charged to the owning query's resource "
    "bill, scoped to (and dropped with) the owning tenant session, "
    "and evicted by the governor's RED ladder.").boolean_conf(True)

SERVING_RESULT_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.serving.resultCache.maxBytes").doc(
    "LRU bound on estimated host bytes held by the serving "
    "result-fragment cache across all tenants; inserting past it "
    "evicts least-recently-used fragments first."
).long_conf(64 * 1024 * 1024)

# --- distributed cross-host execution tier (ISSUE 14) ----------------------

DISTRIBUTED_ENABLED = conf("spark.rapids.tpu.distributed.enabled").doc(
    "Route multi-partition exchanges through the cross-host worker "
    "tier (distributed/): a coordinator places reduce partitions over "
    "worker processes, blocks ship as CRC-framed TKU2 wire blocks, and "
    "the producer-side spill-backed partition queues retain every "
    "shipped block until the consuming stage commits — a worker lost "
    "mid-shuffle (missed heartbeats or dead socket) is recovered by "
    "re-placing its partitions on survivors and re-driving the "
    "retained blocks.  Requires a coordinator with live workers; with "
    "none joined, exchanges fall back to the in-process spill-backed "
    "path.").boolean_conf(False)

DISTRIBUTED_HEARTBEAT_MS = conf(
    "spark.rapids.tpu.distributed.heartbeatMs").doc(
    "Worker heartbeat period.  The coordinator's liveness monitor "
    "scans at the same period and counts a worker late "
    "(worker_heartbeat_misses) past two periods of silence."
).long_conf(200)

DISTRIBUTED_WORKER_LOST_MS = conf(
    "spark.rapids.tpu.distributed.workerLostMs").doc(
    "Heartbeat silence after which a worker is declared LOST: its "
    "partitions re-place onto survivors, the re-drive plan is queued, "
    "a per-worker circuit-breaker entry opens (flapping workers are "
    "quarantined on rejoin until the breaker TTL re-probe), and a "
    "flight-recorder post-mortem bundle captures the placement table "
    "and re-drive plan.").long_conf(1200)

DISTRIBUTED_OP_TIMEOUT_MS = conf(
    "spark.rapids.tpu.distributed.opTimeoutMs").doc(
    "Socket timeout for one data-plane operation (put / fetch / "
    "release) against a worker.  A timed-out op classifies TRANSIENT "
    "and retries up to putRetries times before the worker is declared "
    "lost.").long_conf(4000)

DISTRIBUTED_PUT_RETRIES = conf(
    "spark.rapids.tpu.distributed.putRetries").doc(
    "Bounded transient retries (reconnect + resend) per data-plane "
    "operation before the target worker is declared lost and the "
    "block layer switches to re-placement + re-drive."
).long_conf(2)

DISTRIBUTED_REDRIVE_MAX = conf(
    "spark.rapids.tpu.distributed.redriveMaxAttempts").doc(
    "How many times one reduce partition may be re-placed + re-driven "
    "(repeated worker losses) before WorkerLost escapes to the "
    "operator fault domain — which falls back to the CPU oracle "
    "without indicting the operator's breaker key.").long_conf(4)

DISTRIBUTED_LOSS_BREAKER_THRESHOLD = conf(
    "spark.rapids.tpu.distributed.lossBreakerThreshold").doc(
    "Loss declarations that OPEN a worker's circuit-breaker entry.  "
    "The default (1) quarantines a killed-and-rejoined worker "
    "immediately: it heartbeats but receives no placements until the "
    "resilience breaker TTL admits a re-probe.").long_conf(1)

DISTRIBUTED_TRACE_ENABLED = conf(
    "spark.rapids.tpu.distributed.traceEnabled").doc(
    "Cluster-wide trace propagation (ISSUE 15): stamp the query's "
    "trace id (minted at lifecycle collect start) and the current "
    "operator's span id on every TKD1 control frame, so worker-side "
    "work (store puts/fetches, spill, re-drive serves) records into "
    "the worker-local diagnostics ring attributed to the originating "
    "query, heartbeats piggyback worker counter/ring deltas, and the "
    "driver merges driver+worker spans into one Chrome trace.  Off, "
    "frames carry no trace fields, so workers record no spans and no "
    "merge runs (counters still federate over heartbeats) — the bench "
    "rung4_dist A/B pins the on/off overhead <= 5%."
).boolean_conf(True)

DISTRIBUTED_TELEMETRY_RING = conf(
    "spark.rapids.tpu.distributed.telemetryRingSize").doc(
    "Capacity of the worker-local diagnostics ring (span events for "
    "store puts/fetches/spill/re-drive) AND of the per-worker mirror "
    "ring the coordinator folds heartbeat-shipped deltas into — the "
    "mirror is what a SIGKILLed worker's post-mortem bundle contains "
    "(its 'last-shipped' ring).  0 disables worker span recording "
    "(counters still federate).").long_conf(512)

# --- gray-failure resilience (ISSUE 20) ------------------------------------

DISTRIBUTED_HEDGE_ENABLED = conf(
    "spark.rapids.tpu.distributed.hedgeEnabled").doc(
    "Hedged fetches for the distributed exchange read path "
    "(docs/distributed.md): a paged TKD1 fetch that blows its per-"
    "worker soft deadline (softDeadlineFactor x the worker's p95 "
    "latency EWMA, floored at softDeadlineMinMs) races a hedge "
    "against the producer-side lineage buffer — partition_queues "
    "retains every framed slice until commit, so the hedge source is "
    "free — first-complete-wins, remote duplicates discarded by the "
    "store's per-seq idempotence.  Counters: fetch_hedges launches, "
    "hedges_won lineage wins.  The bench rung4_dist healthy-path A/B "
    "pins the on/off overhead <= 2% with hedges_won == 0."
).boolean_conf(True)

DISTRIBUTED_SOFT_DEADLINE_FACTOR = conf(
    "spark.rapids.tpu.distributed.softDeadlineFactor").doc(
    "Multiplier over a worker's p95-biased latency EWMA that sets its "
    "per-op soft deadline.  An op past the soft deadline is a 'miss' "
    "(counts toward DEGRADED demotion and, on the fetch path, "
    "launches a hedge); the hard stop stays opTimeoutMs."
).double_conf(3.0)

DISTRIBUTED_SOFT_DEADLINE_MIN_MS = conf(
    "spark.rapids.tpu.distributed.softDeadlineMinMs").doc(
    "Floor for the per-worker soft deadline, so an idle fleet with "
    "microsecond EWMAs does not hedge every op on scheduler jitter."
).long_conf(50)

DISTRIBUTED_SLOW_FACTOR = conf(
    "spark.rapids.tpu.distributed.slowFactor").doc(
    "A worker whose latency EWMA sits persistently past slowFactor x "
    "the fleet median (or that misses degradeAfterMisses consecutive "
    "soft deadlines) is declared DEGRADED: demoted in capacity-"
    "weighted placement, its pending partitions speculatively re-"
    "driven onto healthy survivors over the lineage contract — "
    "WITHOUT declaring it LOST or opening the quarantine breaker (a "
    "slow worker is not a dead one).").double_conf(4.0)

DISTRIBUTED_DEGRADE_AFTER_MISSES = conf(
    "spark.rapids.tpu.distributed.degradeAfterMisses").doc(
    "Consecutive soft-deadline misses on one worker's data-plane ops "
    "before the coordinator declares it DEGRADED.").long_conf(3)

DISTRIBUTED_PROMOTE_AFTER_OKS = conf(
    "spark.rapids.tpu.distributed.promoteAfterOks").doc(
    "Consecutive within-deadline observations (served ops or monitor "
    "pings) a DEGRADED worker must bank, with its EWMA back under "
    "slowFactor x the fleet median, before promotion to ALIVE — "
    "sustained recovery, not one lucky op.").long_conf(3)

# --- crash-consistent driver recovery (ISSUE 16) ---------------------------

RECOVERY_ENABLED = conf("spark.rapids.tpu.recovery.enabled").doc(
    "Crash-consistent driver recovery (docs/recovery.md): every "
    "collect() appends admission / stage-checkpoint / end records to a "
    "durable CRC-framed query journal (lifecycle/journal.py), "
    "materialized exchange outputs commit at stage boundaries (local: "
    "atomic tmp+rename checkpoint files keyed by plan-stage "
    "fingerprint; distributed: worker-held partitions pinned by a "
    "journal-recorded lease), and a restarted driver replays the "
    "journal to classify prior queries as completed / resumable / "
    "abandoned and to skip committed stages on re-execution "
    "(stages_recovered).  Off, the journal module is never imported — "
    "the hot path makes zero recovery calls.").boolean_conf(False)

RECOVERY_DIR = conf("spark.rapids.tpu.recovery.dir").doc(
    "Root directory for the query journal, stage checkpoints, and the "
    "coordinator endpoint file workers re-attach through.  Must be "
    "stable across driver restarts (recovery identity lives here).  "
    "Unset: <tmpdir>/srt_recovery.").string_conf(None)

RECOVERY_FSYNC = conf("spark.rapids.tpu.recovery.fsyncOnAppend").doc(
    "Journal durability: fsync the journal after every appended "
    "record (the spark.rapids.tpu.files.fsyncOnCommit discipline "
    "applied to the WAL).  Off by default — single-write atomic "
    "appends already keep the journal prefix-consistent; fsync adds a "
    "per-record syscall and protects against machine (not process) "
    "crashes.").boolean_conf(False)

RECOVERY_LEASE_TTL_MS = conf("spark.rapids.tpu.recovery.leaseTtlMs").doc(
    "How long a journal-recorded stage checkpoint (a distributed "
    "lease pinning worker-held partitions, or a local checkpoint "
    "directory) stays adoptable after the committing driver's death.  "
    "A reborn driver retires anything older (recovery_leases_expired) "
    "and re-executes from scratch — orphaned worker partitions must "
    "not pin memory forever.").long_conf(120_000)

# --- resilience (stage-level fault domains) --------------------------------

RESILIENCE_ENABLED = conf("spark.rapids.tpu.resilience.enabled").doc(
    "Wrap every exec operator in a fault domain that classifies escaping "
    "failures (device OOM / transient / deterministic), retries the "
    "recoverable classes, and falls the rest back to the CPU oracle at "
    "runtime (resilience/ package; reference: the RmmRapidsRetryIterator "
    "state machine plus CPU-Spark stage fallback).").boolean_conf(True)

RESILIENCE_MAX_TRANSIENT_RETRIES = conf(
    "spark.rapids.tpu.resilience.maxTransientRetries").doc(
    "Bounded restarts of an operator after a transient runtime error "
    "(UNAVAILABLE / DEADLINE_EXCEEDED style XLA failures) before it is "
    "treated as deterministic.").integer_conf(3)

RESILIENCE_BACKOFF_BASE_MS = conf(
    "spark.rapids.tpu.resilience.backoffBaseMs").doc(
    "Base delay for exponential backoff between transient retries "
    "(delay = base * 2^attempt + jitter in [0, base), capped at 2s); "
    "0 disables sleeping (tests).").double_conf(10.0)

RESILIENCE_RUNTIME_FALLBACK = conf(
    "spark.rapids.tpu.resilience.runtimeFallbackEnabled").doc(
    "On a deterministic failure, materialize the stage's inputs to host, "
    "execute the stage's plan-node twin through the CPU oracle, and "
    "continue the query on TPU (the mid-query analog of plan-time "
    "willNotWorkOnTpu tagging).  Also enables the whole-query oracle "
    "fallback of last resort in collect().").boolean_conf(True)

RESILIENCE_BREAKER_THRESHOLD = conf(
    "spark.rapids.tpu.resilience.breakerFailureThreshold").doc(
    "Deterministic failures of one (operator, expression-fingerprint) key "
    "before the circuit breaker opens and plan-time tagging routes that "
    "stage to the CPU oracle for subsequent queries.").integer_conf(3)

RESILIENCE_BREAKER_TTL_SEC = conf(
    "spark.rapids.tpu.resilience.breakerTtlSec").doc(
    "How long an open breaker entry holds its stage on CPU before a "
    "half-open probe re-admits it to the TPU (success closes the entry, "
    "failure re-opens with a fresh TTL).").double_conf(300.0)

RESILIENCE_TEST_INJECT = conf(
    "spark.rapids.tpu.resilience.testInject").doc(
    "Chaos-injection hook: 'kind:Operator[:count[:atBatch[:seed]]]' "
    "(kinds: compile, transient, poison, oom, file_corrupt, decode; "
    "';'-separated for multiple), "
    "armed at collect() time.  The force_retry_oom test API generalized "
    "to every failure class.").internal().string_conf("NONE")

AUTO_BROADCAST_JOIN_THRESHOLD = conf(
    "spark.sql.autoBroadcastJoinThreshold").doc(
    "Estimated build-side size below which joins broadcast instead of "
    "shuffling (Spark's conf; file-scan sizes come from file footers, "
    "local tables from their host columns).  -1 disables broadcasting."
).bytes_conf(10 << 20)

COMPILE_CACHE_DIR = conf("spark.rapids.tpu.compileCache.dir").doc(
    "Persistent XLA compile-cache directory, applied process-wide on the "
    "first TpuSession construction so tests/tools/bench all share compiled "
    "programs across processes (a compile costs seconds to minutes; the "
    "cache pays it once).  The applied directory is <dir>/<backend>; with "
    "JAX_COMPILATION_CACHE_DIR set in the environment the cache was "
    "placed from outside and this conf sets nothing.  Empty string or "
    "'0' disables.  Default: <repo>/.jax_compile_cache.  Legacy alias of "
    "spark.rapids.tpu.compile.cacheDir, which wins when set."
).string_conf(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache"))

# --- compile cache / AOT pipeline (compilecache/) --------------------------

COMPILE_CACHE_DIR_V2 = conf("spark.rapids.tpu.compile.cacheDir").doc(
    "Persistent XLA executable cache directory "
    "(jax_compilation_cache_dir): a fresh process re-running the same "
    "plan deserializes executables instead of compiling.  Preferred "
    "spelling; unset falls back to spark.rapids.tpu.compileCache.dir "
    "(and its repo-local default).  Empty string or '0' disables."
).string_conf(None)

COMPILE_AOT_ENABLED = conf("spark.rapids.tpu.compile.aot.enabled").doc(
    "Plan-time AOT compilation: after overrides produce the exec tree, "
    "enumerate the (stage function x shape-bucket) programs the query "
    "will need and compile them concurrently on a bounded background "
    "pool, so batch 1 of operator 1 overlaps the compiles of everything "
    "downstream instead of serializing minute-long compiles between "
    "launches (compilecache/aot.py).").boolean_conf(True)

COMPILE_AOT_THREADS = conf("spark.rapids.tpu.compile.aot.threads").doc(
    "Background compile pool width: XLA compiles are CPU-bound on the "
    "host and parallelize well."
).integer_conf(4)

COMPILE_REGISTRY_ENABLED = conf(
    "spark.rapids.tpu.compile.registry.enabled").doc(
    "In-process executable registry: exec nodes share compiled stage "
    "programs keyed by semantic fingerprint (expressions + schemas + "
    "confs), so a re-planned query compiles nothing the process already "
    "built.  Off: every exec instance keeps private jits (the seed "
    "behavior).").boolean_conf(True)

COMPILE_REGISTRY_MAX_PROGRAMS = conf(
    "spark.rapids.tpu.compile.registry.maxPrograms").doc(
    "LRU bound on registered programs (each entry pins its compiled "
    "executables); evicted programs simply recompile on next use."
).integer_conf(1024)

SKEW_JOIN_ENABLED = conf("spark.sql.adaptive.skewJoin.enabled").doc(
    "AQE skew handling for the mesh join (Spark's OptimizeSkewedJoin "
    "analog): when one device's matched-pair total for a probe epoch "
    "exceeds skewedPartitionFactor x the device mean, the epoch splits "
    "in half and re-routes — bounding the per-device materialization "
    "capacity a hot key would otherwise inflate.").boolean_conf(True)

SKEW_JOIN_FACTOR = conf(
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor").doc(
    "A device is skewed when its epoch output exceeds this factor times "
    "the device mean (Spark's default 5).").integer_conf(5)

SKEW_JOIN_MIN_ROWS = conf(
    "spark.rapids.tpu.mesh.skewJoin.minEpochRows").doc(
    "Epochs at or below this row count stop splitting (the floor of the "
    "skew ladder).").integer_conf(1024)

AGG_SMALL_GROUPS_CAP = conf("spark.rapids.tpu.agg.smallGroupsCap").doc(
    "Sort-based group-by emits results through a bounded-cardinality "
    "program when the group count fits this cap: boundary/cumsum forms "
    "replace the full-width segment scatters (~20x device time at 20M "
    "rows), with host-side growth to the next power of two on overflow "
    "(the output row count is synced anyway, so the check is free).  "
    "0 disables (always full-width).").integer_conf(65536)

# --- plan / exec switches --------------------------------------------------

ENABLE_CAST_STRING_TO_TIMESTAMP = conf(
    "spark.rapids.sql.castStringToTimestamp.enabled").doc(
    "String->timestamp cast compat switch (device civil parser; named "
    "timezones parse as null).").boolean_conf(True)

# --- IO --------------------------------------------------------------------

PARQUET_READER_TYPE = conf("spark.rapids.sql.format.parquet.reader.type").doc(
    "PERFILE, COALESCING, MULTITHREADED, or AUTO (reference: "
    "GpuParquetScan readers).").string_conf("AUTO")

PARQUET_MULTITHREAD_READ_NUM_THREADS = conf(
    "spark.rapids.sql.multiThreadedRead.numThreads").doc(
    "Host threads fetching/decoding files in parallel.").integer_conf(20)

PARQUET_READ_ENABLED = conf("spark.rapids.sql.format.parquet.read.enabled").doc(
    "Enable TPU parquet scans.").boolean_conf(True)

PARQUET_WRITE_ENABLED = conf(
    "spark.rapids.sql.format.parquet.write.enabled").doc(
    "Enable TPU parquet writes.").boolean_conf(True)

CSV_READ_ENABLED = conf("spark.rapids.sql.format.csv.read.enabled").boolean_conf(True)
JSON_READ_ENABLED = conf("spark.rapids.sql.format.json.read.enabled").boolean_conf(True)
PARQUET_DEVICE_DECODE = conf(
    "spark.rapids.sql.format.parquet.decode.device").doc(
    "Decode Parquet pages with the Pallas kernels (bit-unpack + run "
    "expansion + dictionary gather on device; host parses only footers "
    "and run headers).  Files outside the supported subset (v2 pages, "
    "snappy, byte arrays, nested) silently fall back to the host pyarrow "
    "decode per file.  Off by default: correct on TPU, but the page "
    "pipeline dispatches many small eager device ops per page, whose "
    "launch overhead has not been weighed against the host decode on "
    "the chip yet.").boolean_conf(False)
PARQUET_DEVICE_ENCODE = conf(
    "spark.rapids.sql.format.parquet.encode.device").doc(
    "Encode Parquet pages with device kernels (dictionary build, k-bit "
    "index packing and def-level packing run as jitted programs; the "
    "host assembles thrift headers + snappy framing through the C "
    "compressor twin — io/parquet_encode.py, the decode pipeline's "
    "mirror).  Flat int/float/string schemas; others keep the pyarrow "
    "host encode.  Off by default for the same per-page dispatch reason "
    "as decode.device.").boolean_conf(False)

AVRO_READ_ENABLED = conf("spark.rapids.sql.format.avro.read.enabled").doc(
    "Enable TPU Avro scans (pure-python container decode, io/avro.py)."
).boolean_conf(True)

# --- transport-aware scan pipeline (ISSUE 6) -------------------------------

PARQUET_COMPRESSED_TRANSFER = conf(
    "spark.rapids.sql.format.parquet.transfer.compressed").doc(
    "With parquet decode.device on, ship eligible column chunks across "
    "the host->device link as RAW COMPRESSED page bytes and decompress "
    "(snappy block gather) + decode (RLE/bit-pack/dictionary) on device, "
    "so the link carries the smallest representation (the host->device "
    "link is the slowest hop of a cold scan).  Chunks outside "
    "the device-decompressible subset (zstd codec, PLAIN byte_array "
    "pages) fall back PER CHUNK to the decoded-transfer device path "
    "(`chunk_decode_fallbacks`).  Physical link bytes land in "
    "`bytes_h2d`; the decoded size lands in `bytes_h2d_logical`."
).boolean_conf(True)

SCAN_PREFETCH_DEPTH = conf("spark.rapids.tpu.scan.prefetch.depth").doc(
    "Depth of the queues between the scan's three staging threads (read, "
    "to_columns, H2D): a file is read by units (runs of whole parquet row "
    "groups; one unit a file for other formats), and up to this many units "
    "wait between two stages, so a unit is read while its predecessors are "
    "decoded, uploaded and computed on (double-buffering at the default "
    "2).  Overlap efficiency is observable via `bytes_h2d_overlapped` / "
    "`prefetch_stall_ns`, `scan_units` and the `scan_prefetch` diagnostics "
    "event.  0 disables (read, decode and upload strictly one after the "
    "other on the query's thread).").integer_conf(2)

SCAN_HOT_CACHE = conf("spark.rapids.tpu.scan.hotTableCache.enabled").doc(
    "Device-resident hot-table cache: completed file scans register "
    "their device batches (keyed by file fingerprints + column set + "
    "pushed filters + snapshot id) so a repeated query over the same "
    "table skips the read+decode+transfer entirely "
    "(`hot_cache_hits`/`hot_cache_misses`).  Entries are spillable "
    "(memory/spill.py): HBM pressure migrates them down-tier instead of "
    "OOMing, and `TpuSession.close()` drops them.  Off by default; "
    "serving-tier deployments replaying dashboards enable it."
).boolean_conf(False)

SCAN_HOT_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.scan.hotTableCache.maxBytes").doc(
    "Device-bytes bound on the hot-table cache; inserting past it "
    "evicts least-recently-used entries (`hot_cache_evictions`).  A "
    "single scan larger than the bound is not cached.").bytes_conf(1 << 30)

# --- IO fault tolerance (io/faults.py — per-file scan fault domain) --------

IGNORE_CORRUPT_FILES = conf("spark.sql.files.ignoreCorruptFiles").doc(
    "Spark conf: skip files whose bytes fail to decode (corrupt / "
    "truncated / schema-drifted) instead of failing the query.  Each "
    "skip bumps files_skipped_corrupt, emits an io_fault diagnostics "
    "event, and lands in the per-query quarantine manifest "
    "(docs/io_resilience.md).").boolean_conf(False)

IGNORE_MISSING_FILES = conf("spark.sql.files.ignoreMissingFiles").doc(
    "Spark conf: skip files that vanished between planning and read "
    "(ENOENT) instead of failing the query; skips bump "
    "files_skipped_missing and are quarantined like corrupt files."
).boolean_conf(False)

TPU_IGNORE_CORRUPT_FILES = conf(
    "spark.rapids.tpu.files.ignoreCorruptFiles").doc(
    "Tri-state alias of spark.sql.files.ignoreCorruptFiles: set "
    "true/false to override the Spark conf for TPU scans only; unset "
    "defers to it.").string_conf(None)

TPU_IGNORE_MISSING_FILES = conf(
    "spark.rapids.tpu.files.ignoreMissingFiles").doc(
    "Tri-state alias of spark.sql.files.ignoreMissingFiles: set "
    "true/false to override the Spark conf for TPU scans only; unset "
    "defers to it.").string_conf(None)

FSYNC_ON_COMMIT = conf("spark.rapids.tpu.files.fsyncOnCommit").doc(
    "Writer durability: fsync every staged output file (and its "
    "directory) before the atomic commit rename, so a machine crash "
    "right after commit cannot surface zero-length files.  Off by "
    "default — rename-atomicity alone already guarantees readers never "
    "observe partial output; fsync adds a per-file syscall cost."
).boolean_conf(False)

# --- shuffle ---------------------------------------------------------------

ADAPTIVE_ENABLED = conf("spark.sql.adaptive.enabled").doc(
    "AQE analog: shuffled equi-joins re-plan themselves at execution time "
    "— the build side materializes first and, when its measured bytes sit "
    "under spark.sql.autoBroadcastJoinThreshold, the join runs broadcast "
    "with both planned exchanges elided (runtime stats beat static "
    "planning).").boolean_conf(True)

OPTIMIZER_ENABLED = conf("spark.rapids.sql.optimizer.enabled").doc(
    "Cost-based fallback (CostBasedOptimizer analog, default off like the "
    "reference): plans whose estimated input is below "
    "spark.rapids.sql.optimizer.smallPlanBytes stay on CPU — the device "
    "round-trip cannot pay for itself.").boolean_conf(False)

OPTIMIZER_SMALL_PLAN_BYTES = conf(
    "spark.rapids.sql.optimizer.smallPlanBytes").doc(
    "Cost-based fallback threshold (bytes).").integer_conf(32768)

ARROW_EVAL_ENABLED = conf("spark.rapids.sql.python.arrowEval.enabled").doc(
    "Run plain python UDFs inside the TPU plan through the host arrow-eval "
    "path (GpuArrowEvalPythonExec analog): batches cross to the host for "
    "the UDF only, everything else stays on device.  false: such stages "
    "fall back to CPU entirely.").boolean_conf(True)

UDF_COMPILER_ENABLED = conf("spark.rapids.sql.udfCompiler.enabled").doc(
    "Translate simple python UDFs into engine expressions at plan time by "
    "operator-overload tracing (the udf-compiler analog of the "
    "reference's bytecode decompiler); untranslatable functions keep the "
    "arrow-eval path.").boolean_conf(True)

SHUFFLE_MODE = conf("spark.rapids.shuffle.mode").doc(
    "MULTITHREADED (serialize batches host-side, concat-friendly Kudo-style "
    "format), ICI (device-resident all-to-all over the TPU interconnect via "
    "XLA collectives — replaces the reference's UCX transport), or CACHE_ONLY."
).string_conf("MULTITHREADED")

MESH_ENABLED = conf("spark.rapids.tpu.mesh.enabled").doc(
    "Execute eligible plan stages SPMD over a jax.sharding.Mesh of all "
    "visible devices.  With shuffle.mode=ICI the partial-agg -> exchange -> "
    "final-agg stage pair compiles to ONE collective program per batch "
    "(scan shards rows, all-to-all repartitions by key hash over the "
    "interconnect).").boolean_conf(False)

SINGLE_DEVICE_SHUFFLE_COALESCE = conf(
    "spark.rapids.tpu.shuffle.singleDeviceCoalesce").doc(
    "On a single device with the host shuffle, collapse hash/round-robin "
    "exchanges to ONE partition (an AQE-style partition coalesce: per-"
    "partition program launches are pure overhead without a second chip; "
    "aggregation/join results are partition-count independent)."
).boolean_conf(True)

COMPLETE_AGG_COLLAPSE = conf(
    "spark.rapids.tpu.completeAggCollapse.enabled").doc(
    "When a two-phase aggregate's exchange runs on one device (mesh off "
    "or a single chip), collapse Final<-Coalesce<-Exchange<-Partial into "
    "ONE COMPLETE-mode aggregate: a single-batch input then aggregates and "
    "finalizes in one XLA program instead of three (the single-device "
    "analog of AQE's exchange elision — each saved launch is a saved host "
    "round trip).").boolean_conf(True)

JOIN_AGG_FUSION = conf("spark.rapids.tpu.joinAggFusion.enabled").doc(
    "Compile an aggregate sitting directly on an equi-join INTO the join's "
    "materialization program (and, when the build side's keys are unique — "
    "the dim-table case — run probe+gather+aggregate as ONE program with "
    "no pair-count host sync).  Each saved launch is a saved host round "
    "trip; joined rows feeding an aggregate never round-trip through HBM."
).boolean_conf(True)

WINDOW_CHAIN_FUSION = conf(
    "spark.rapids.tpu.windowChainFusion.enabled").doc(
    "Compile [COMPLETE aggregate ->] window [-> project/filter] chains "
    "into ONE XLA program (the window function already runs as a single "
    "jitted scan program; a grouped aggregate below and a stage above "
    "compose with it via device-scalar row counts — no host sync between "
    "operators).").boolean_conf(True)

FUSION_ENABLED = conf("spark.rapids.tpu.fusion.enabled").doc(
    "Whole-plan subtree fusion (ISSUE 17): compile each maximal "
    "pipeline-able chain of narrow operators (project/filter stages, "
    "expand) into ONE jitted XLA program routed through the compile "
    "cache registry — a 3-operator chain then costs one launch and zero "
    "intermediate host round trips instead of three launches with "
    "per-edge materialization.  Eligibility is the fusibility "
    "manifest's fusable set intersected with the cost model's predicted "
    "intermediate sizes (see fusion.maxIntermediateFraction)."
).boolean_conf(True)

FUSION_MAX_INTERMEDIATE_FRACTION = conf(
    "spark.rapids.tpu.fusion.maxIntermediateFraction").doc(
    "Fusion boundary rule: a pipeline chain fuses through an operator "
    "edge only while the cost-model-predicted intermediate at that edge "
    "(static AOT rows, else the calibration store's measured rows EWMA, "
    "else the capacity bound — exec/partition_sizing.py) stays within "
    "this fraction of the HBM pool.  A predicted-oversized intermediate "
    "splits the chain at that edge so the fused program's working set "
    "cannot blow the pool.").double_conf(0.5)

FUSION_COLLECT_SHRINK_MAX_WASTE = conf(
    "spark.rapids.tpu.fusion.collectShrinkMaxWasteBytes").doc(
    "Collect-boundary shrink elision: to_host_columns normally launches "
    "one slice program to shrink a padded batch to its tight capacity "
    "bucket before the device->host copy.  When the padding that would "
    "be transferred anyway is at most this many bytes, the shrink "
    "launch is elided (per-column to_host truncation already drops the "
    "padding rows on host) — one program and its host round trip saved "
    "per collect, and one fewer (in-capacity, out-capacity) shrink "
    "shape to compile.  "
    "0 disables the elision.").bytes_conf(8 << 20)

MESH_DEVICES = conf("spark.rapids.tpu.mesh.devices").doc(
    "Number of mesh devices for ICI stages (0 = all visible devices).  "
    "Non-power-of-2 counts are supported; capacities pad to multiples of "
    "the device count.").integer_conf(0)

MESH_AGG_ENABLED = conf("spark.rapids.tpu.mesh.agg.enabled").doc(
    "Per-stage kill switch: run eligible aggregation stage pairs as ICI "
    "collective programs (requires mesh.enabled + shuffle.mode=ICI)."
).boolean_conf(True)

MESH_JOIN_ENABLED = conf("spark.rapids.tpu.mesh.join.enabled").doc(
    "Per-stage kill switch: run eligible shuffled equi-joins as ICI "
    "collective programs.").boolean_conf(True)

MESH_SORT_ENABLED = conf("spark.rapids.tpu.mesh.sort.enabled").doc(
    "Per-stage kill switch: run global sorts as the distributed "
    "range-exchange ICI sort.").boolean_conf(True)

MESH_WINDOW_ENABLED = conf("spark.rapids.tpu.mesh.window.enabled").doc(
    "Per-stage kill switch: run partitioned window stages as the "
    "distributed ICI window (hash all-to-all on PARTITION BY, then the "
    "single-chip window program per device).").boolean_conf(True)

MESH_REPARTITION_ENABLED = conf(
    "spark.rapids.tpu.mesh.repartition.enabled").doc(
    "Per-stage kill switch: lower remaining hash/round-robin shuffle "
    "exchanges (those no specialized ICI stage claims) to the generic "
    "mesh all-to-all repartition.").boolean_conf(True)

MESH_EPOCH_BYTES = conf("spark.rapids.tpu.mesh.epochTargetBytes").doc(
    "Input bytes gathered into one mesh collective epoch.  ICI stages "
    "stream the child's batches through the SPMD program in epochs of "
    "roughly this size instead of concatenating the whole input, so "
    "per-device memory stays bounded by (epoch shard + accumulator/build "
    "state).").integer_conf(1 << 28)

# --- out-of-core partitioned exchange (ISSUE 10) ---------------------------

EXCHANGE_SIZED_PARTITIONS = conf(
    "spark.rapids.tpu.exchange.sizedPartitions.enabled").doc(
    "Size-aware exchange partitioning: at plan time, estimate each "
    "shuffle exchange's input bytes from the AOT shape predictor "
    "(aot_output_rows/aot_output_caps — refined by the profiling cost "
    "model's calibrated per-operator output-bytes prediction when a "
    "store exists) and GROW the partition count so one partition's "
    "working set fits exchange.targetPartitionFraction of the HBM pool. "
    "Only ever raises the planned count (datasets far larger than HBM "
    "stream partition-by-partition instead of materializing whole); "
    "small inputs keep their planned counts.  Sized exchanges are "
    "exempt from the single-device partition collapse."
).boolean_conf(True)

EXCHANGE_TARGET_PARTITION_FRACTION = conf(
    "spark.rapids.tpu.exchange.targetPartitionFraction").doc(
    "Fraction of the HBM pool one exchange partition's working set "
    "should fit when sizedPartitions chooses a partition count "
    "(partitions = ceil(estimated bytes / (pool * fraction)))."
).double_conf(0.125)

EXCHANGE_MAX_PARTITIONS = conf(
    "spark.rapids.tpu.exchange.maxPartitions").doc(
    "Upper bound on the partition count sizedPartitions may choose "
    "(each partition costs a read-side program launch and a host "
    "sync)."
).integer_conf(256)

EXCHANGE_SPILL_ENABLED = conf(
    "spark.rapids.tpu.exchange.spill.enabled").doc(
    "Stream shuffle exchange partitions through spill-backed partition "
    "queues (shuffle/partition_queues.py): map-side slices register "
    "with the SpillFramework up to exchange.deviceResidentBytes, and "
    "slices beyond the budget cross the host boundary as CRC-framed "
    "serializer blocks — device residency stays bounded instead of "
    "materializing the whole exchange input.  false: the legacy "
    "shuffle-manager path (serialize every slice host-side)."
).boolean_conf(True)

EXCHANGE_DEVICE_RESIDENT_BYTES = conf(
    "spark.rapids.tpu.exchange.deviceResidentBytes").doc(
    "Device bytes the spill-backed exchange queues may keep resident "
    "as SpillFramework handles before further slices serialize to "
    "CRC-framed host blocks.  0 (default) derives the budget from the "
    "pool: pool_bytes * exchange.targetPartitionFraction * 2."
).bytes_conf(0)

EXCHANGE_COALESCE_SMALL_BYTES = conf(
    "spark.rapids.tpu.exchange.coalesceSmallPartitionBytes").doc(
    "AQE shuffle-read coalescing threshold (SURVEY §2.4): adjacent "
    "reduce partitions below this byte size merge into one read window "
    "in TpuAdaptiveShuffleReaderExec (counted by partitions_coalesced); "
    "partitions at or above it emit alone.  The batch-size goal still "
    "caps each window.").bytes_conf(4 << 20)

# --- ICI multi-chip shuffle (ISSUE 10) -------------------------------------

ICI_HOST_BOUNDARY_CODEC = conf(
    "spark.rapids.tpu.ici.hostBoundaryCodec").doc(
    "Codec for CRC-framed blocks crossing the ICI/exchange host "
    "boundary (spill-backed partition queues, ici_host_frame).  Unset "
    "defers to spark.rapids.shuffle.compression.codec."
).string_conf(None)

ICI_CROSS_SLICE_HOSTS = conf(
    "spark.rapids.tpu.ici.crossSliceHosts").doc(
    "When > 0, the generic mesh repartition routes through a two-level "
    "(host x ici) mesh (parallel/crossslice.py): phase 1 moves rows to "
    "their destination's local device index over intra-slice ICI, "
    "phase 2 delivers each row across the host (DCN-analog) axis "
    "exactly once.  The device count must be divisible by this host "
    "count.  0 (default): the flat single-axis all-to-all."
).integer_conf(0)

SHUFFLE_MT_WRITER_THREADS = conf(
    "spark.rapids.shuffle.multiThreaded.writer.threads").integer_conf(20)

SHUFFLE_PARTITIONS = conf("spark.sql.shuffle.partitions").doc(
    "Number of shuffle partitions.").integer_conf(16)

SHUFFLE_COMPRESSION_CODEC = conf(
    "spark.rapids.shuffle.compression.codec").doc(
    "Codec for serialized shuffle batches: none, lz4, zstd.").string_conf("lz4")

# --- metrics / debug -------------------------------------------------------

METRICS_LEVEL = conf("spark.rapids.sql.metrics.level").doc(
    "ESSENTIAL, MODERATE, or DEBUG.").string_conf("MODERATE")

# --- diagnostics (diagnostics/ — spans, event log, profile reports) --------

DIAGNOSTICS_ENABLED = conf("spark.rapids.tpu.diagnostics.enabled").doc(
    "Install a QueryDiagnostics recorder around every collect(): each "
    "operator's batch iteration, jit launch, logical host sync, "
    "inline/AOT compile, cache hit/miss, and resilience event is "
    "recorded as a span/event attributed to the current operator, with "
    "per-operator perf-counter deltas that sum exactly to the process-"
    "global deltas for the query.  Event verbosity follows "
    "spark.rapids.sql.metrics.level.  Disabled (default): every "
    "instrumentation site costs one ambient None-check per event."
).boolean_conf(False)

DIAGNOSTICS_EVENT_LOG_DIR = conf(
    "spark.rapids.tpu.diagnostics.eventLogDir").doc(
    "Directory for per-query JSONL structured event logs "
    "(query-<id>.jsonl, atomic tmp+rename flush per query, rotation via "
    "eventLog.maxFiles); consumed by tools/profile_report.py.  Unset: "
    "events stay in memory (explain('analyze') still works)."
).string_conf(None)

DIAGNOSTICS_TRACE_DIR = conf(
    "spark.rapids.tpu.diagnostics.chromeTraceDir").doc(
    "Directory for per-query Chrome-trace files (query-<id>.trace.json) "
    "rendering the operator timeline with launches/syncs/compiles "
    "nested per operator track — load in chrome://tracing or "
    "ui.perfetto.dev.  Unset: no trace files."
).string_conf(None)

DIAGNOSTICS_MAX_FILES = conf(
    "spark.rapids.tpu.diagnostics.eventLog.maxFiles").doc(
    "Rotation bound per diagnostics sink directory: after each flush, "
    "oldest files beyond this count are deleted.  <= 0 disables "
    "rotation.").integer_conf(64)

DIAGNOSTICS_MAX_EVENTS = conf(
    "spark.rapids.tpu.diagnostics.maxEvents").doc(
    "In-memory bound on recorded events per query: a launch-per-row "
    "pathological query must not hold GBs of event dicts until flush.  "
    "Overflow is counted into query_end's events_dropped field; operator "
    "summaries and query_start/end are always kept.").integer_conf(200000)

# --- telemetry (telemetry/ — always-on metrics, flight recorder, SLOs) -----

TELEMETRY_ENABLED = conf("spark.rapids.tpu.telemetry.enabled").doc(
    "Always-on telemetry tier: a process-global time-series metrics "
    "registry fed by a sampler thread (admission queue depth, HBM "
    "occupancy, spill tiers, cache hit rates, H2D bandwidth), "
    "per-plan-signature latency histograms with p50/p95 SLO tracking "
    "recorded at collect() exit, and the failure flight recorder.  The "
    "hub is built by the first TpuSession whose conf leaves this true; "
    "per-batch hot paths are never instrumented (docs/observability.md)."
).boolean_conf(True)

TELEMETRY_SAMPLE_PERIOD_MS = conf(
    "spark.rapids.tpu.telemetry.samplePeriodMs").doc(
    "Sampler thread period: every period the daemon snapshots the "
    "process singletons (peek-only — an idle tick creates nothing) into "
    "the time-series registry and the in-memory timeline.  0 disables "
    "the sampler (the registry, SLO histograms, and flight recorder "
    "still work; only the periodic gauges stop).").double_conf(500.0)

TELEMETRY_RETENTION = conf("spark.rapids.tpu.telemetry.retention").doc(
    "Ring-buffer bound on retained samples per time series (and on "
    "timeline rows): at the default 500ms period, 720 points is a "
    "six-minute sliding window.  A long-running process holds a window, "
    "never an unbounded history.").integer_conf(720)

TELEMETRY_PORT = conf("spark.rapids.tpu.telemetry.port").doc(
    "Bind a localhost-only (127.0.0.1) HTTP scrape endpoint serving GET "
    "/metrics in Prometheus exposition format.  0 disables (the "
    "default); telemetry.export() returns the same text in-process "
    "either way.  Fleet exposure belongs to a sidecar, not this "
    "library.").integer_conf(0)

TELEMETRY_JSONL_DIR = conf("spark.rapids.tpu.telemetry.jsonlDir").doc(
    "Directory for the periodic JSONL telemetry log "
    "(telemetry-<pid>.jsonl, one line per sampler tick) — the "
    "process-level companion of the per-query diagnostics event log.  "
    "Unset: samples stay in the in-memory timeline only."
).string_conf(None)

TELEMETRY_FLIGHT_ENABLED = conf(
    "spark.rapids.tpu.telemetry.flightRecorder.enabled").doc(
    "Always-on failure flight recorder: a fixed-size in-memory ring of "
    "recent query-level events (admitted/finished/cancelled/deadline/"
    "breaker — a handful of appends per QUERY, never per batch) that "
    "auto-dumps a post-mortem bundle (ring + all-thread stacks with the "
    "offending query's thread named + counter snapshot + active-query "
    "table) when a deadline trips, a query is cancelled mid-batch, a "
    "circuit breaker opens, or collect() raises.  On by default."
).boolean_conf(True)

TELEMETRY_FLIGHT_CAPACITY = conf(
    "spark.rapids.tpu.telemetry.flightRecorder.capacity").doc(
    "Flight-recorder ring size in events (oldest evicted first)."
).integer_conf(2048)

TELEMETRY_FLIGHT_DUMP_DIR = conf(
    "spark.rapids.tpu.telemetry.flightRecorder.dumpDir").doc(
    "Directory post-mortem bundles are written to (atomic tmp+rename "
    "JSON, postmortem-<ts>-<reason>[-<qid>].json).  Unset: bundles are "
    "kept in memory only (the last 8, telemetry.last_postmortem())."
).string_conf(None)

TELEMETRY_SLO_TARGET_P95_MS = conf(
    "spark.rapids.tpu.telemetry.slo.targetP95Ms").doc(
    "Per-query latency SLO target: any collect() slower than this bumps "
    "slo_violations and drops an slo_violation event into the flight "
    "ring.  0 disables (latency histograms still record; "
    "tools/bench_gate.py owns cross-run regression gating)."
).double_conf(0.0)

# --- profiling (profiling/ — calibration store, cost model, advisor) -------

PROFILE_DIR = conf("spark.rapids.tpu.profile.dir").doc(
    "Directory for the persistent operator calibration store "
    "(calibration.json, atomic merge-on-write).  When set, every "
    "diagnostics-recorded query folds its per-operator spans "
    "(self_wall_ns, syncs, H2D/D2H bytes, fallback/retry outcomes) into "
    "per-(operator, expr-fingerprint, shape-bucket) decaying EWMAs at "
    "query_end, and collect() annotates the plan with cost-model "
    "predictions (cost_model_hits/misses/cost_model_predicted_wall_ns "
    "counters, explain('cost')).  Unset (default): zero profiling-module "
    "calls per query — the disabled path is free."
).string_conf(None)

PROFILE_EWMA_ALPHA = conf("spark.rapids.tpu.profile.ewmaAlpha").doc(
    "Decay factor for the calibration store's exponentially weighted "
    "moving averages: new = alpha*obs + (1-alpha)*old.  Higher tracks "
    "drift faster; lower smooths noisy walls.  Clamped to (0, 1]."
).double_conf(0.25)

PROFILE_COST_MODEL_ENABLED = conf(
    "spark.rapids.tpu.profile.costModel.enabled").doc(
    "With profile.dir set, walk the planned exec tree before execution "
    "and predict per-operator wall / transfer bytes / confidence from "
    "the calibration store (explain('cost'), the cost_model diagnostics "
    "event, and the cost_model_* counters).  false: the store still "
    "accumulates observations but no plan-time prediction runs."
).boolean_conf(True)

PROFILE_ADVISOR_ENABLED = conf(
    "spark.rapids.tpu.profile.advisor.enabled").doc(
    "Consult the qualification advisory file (tools/qualify.py "
    "--advisory-out) at plan time: an operator class the profile shows "
    "as persistently fallback-heavy is routed to its native/CPU "
    "placement (advisor_plan_fallbacks counter) while every other class "
    "keeps its default placement.  Off by default — the seed of "
    "cost-based routing, opt-in until the cost model earns trust."
).boolean_conf(False)

PROFILE_ADVISOR_FILE = conf("spark.rapids.tpu.profile.advisor.file").doc(
    "Path of the advisory JSON the plan-time consult reads.  Unset: "
    "<spark.rapids.tpu.profile.dir>/advisory.json when profile.dir is "
    "set, else no advisory."
).string_conf(None)

# --- progress (progress/ — live per-operator progress, ETA, stalls) --------

PROGRESS_ENABLED = conf("spark.rapids.tpu.progress.enabled").doc(
    "Live query introspection: every lifecycle-managed collect() "
    "registers with the process-global progress tracker — per-operator "
    "batches/rows/bytes produced so far, percent-complete and ETA "
    "joined from the profiling cost model's predictions, and causal "
    "attribution of background work (AOT compiles, scan prefetch "
    "uploads, shuffle-write serialization) to the owning query.  "
    "Surfaced via session.progress(), live df.explain('analyze'), the "
    "/progress JSON route on the telemetry HTTP endpoint, and the "
    "sampler's progress_* gauges.  Disabled (default): every "
    "instrumentation site costs one ambient attribute check — zero "
    "calls into progress modules (docs/progress.md)."
).boolean_conf(False)

PROGRESS_STALL_MS = conf("spark.rapids.tpu.progress.stallMs").doc(
    "Heartbeat stall detector (requires progress.enabled): when NO "
    "operator of a live query advances — no batch pull completes and "
    "no background work is attributed — for this many ms, the "
    "watchdog's stall scan bumps stalls_detected, emits a query_stall "
    "diagnostics event naming the stuck operator (the innermost "
    "in-flight batch pull), and dumps a flight-recorder post-mortem "
    "embedding the live progress snapshot.  Re-arms after each "
    "advance, so a later wedge of the same query reports again.  "
    "0 disables stall detection.").long_conf(0)

PROGRESS_MAX_FINISHED = conf("spark.rapids.tpu.progress.maxFinished").doc(
    "Recently finished query snapshots the tracker retains for the "
    "/progress surface (oldest evicted first); live queries are always "
    "reported regardless.").integer_conf(32)

# --- accounting (accounting/ — per-query resource bills + sentinel) --------

ACCOUNTING_ENABLED = conf("spark.rapids.tpu.accounting.enabled").doc(
    "Per-query resource bills: every HBM registration/spill/release in "
    "the spill framework charges the owning query's ledger (device "
    "bytes charged/released, per-query peak, device-byte-seconds, "
    "spill traffic per tier with the draining exchange partition "
    "stamped), joined at collect end with the query's counter deltas "
    "(H2D/D2H bytes, launches, syncs, compile wall), progress "
    "background wall, and federated worker store bytes — emitted as a "
    "resource_bill diagnostics event plus bill_* telemetry gauges, and "
    "settled at lifecycle exit (a nonzero residual is a leak the test "
    "gate fails on).  Disabled (default): every charge site costs one "
    "ambient attribute check — zero calls into accounting modules "
    "(docs/accounting.md)."
).boolean_conf(False)

ACCOUNTING_RETAINED_BILLS = conf(
    "spark.rapids.tpu.accounting.retainedBills").doc(
    "Settled bills the ledger registry retains (oldest evicted first) "
    "for tools/history.py pages and bench.py columns.  An evicted "
    "bill's nonzero residual stays visible to the leak gate."
).integer_conf(64)

ACCOUNTING_SENTINEL_ENABLED = conf(
    "spark.rapids.tpu.accounting.sentinel.enabled").doc(
    "With accounting.enabled AND profile.dir set, compare each "
    "finished query's bill + wall against the calibration store's "
    "per-plan-signature EWMAs (wall, host syncs, spill bytes, "
    "compile-cache hit rate) at collect exit.  An excursion past the "
    "ratio/z thresholds bumps perf_regressions_flagged, emits a "
    "regression diagnostics event + flight-ring event, and dumps a "
    "post-mortem bundle carrying the offending bill, the violated "
    "baseline, and the per-operator self-wall delta table naming the "
    "regressed operator.  Flagged observations are NOT folded into "
    "the baseline; only clean status=ok queries calibrate."
).boolean_conf(True)

ACCOUNTING_SENTINEL_MIN_SAMPLES = conf(
    "spark.rapids.tpu.accounting.sentinel.minSamples").doc(
    "Observations a plan signature's baseline needs before the "
    "sentinel evaluates it — younger baselines only accumulate."
).integer_conf(3)

ACCOUNTING_SENTINEL_WALL_RATIO = conf(
    "spark.rapids.tpu.accounting.sentinel.wallRatio").doc(
    "Multiplicative excursion gate: a dimension must exceed its "
    "baseline EWMA by this factor to flag (wall additionally requires "
    "the z gate; syncs/spill additionally require absolute excess "
    "floors so tiny baselines cannot alarm on noise)."
).double_conf(2.0)

ACCOUNTING_SENTINEL_Z = conf("spark.rapids.tpu.accounting.sentinel.z").doc(
    "Z-score gate for the wall dimension: (observed - baseline) / "
    "deviation-EWMA must reach this many sigmas (deviation floored at "
    "5% of the baseline mean so near-constant history cannot make "
    "jitter look significant)."
).double_conf(4.0)

ACCOUNTING_SENTINEL_MIN_WALL_EXCESS_MS = conf(
    "spark.rapids.tpu.accounting.sentinel.minWallExcessMs").doc(
    "Absolute wall excess floor in ms: below this a ratio/z excursion "
    "on a sub-millisecond baseline is noise, not a regression."
).double_conf(5.0)

MEM_DEBUG = conf("spark.rapids.memory.gpu.debug").doc(
    "Log arena allocations.").boolean_conf(False)

TEST_RETRY_OOM_INJECTION_MODE = conf(
    "spark.rapids.sql.test.injectRetryOOM").doc(
    "Test hook: force a RetryOOM/SplitAndRetryOOM in retry blocks "
    "(reference: RmmSpark.forceRetryOOM).").string_conf("NONE")

# --- TPU-specific ----------------------------------------------------------

ORC_DEVICE_DECODE = conf(
    "spark.rapids.sql.format.orc.decode.device").doc(
    "Decode ORC stripe numerics on device: host parses protobuf footers "
    "and splits RLEv2 runs, the Pallas bit-unpack kernel expands DIRECT "
    "payloads (MSB packing bridged by byte/value bit-reversal), DELTA "
    "runs cumsum on device.  Unsupported shapes silently fall back to "
    "the pyarrow host decode.  Off by default for the same reason as the "
    "parquet knob: many small eager dispatches per run.").boolean_conf(False)

DECODE_LOG_FALLBACK = conf(
    "spark.rapids.sql.decode.logFallback").doc(
    "Log (stderr) why a file fell back from the device decode (parquet "
    "OR orc) to the host pyarrow decode — silent fallbacks are otherwise "
    "invisible.").boolean_conf(False)

TPU_SCAN_CACHE = conf("spark.rapids.tpu.scan.cacheDeviceBatches").doc(
    "Keep scanned batches resident in HBM across queries over the same "
    "table (the df.cache / ParquetCachedBatchSerializer analog).  Off by "
    "default; benchmarks of warm-data queries enable it.").boolean_conf(False)

TPU_WHOLESTAGE_FUSION = conf("spark.rapids.tpu.wholeStageFusion.enabled").doc(
    "Fuse chains of narrow operators (project/filter) into one jitted XLA "
    "program per stage.").boolean_conf(True)


class TpuConf:
    """Immutable snapshot view over a settings dict (RapidsConf analog)."""

    def __init__(self, settings: Optional[Dict[str, str]] = None):
        self.settings: Dict[str, str] = dict(settings or {})

    def get(self, entry: ConfEntry):
        return entry.get(self.settings)

    def get_key(self, key: str):
        e = _REGISTRY.get(key)
        if e is None:
            raise KeyError(f"unknown config {key}")
        return self.get(e)

    def is_op_enabled(self, op_name: str, kind: str = "expression") -> bool:
        """Per-op kill switch: spark.rapids.sql.<kind>.<OpName> (reference:
        RapidsConf.isOperatorEnabled)."""
        raw = self.settings.get(f"spark.rapids.sql.{kind}.{op_name}")
        if raw is None:
            return True
        return str(raw).strip().lower() in ("true", "1", "yes")

    def with_settings(self, **kv) -> "TpuConf":
        s = dict(self.settings)
        s.update({k.replace("__", "."): v for k, v in kv.items()})
        return TpuConf(s)

    def set(self, key: str, value) -> "TpuConf":
        s = dict(self.settings)
        s[key] = value
        return TpuConf(s)

    # -- convenience properties used throughout the codebase --
    @property
    def sql_enabled(self):
        return self.get(SQL_ENABLED)

    @property
    def ansi_enabled(self):
        return self.get(ANSI_ENABLED)

    @property
    def explain(self):
        return self.get(EXPLAIN)

    @property
    def batch_size_bytes(self):
        return self.get(BATCH_SIZE_BYTES)

    @property
    def concurrent_tpu_tasks(self):
        return self.get(CONCURRENT_TPU_TASKS)

    @property
    def shuffle_partitions(self):
        return self.get(SHUFFLE_PARTITIONS)


_lock = threading.Lock()
_active = TpuConf()
_tls = threading.local()


def get_conf() -> TpuConf:
    override = getattr(_tls, "override", None)
    return override if override is not None else _active


def set_conf(c: TpuConf) -> TpuConf:
    global _active
    with _lock:
        _active = c
    return c


class ambient_conf:
    """Thread-local conf override: background threads (the AOT compile
    pool) trace programs whose expressions read the ambient conf at trace
    time; pinning the conf captured at submit keeps a warm-up's trace
    consistent with its registry key even if the main thread re-plans a
    different session mid-compile."""

    def __init__(self, conf: TpuConf):
        self._conf = conf

    def __enter__(self):
        self._prev = getattr(_tls, "override", None)
        _tls.override = self._conf
        return self._conf

    def __exit__(self, *a):
        _tls.override = self._prev


def all_entries() -> List[ConfEntry]:
    """Walked by docs/gen_configs.py to emit the config reference table."""
    return [e for _, e in sorted(_REGISTRY.items())]
