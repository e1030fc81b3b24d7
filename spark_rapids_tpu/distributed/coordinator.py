"""Coordinator — membership, heartbeat liveness, placement, loss
recovery bookkeeping for the cross-host tier.

Reference analog: the driver-side shuffle coordination the reference
delegates to Spark's MapOutputTracker + the RapidsShuffleHeartbeat
endpoint (SURVEY.md §2.7); Theseus (arXiv:2508.05029) centralizes
exactly this: a lightweight control plane that PLACES data movement and
survives executor churn.  The coordinator owns:

  * **membership** — workers join (HELLO over the control listener) and
    leave (GOODBYE / dead socket) between queries; every join warms from
    the shared persistent stores on the worker side and bumps
    ``workers_joined``.
  * **liveness** — each worker heartbeats every
    ``spark.rapids.tpu.distributed.heartbeatMs``; the monitor thread
    counts late workers (``worker_heartbeat_misses``) and declares one
    LOST past ``workerLostMs`` (or instantly on a dead socket reported
    by the block layer).  A loss bumps ``worker_lost``, records a
    per-worker circuit-breaker entry (key ``("DistributedWorker",
    worker_id)``) so a flapping worker that rejoins is QUARANTINED until
    the breaker TTL re-probe, emits the ``distributed`` diagnostics
    event, and dumps a flight-recorder post-mortem bundle carrying the
    placement table and the re-drive plan.
  * **placement** — ``place()`` spreads one exchange's reduce partitions
    over placeable workers, least-loaded first, weighted by each
    worker's advertised memory (fed by ``exec/partition_sizing.py``
    estimates on the exchange side).
  * **re-drive bookkeeping** — a loss re-places the dead worker's
    partitions on survivors and queues them for re-drive; the exchange
    client claims the queue and re-pushes the retained producer-side
    blocks (lineage retry), bumping ``partitions_replayed``.

  * **gray failure** (ISSUE 20, docs/distributed.md) — the full state
    machine is ALIVE <-> DEGRADED -> LOST: every data-plane op walls
    into a per-worker p95-biased latency EWMA (refined by heartbeat-
    federated worker service times); a worker past ``slowFactor``x the
    fleet median, or stacking consecutive soft-deadline misses, is
    DEGRADED — demoted in capacity-weighted placement, its pending
    partitions speculatively re-driven onto healthy survivors
    (``speculative_redrives``), quarantine breaker untouched — and
    promoted back after ``promoteAfterOks`` within-deadline
    observations.  ``soft_deadline_s()`` is what the client's hedged
    fetch path races against.

The coordinator never holds partition DATA — blocks flow producer ->
worker -> consumer; losing the coordinator process loses the query but
never corrupts one (every data block is CRC-framed end to end).
"""
from __future__ import annotations

import json
import socket
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu.distributed import protocol as P
from spark_rapids_tpu.distributed.protocol import WorkerDegraded, WorkerLost

ALIVE = "ALIVE"
QUARANTINED = "QUARANTINED"
LOST = "LOST"
LEFT = "LEFT"
# gray failure (ISSUE 20): slow, not dead — demoted in placement, its
# pending partitions speculated onto healthy survivors, promotable back
# to ALIVE on sustained recovery.  ALIVE <-> DEGRADED -> LOST.
DEGRADED = "DEGRADED"

# the per-worker circuit-breaker key family: first element mirrors the
# (operator-class, fingerprint) shape the breaker registry indexes by
BREAKER_OP = "DistributedWorker"


def _full_jitter_sleep(attempt: int, base_s: float = 0.02,
                       cap_s: float = 0.2, sleep=time.sleep,
                       rand=None) -> float:
    """Full-jitter backoff for the distributed retry path (ISSUE 20
    audit): sleep uniform(0, min(base * 2^(attempt-1), cap)) — a
    coordinated fleet retrying a hiccuping worker must not re-arrive in
    lockstep the way the old fixed ``0.02 * attempt`` schedule did.
    Returns the slept duration so the regression test can pin the
    distribution without patching time."""
    import random as _random

    cap = min(base_s * (2 ** max(attempt - 1, 0)), cap_s)
    delay = (rand if rand is not None else _random.random)() * cap
    sleep(delay)
    return delay


class WorkerInfo:
    __slots__ = ("worker_id", "host", "data_port", "pid", "mem_bytes",
                 "state", "last_hb", "joined_at", "control",
                 "hb_missed", "probe_failed", "warmed_entries",
                 "counters", "store_stats", "mirror", "mirror_last_n",
                 "clock_offset_s", "held", "lat_ewma_s", "lat_samples",
                 "miss_streak", "ok_streak", "slow_ticks",
                 "degraded_since")

    def __init__(self, worker_id: str, host: str, data_port: int,
                 pid: int, mem_bytes: int, control: socket.socket,
                 warmed_entries: int = 0, mirror_capacity: int = 512):
        self.worker_id = worker_id
        self.host = host
        self.data_port = data_port
        self.pid = pid
        self.mem_bytes = max(int(mem_bytes), 1)
        self.state = ALIVE
        self.last_hb = time.monotonic()
        self.joined_at = time.monotonic()
        self.control = control
        self.hb_missed = False
        self.probe_failed = False
        self.warmed_entries = warmed_entries
        # federated telemetry (ISSUE 15): the worker's latest
        # heartbeat-reported counter snapshot + store stats, the mirror
        # of its diagnostics ring (what a SIGKILLed worker's post-mortem
        # contains), the ring sequence already folded (heartbeat deltas
        # and full `dump` pulls both dedup on it), and the
        # handshake-estimated clock offset (driver wall - worker wall;
        # min over samples, so one slow frame cannot skew it)
        self.counters: Dict[str, int] = {}
        self.store_stats: Dict[str, int] = {}
        self.mirror: deque = deque(maxlen=max(int(mirror_capacity), 1))
        self.mirror_last_n = 0
        self.clock_offset_s: Optional[float] = None
        # crash recovery (ISSUE 16): the (wire_exch, pid, n_blocks,
        # max_seq) inventory a re-attaching worker enumerated in its
        # HELLO — what a reborn coordinator rebuilds the placement map
        # from when adopting a journaled stage lease
        self.held: List[Tuple[int, int, int, int]] = []
        # gray-failure bookkeeping (ISSUE 20): a p95-biased latency
        # EWMA over this worker's data-plane op walls (driver-observed,
        # refined by the heartbeat-federated worker-side service time),
        # consecutive soft-deadline miss / within-deadline streaks,
        # monitor ticks spent past slowFactor x the fleet median, and
        # when the worker entered DEGRADED (None while healthy)
        self.lat_ewma_s: Optional[float] = None
        self.lat_samples = 0
        self.miss_streak = 0
        self.ok_streak = 0
        self.slow_ticks = 0
        self.degraded_since: Optional[float] = None


class Coordinator:
    """One per process; built lazily by the first distributed exchange
    (or explicitly by tests/harnesses via ``get_coordinator``)."""

    def __init__(self, conf=None):
        from spark_rapids_tpu.config import (
            DISTRIBUTED_DEGRADE_AFTER_MISSES,
            DISTRIBUTED_HEARTBEAT_MS,
            DISTRIBUTED_HEDGE_ENABLED,
            DISTRIBUTED_LOSS_BREAKER_THRESHOLD,
            DISTRIBUTED_OP_TIMEOUT_MS,
            DISTRIBUTED_PROMOTE_AFTER_OKS,
            DISTRIBUTED_PUT_RETRIES,
            DISTRIBUTED_SLOW_FACTOR,
            DISTRIBUTED_SOFT_DEADLINE_FACTOR,
            DISTRIBUTED_SOFT_DEADLINE_MIN_MS,
            DISTRIBUTED_TELEMETRY_RING,
            DISTRIBUTED_TRACE_ENABLED,
            DISTRIBUTED_WORKER_LOST_MS,
            RESILIENCE_BREAKER_TTL_SEC,
            get_conf,
        )

        c = conf if conf is not None else get_conf()
        self.heartbeat_s = max(
            int(c.get(DISTRIBUTED_HEARTBEAT_MS)), 10) / 1000.0
        self.lost_s = max(int(c.get(DISTRIBUTED_WORKER_LOST_MS)),
                          int(c.get(DISTRIBUTED_HEARTBEAT_MS))) / 1000.0
        self.op_timeout_s = max(
            int(c.get(DISTRIBUTED_OP_TIMEOUT_MS)), 100) / 1000.0
        self.put_retries = int(c.get(DISTRIBUTED_PUT_RETRIES))
        self.breaker_threshold = int(
            c.get(DISTRIBUTED_LOSS_BREAKER_THRESHOLD))
        self.breaker_ttl_s = float(c.get(RESILIENCE_BREAKER_TTL_SEC))
        self.trace_enabled = bool(c.get(DISTRIBUTED_TRACE_ENABLED))
        self.telemetry_ring = int(c.get(DISTRIBUTED_TELEMETRY_RING))
        # gray-failure resilience (ISSUE 20)
        self.hedge_enabled = bool(c.get(DISTRIBUTED_HEDGE_ENABLED))
        self.soft_factor = max(
            float(c.get(DISTRIBUTED_SOFT_DEADLINE_FACTOR)), 1.0)
        self.soft_min_s = max(
            int(c.get(DISTRIBUTED_SOFT_DEADLINE_MIN_MS)), 1) / 1000.0
        self.slow_factor = max(
            float(c.get(DISTRIBUTED_SLOW_FACTOR)), 1.0)
        self.degrade_after = max(
            int(c.get(DISTRIBUTED_DEGRADE_AFTER_MISSES)), 1)
        self.promote_after = max(
            int(c.get(DISTRIBUTED_PROMOTE_AFTER_OKS)), 1)

        self._lock = threading.Lock()
        self._workers: Dict[str, WorkerInfo] = {}
        # wire ids: the identifier used in put/fetch/release headers is
        # minted HERE, never reused for the coordinator's lifetime.
        # Shuffle-manager ids are process-unique themselves (the
        # module-level counter in shuffle/manager.py), so for manager
        # callers this is defense in depth; it is load-bearing for
        # DIRECT place() callers (tests, tools) whose raw exchange ids
        # can repeat — a stale worker-store entry under a colliding
        # (exch, pid) key would satisfy the consumer's completeness
        # check with WRONG (CRC-valid) rows
        import itertools as _it

        self._wire_ids = _it.count(1)
        self._wire_of: Dict[int, int] = {}
        # (exch, pid) -> worker_id
        self._placement: Dict[Tuple[int, int], str] = {}
        # shipped-block bookkeeping for the leak gate: (exch, pid) ->
        # blocks currently held remotely
        self._holdings: Dict[Tuple[int, int], int] = {}
        # pids a loss re-placed, awaiting producer re-drive
        self._redrives: Dict[int, Set[int]] = {}
        # gray failure (ISSUE 20): workers speculation moved an
        # exchange's partitions AWAY from.  Unlike a LOST worker, a
        # DEGRADED one still runs — release_exchange must broadcast to
        # these former owners too, or their store copies outlive the
        # query
        self._former_owners: Dict[int, Set[str]] = {}
        # put-receipt reconciliation (ISSUE 15): blocks this coordinator
        # shipped vs blocks workers REPORT having received (heartbeat
        # counters: store_puts + store_put_dedups).  A rejoin resets a
        # worker's counters, so the superseded incarnation's last report
        # retires into _acked_retired.  gauges() surfaces the difference
        # as `dist_blocks_unacked` — nonzero past heartbeat lag means
        # frames the CRC can't flag because they never arrived at all
        # (or a dead worker's unreported tail, exactly what re-drive
        # re-ships).
        self._shipped_blocks = 0
        self._acked_retired = 0
        # data-plane connections (shared by put/fetch/release), one per
        # worker, serialized by a per-worker lock
        self._conns: Dict[str, socket.socket] = {}
        self._conn_locks: Dict[str, threading.Lock] = {}
        self._stop = threading.Event()

        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(32)
        self.port = self._listener.getsockname()[1]
        # crash recovery (ISSUE 16): publish this incarnation's control
        # endpoint under the recovery root so workers that outlived a
        # dead driver re-dial the successor (atomic tmp+rename; workers
        # poll the file during their bounded re-attach window)
        from spark_rapids_tpu.config import RECOVERY_ENABLED

        if bool(c.get(RECOVERY_ENABLED)):
            from spark_rapids_tpu.lifecycle import journal as _journal

            try:
                _journal.write_endpoint(_journal.resolve_root(c),
                                        "127.0.0.1", self.port)
            # tpulint: disable=cancel-swallow (durability isolation: an
            # unwritable endpoint file degrades re-attach, never the
            # coordinator itself)
            except Exception:
                pass
        self._threads: List[threading.Thread] = []
        for target, name in ((self._accept_loop, "accept"),
                             (self._monitor_loop, "monitor")):
            t = threading.Thread(target=target, daemon=True,
                                 name=f"srt-dist-coord-{name}")
            t.start()
            self._threads.append(t)

    # -- membership ------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, addr = self._listener.accept()
            except OSError:
                if self._stop.is_set():
                    return
                # transient accept failure (EMFILE during a heavy
                # shuffle, interrupted syscall): keep serving joins —
                # a dead accept loop would silently disable elastic
                # membership for the rest of the process
                time.sleep(self.heartbeat_s)
                continue
            conn.settimeout(self.lost_s * 2 + 1.0)
            t = threading.Thread(
                target=self._control_conn, args=(conn, addr[0]),
                daemon=True, name="srt-dist-coord-control")
            t.start()

    def _control_conn(self, conn: socket.socket, host: str) -> None:
        """One worker's control connection: HELLO, then heartbeats until
        EOF/error (= dead socket)."""
        wid = None
        try:
            header, _ = P.recv_msg(conn)
            if header.get("op") != "hello":
                P.send_msg(conn, {"error": "expected hello"})
                return
            wid = str(header["worker_id"])
            self._admit(wid, host, header, conn)
            P.send_msg(conn, {"op": "welcome", "worker_id": wid})
            while not self._stop.is_set():
                msg, _ = P.recv_msg(conn)
                op = msg.get("op")
                if op == "heartbeat":
                    self._heartbeat(wid, msg)
                elif op == "goodbye":
                    self._leave(wid)
                    return
        except (OSError, ConnectionError, P.ProtocolCorruption):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass
            if wid is not None and not self._stop.is_set():
                # EOF without goodbye: dead socket — LOST, unless this
                # connection was already superseded by a rejoin, the
                # worker left cleanly, or the coordinator itself is
                # shutting down (a teardown must not bleed stray loss
                # declarations into whatever runs next)
                with self._lock:
                    w = self._workers.get(wid)
                    stale = w is None or w.control is not conn \
                        or w.state in (LOST, LEFT)
                if not stale:
                    self.declare_lost(wid, "control socket closed")

    def _admit(self, wid: str, host: str, header: Dict,
               conn: socket.socket) -> None:
        from spark_rapids_tpu.resilience.breaker import get_breaker

        info = WorkerInfo(wid, host, int(header["data_port"]),
                          int(header.get("pid", 0)),
                          int(header.get("mem_bytes", 1 << 20)), conn,
                          int(header.get("warmed_entries", 0)),
                          mirror_capacity=self.telemetry_ring)
        if "t_wall" in header:
            # clock-offset handshake: driver receipt wall minus worker
            # send wall.  Overestimates by the one-way frame latency;
            # heartbeats refine it (min over samples, see _fold below)
            info.clock_offset_s = time.time() - float(header["t_wall"])
        inventory = header.get("held") or []
        if inventory:
            # recovery re-HELLO (ISSUE 16): the worker outlived a dead
            # driver and is re-attaching with its held partitions.  Its
            # prior incarnation's control socket died WITH the driver,
            # so any ("DistributedWorker", id) breaker entry that loss
            # left behind is about the crash, not about this worker —
            # clear it outright; quarantining the one process that
            # still holds the checkpointed blocks would turn a
            # resumable query into a full re-execution
            info.held = [(int(e), int(p), int(n), int(mx))
                         for e, p, n, mx in inventory]
            get_breaker().clear_key((BREAKER_OP, wid))
        # flapping-worker quarantine: a worker id whose loss history
        # holds the breaker OPEN joins QUARANTINED (heartbeats, but is
        # never placed) until the TTL re-probe admits it again
        held = get_breaker().consult((BREAKER_OP, wid),
                                     self.breaker_ttl_s)
        if held is not None:
            info.state = QUARANTINED
        with self._lock:
            if info.held:
                # cross-incarnation wire-id safety: this coordinator's
                # counter restarted at 1, but the re-attached worker's
                # store still keys blocks by the DEAD incarnation's wire
                # ids — minting a colliding id would let stale
                # (CRC-valid!) blocks satisfy a new exchange's
                # completeness check with wrong rows.  Reseed past the
                # inventory's max before any place() can run.
                import itertools as _it

                nxt = next(self._wire_ids)
                top = max(e for e, _p, _n, _mx in info.held) + 1
                self._wire_ids = _it.count(max(nxt, top))
            old = self._workers.get(wid)
            if old is not None and old.counters:
                # the superseded incarnation's put receipts retire into
                # the running total — the rejoined process restarts its
                # counters at zero
                self._acked_retired += (
                    int(old.counters.get("store_puts", 0))
                    + int(old.counters.get("store_put_dedups", 0)))
            self._workers[wid] = info
            self._conn_locks.setdefault(wid, threading.Lock())
            # a rejoin supersedes the old connection; drop any stale
            # data conn so the next op dials the new port
            stale_conn = self._conns.pop(wid, None)
        if old is not None and old.control is not conn:
            try:
                old.control.close()
            except OSError:
                pass
        if stale_conn is not None:
            try:
                stale_conn.close()
            except OSError:
                pass
        PC.bump("workers_joined")
        self._diag_event("worker_joined" if info.state == ALIVE
                         else "worker_quarantined", wid,
                         f"mem={info.mem_bytes} state={info.state}")
        self._flight_event("worker_joined", worker_id=wid,
                           state=info.state)

    def _heartbeat(self, wid: str, msg: Optional[Dict] = None) -> None:
        tel = None
        with self._lock:
            w = self._workers.get(wid)
            if w is not None:
                w.last_hb = time.monotonic()
                w.hb_missed = False
                w.probe_failed = False
                # a quarantined worker re-probes via consult() in
                # placeable_workers(); heartbeats alone never un-lose a
                # LOST worker (it must rejoin with a fresh HELLO)
                if msg is not None:
                    tel = self._fold_telemetry_locked(w, msg)
        if tel is not None:
            # one ambient check: a recorded query sees the federation
            # arrive as `worker_telemetry` diagnostics events
            from spark_rapids_tpu.diagnostics import context as _DIAG

            rec = _DIAG.RECORDER
            if rec is not None:
                rec.worker_telemetry(wid, tel["blocks"], tel["bytes"],
                                     tel["mem_used"], tel["counters"])

    def _fold_telemetry_locked(self, w: WorkerInfo,
                               msg: Dict) -> Optional[Dict]:
        """Fold one heartbeat/dump payload into the worker's federated
        state (caller holds self._lock).  Returns the summary for the
        diagnostics event, or None when the payload carried no
        telemetry (an old-protocol worker)."""
        counters = msg.get("counters")
        if counters is None and "ring" not in msg:
            return None
        if isinstance(counters, dict):
            new = {k: int(v) for k, v in counters.items()}
            # federated latency refinement (ISSUE 20): the heartbeat-
            # piggybacked service-time counters contribute one mean-
            # per-op sample per fold to the worker's p95 EWMA — a
            # thrashing spill disk shows up here even when the driver
            # sent it no ops this interval.  Deltas against the prior
            # snapshot; a rejoin resets worker counters, which the
            # negative-delta guard skips.
            d_wall = (new.get("put_wall_ns", 0)
                      + new.get("fetch_wall_ns", 0)
                      - int(w.counters.get("put_wall_ns", 0))
                      - int(w.counters.get("fetch_wall_ns", 0)))
            d_ops = (new.get("store_puts", 0)
                     + new.get("store_put_dedups", 0)
                     + new.get("store_fetches", 0)
                     - int(w.counters.get("store_puts", 0))
                     - int(w.counters.get("store_put_dedups", 0))
                     - int(w.counters.get("store_fetches", 0)))
            if d_ops > 0 and d_wall >= 0:
                self._note_sample_locked(w, (d_wall / d_ops) / 1e9)
            w.counters = new
        w.store_stats = {k: int(msg[k]) for k in
                         ("blocks", "bytes", "mem_used", "spilled_blocks",
                          "partitions") if k in msg}
        for e in msg.get("ring") or ():
            n = int(e.get("n", 0))
            if n > w.mirror_last_n:
                w.mirror.append(e)
                w.mirror_last_n = n
        if "t_wall" in msg:
            off = time.time() - float(msg["t_wall"])
            if w.clock_offset_s is None or off < w.clock_offset_s:
                w.clock_offset_s = off
        return {"blocks": int(msg.get("blocks", 0)),
                "bytes": int(msg.get("bytes", 0)),
                "mem_used": int(msg.get("mem_used", 0)),
                "counters": dict(w.counters)}

    def _leave(self, wid: str) -> None:
        with self._lock:
            w = self._workers.get(wid)
            if w is None:
                return
            w.state = LEFT
            conn = self._conns.pop(wid, None)
        if conn is not None:
            try:
                conn.close()
            except OSError:
                pass
        self._diag_event("worker_left", wid, "")
        self._flight_event("worker_left", worker_id=wid)

    # -- liveness --------------------------------------------------------
    def _monitor_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_s):
            now = time.monotonic()
            late: List[str] = []
            lost: List[str] = []
            degraded: List[str] = []
            with self._lock:
                for wid, w in self._workers.items():
                    if w.state not in (ALIVE, QUARANTINED, DEGRADED):
                        continue
                    age = now - w.last_hb
                    if age > self.lost_s:
                        lost.append(wid)
                    elif age > self.heartbeat_s * 2 and not w.hb_missed:
                        w.hb_missed = True
                        late.append(wid)
                    if w.state == DEGRADED and wid not in lost:
                        degraded.append(wid)
            for wid in late:
                PC.bump("worker_heartbeat_misses")
            self._scan_stragglers()
            for wid in degraded:
                # a DEGRADED worker may carry no traffic (speculation
                # moved its partitions), so promotion cannot wait for
                # served ops — a timed data-port ping per scan keeps its
                # latency EWMA fed and banks the recovery streak
                t0 = time.monotonic()
                alive, _refused = self._probe_alive(wid)
                if alive:
                    self.note_op_latency(wid, time.monotonic() - t0)
            for wid in lost:
                # heartbeat silence alone is ambiguous on a BUSY driver:
                # a long GIL hold (XLA compile) starves the reader
                # threads, so frames sit unread while the worker is
                # fine.  An active data-port probe disambiguates — a
                # live worker answers, a SIGSTOPped one times out, a
                # SIGKILLed one refuses — and a TIMED-OUT probe must
                # fail twice in a row before declaring (one slow answer
                # under load is not a death certificate; a refused
                # connection is).
                alive, refused = self._probe_alive(wid)
                if alive:
                    self._heartbeat(wid)
                    continue
                with self._lock:
                    w = self._workers.get(wid)
                    first_failure = w is not None and not w.probe_failed
                    if w is not None:
                        w.probe_failed = True
                if first_failure and not refused:
                    continue      # re-probe next scan before declaring
                self.declare_lost(
                    wid, f"no heartbeat for {self.lost_s * 1000:.0f}ms "
                         f"and data-port probe failed")

    def _probe_alive(self, wid: str) -> Tuple[bool, bool]:
        """One ping against the worker's data listener (fresh
        connection; the pooled conn may be mid-operation).  Returns
        (alive, connection_refused) — refusal means the process is
        gone and needs no second opinion."""
        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state in (LOST, LEFT):
                return False, True
            host, port = w.host, w.data_port
        try:
            s = P.connect(host, port, self.op_timeout_s)
            try:
                rep, _ = P.request(s, {"op": "ping"})
                return bool(rep.get("ok")), False
            finally:
                s.close()
        except ConnectionRefusedError:
            return False, True
        except (OSError, ConnectionError, RuntimeError,
                P.ProtocolCorruption):
            return False, False

    def declare_lost(self, wid: str, reason: str) -> bool:
        """Idempotent LOST declaration: quarantine the id, re-place its
        partitions on survivors, queue them for re-drive, and emit the
        post-mortem bundle.  True when this call performed the
        declaration."""
        from spark_rapids_tpu.resilience.breaker import get_breaker

        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state in (LOST, LEFT):
                return False
            w.state = LOST
            control, conn = w.control, self._conns.pop(wid, None)
            owned = [k for k, owner in self._placement.items()
                     if owner == wid]
        for s in (control, conn):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        # re-place + queue re-drives FIRST: once the LOST state is
        # visible (state was flipped under the lock above) an observer
        # acting on it must find the re-drive plan already queued — the
        # breaker hook below can spend tens of ms building a post-mortem
        # bundle, and recovery must not wait on observability
        replaced = self._replace_owner(owned)
        PC.bump("worker_lost")
        get_breaker().record_failure((BREAKER_OP, wid),
                                     self.breaker_threshold,
                                     reason=f"worker lost: {reason}")
        plan = [{"exch": e, "pid": p, "to": to}
                for (e, p), to in sorted(replaced.items())]
        self._diag_event("worker_lost", wid,
                         f"{reason}; re-placing {len(plan)} partitions")
        self._flight_event("worker_lost", worker_id=wid, reason=reason,
                           replaced=len(plan))
        self._postmortem(wid, reason, plan)
        return True

    # -- gray failure (ISSUE 20) ----------------------------------------
    def _note_sample_locked(self, w: WorkerInfo, wall_s: float) -> None:
        """Fold one op wall into the worker's p95-biased latency EWMA
        (caller holds self._lock): overshoots pull the estimate up fast,
        undershoots bleed off slowly, so the estimate rides near the
        tail of the distribution rather than its mean."""
        if w.lat_ewma_s is None:
            w.lat_ewma_s = wall_s
        else:
            a = 0.5 if wall_s > w.lat_ewma_s else 0.05
            w.lat_ewma_s += a * (wall_s - w.lat_ewma_s)
        w.lat_samples += 1

    def soft_deadline_s(self, wid: str) -> Optional[float]:
        """The worker's current per-op soft deadline:
        max(softDeadlineMinMs, softDeadlineFactor x its p95 latency
        EWMA); the floor alone before any samples.  None when hedging
        is off — the caller then never hedges or counts misses."""
        if not self.hedge_enabled:
            return None
        with self._lock:
            w = self._workers.get(wid)
            ewma = None if w is None else w.lat_ewma_s
        if ewma is None:
            return self.soft_min_s
        return max(self.soft_min_s, self.soft_factor * ewma)

    def note_op_latency(self, wid: str, wall_s: float) -> None:
        """One completed data-plane op wall against one worker: feed
        the EWMA, judge it against the soft deadline derived from the
        PRIOR estimate (an op must not raise its own bar), and step the
        degrade/promote streaks."""
        degrade_evidence = None
        promote = False
        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state in (LOST, LEFT):
                return
            prior = w.lat_ewma_s
            self._note_sample_locked(w, wall_s)
            if prior is None:
                return
            deadline = max(self.soft_min_s, self.soft_factor * prior)
            if wall_s > deadline:
                w.miss_streak += 1
                w.ok_streak = 0
                if w.state == ALIVE \
                        and w.miss_streak >= self.degrade_after:
                    degrade_evidence = (
                        f"{w.miss_streak} consecutive soft-deadline "
                        f"misses (last {wall_s * 1e3:.1f}ms > "
                        f"{deadline * 1e3:.1f}ms)")
            else:
                w.ok_streak += 1
                w.miss_streak = 0
                promote = (w.state == DEGRADED
                           and w.ok_streak >= self.promote_after
                           and self._recovered_locked(w))
        if degrade_evidence is not None:
            self.declare_degraded(wid, degrade_evidence)
        elif promote:
            self._promote(wid)

    def note_soft_deadline_miss(self, wid: str) -> None:
        """A caller (the hedged fetch path) watched an op blow its soft
        deadline while still in flight — count the miss now; the op's
        eventual wall will feed the EWMA when it lands."""
        evidence = None
        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state in (LOST, LEFT):
                return
            w.miss_streak += 1
            w.ok_streak = 0
            if w.state == ALIVE and w.miss_streak >= self.degrade_after:
                evidence = (f"{w.miss_streak} consecutive soft-deadline "
                            f"misses (hedged fetches)")
        if evidence is not None:
            self.declare_degraded(wid, evidence)

    def _recovered_locked(self, w: WorkerInfo) -> bool:
        """Caller holds self._lock: is this worker's EWMA back under
        slowFactor x the healthy fleet's median?  Vacuously true with
        no healthy peers to compare against."""
        peers = [x.lat_ewma_s for x in self._workers.values()
                 if x.state == ALIVE and x.lat_ewma_s is not None]
        if not peers or w.lat_ewma_s is None:
            return True
        med = sorted(peers)[len(peers) // 2]
        return med <= 0 or w.lat_ewma_s <= self.slow_factor * med

    def _scan_stragglers(self) -> None:
        """One monitor tick of the fleet-median rule: an ALIVE worker
        whose EWMA sits past slowFactor x the fleet median for
        degradeAfterMisses consecutive scans is DEGRADED — the
        persistent-outlier complement to the per-op miss streak."""
        victims: List[Tuple[str, float, float]] = []
        with self._lock:
            sam = [w.lat_ewma_s for w in self._workers.values()
                   if w.state in (ALIVE, DEGRADED)
                   and w.lat_ewma_s is not None and w.lat_samples >= 3]
            if len(sam) >= 2:
                med = sorted(sam)[len(sam) // 2]
                for wid, w in self._workers.items():
                    if w.state != ALIVE or w.lat_ewma_s is None \
                            or w.lat_samples < 3:
                        continue
                    if med > 0 and w.lat_ewma_s > self.slow_factor * med:
                        w.slow_ticks += 1
                        if w.slow_ticks >= self.degrade_after:
                            victims.append((wid, w.lat_ewma_s, med))
                    else:
                        w.slow_ticks = 0
        for wid, ewma, med in victims:
            self.declare_degraded(
                wid, f"latency EWMA {ewma * 1e3:.1f}ms persistently > "
                     f"slowFactor({self.slow_factor:g}) x fleet median "
                     f"{med * 1e3:.1f}ms")

    def declare_degraded(self, wid: str, evidence: str) -> bool:
        """Demote one ALIVE worker to DEGRADED: speculate its pending
        partitions onto healthy survivors (lineage contract, same as
        loss recovery) WITHOUT declaring it LOST and WITHOUT the
        quarantine breaker — a slow worker is not a dead one.  It keeps
        heartbeating, keeps serving what it still owns, takes demoted
        placement weight, and promotes back on sustained recovery.
        True when this call performed the demotion."""
        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state != ALIVE:
                return False
            w.state = DEGRADED
            w.degraded_since = time.monotonic()
            w.ok_streak = 0
            w.slow_ticks = 0
            owned = [k for k, owner in self._placement.items()
                     if owner == wid]
            healthy = any(x.state == ALIVE
                          for x in self._workers.values())
        PC.bump("workers_degraded")
        replaced: Dict[Tuple[int, int], str] = {}
        if owned and healthy:
            # speculation re-uses the loss re-placement machinery (the
            # client re-drives from its retained producer-side queues;
            # the worker store's per-seq idempotence discards any
            # duplicate the in-flight originals already landed) — but
            # only when a healthy survivor exists; with none, the
            # partitions stay where they are (slow beats stranded)
            replaced = self._replace_owner(owned)
            if replaced:
                with self._lock:
                    for (e, _p) in replaced:
                        self._former_owners.setdefault(e, set()).add(wid)
                PC.bump("speculative_redrives", len(replaced))
        plan = [{"exch": e, "pid": p, "to": to}
                for (e, p), to in sorted(replaced.items())]
        self._diag_event(
            "worker_degraded", wid,
            f"{evidence}; speculating {len(plan)} pending partitions")
        self._flight_event("worker_degraded", worker_id=wid,
                           evidence=evidence, speculated=len(plan))
        self._postmortem(wid, evidence, plan, kind="worker_degraded")
        return True

    def _promote(self, wid: str) -> None:
        """DEGRADED -> ALIVE on sustained recovery (the note_op_latency
        streaks banked promoteAfterOks within-deadline observations and
        the EWMA is back under the fleet bar)."""
        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state != DEGRADED:
                return
            w.state = ALIVE
            since = w.degraded_since
            w.degraded_since = None
            w.miss_streak = 0
            w.slow_ticks = 0
        dur = (time.monotonic() - since) if since is not None else 0.0
        self._diag_event("worker_promoted", wid,
                         f"recovered after {dur * 1e3:.0f}ms degraded")
        self._flight_event("worker_promoted", worker_id=wid,
                           degraded_s=round(dur, 3))

    def fleet_pressure(self) -> float:
        """Fleet tail-latency pressure in [0, 1] for the governor
        (peek-only): the DEGRADED fraction of the fleet, or — when at
        least two workers carry latency estimates — how far the worst
        EWMA sits past slowFactor x the median, whichever is worse."""
        with self._lock:
            states = [w.state for w in self._workers.values()
                      if w.state in (ALIVE, DEGRADED)]
            sam = [w.lat_ewma_s for w in self._workers.values()
                   if w.state in (ALIVE, DEGRADED)
                   and w.lat_ewma_s is not None and w.lat_samples >= 3]
        if not states:
            return 0.0
        p = states.count(DEGRADED) / len(states)
        if len(sam) >= 2:
            med = sorted(sam)[len(sam) // 2]
            if med > 0:
                ratio = max(sam) / med
                p = max(p, (ratio - self.slow_factor) / self.slow_factor)
        return max(0.0, min(p, 1.0))

    def _replace_owner(
            self, keys: List[Tuple[int, int]]
    ) -> Dict[Tuple[int, int], str]:
        """Re-place the given (exch, pid) keys on surviving placeable
        workers and queue them for re-drive.  Keys with no survivor stay
        mapped to the dead worker — the client's re-drive attempt will
        raise WorkerLost and the fault domain falls back."""
        survivors = self.placeable_workers()
        out: Dict[Tuple[int, int], str] = {}
        if not survivors:
            with self._lock:
                for e, p in keys:
                    self._redrives.setdefault(e, set()).add(p)
            return out
        with self._lock:
            # re-verify under the lock: a CONCURRENT loss may have
            # flipped a snapshot survivor to LOST between the
            # placeable scan above and here — assigning to it would
            # strand these keys on a dead worker (its own declare_lost
            # already snapshotted its owned keys and will not re-run)
            live = [w for w in survivors if w.state == ALIVE]
            if not live:
                # last resort: a DEGRADED survivor is slow, not dead —
                # landing the keys on it beats stranding them
                live = [w for w in survivors if w.state == DEGRADED]
            if not live:
                for e, p in keys:
                    self._redrives.setdefault(e, set()).add(p)
                return out
            loads: Dict[str, float] = {w.worker_id: 0.0 for w in live}
            for k, owner in self._placement.items():
                if owner in loads:
                    loads[owner] += self._holdings.get(k, 0)
            by_id = {w.worker_id: w for w in live}
            for e, p in sorted(keys):
                wid = min(loads, key=lambda i: (loads[i] / by_id[i]
                                                .mem_bytes, i))
                self._placement[(e, p)] = wid
                self._holdings.pop((e, p), None)
                loads[wid] += 1
                self._redrives.setdefault(e, set()).add(p)
                out[(e, p)] = wid
        return out

    # -- placement -------------------------------------------------------
    def placeable_workers(self) -> List[WorkerInfo]:
        """ALIVE workers, DEGRADED ones (demoted — place() divides
        their capacity weight by slowFactor; a slow worker still beats
        no worker), plus QUARANTINED ones whose breaker TTL expired
        (the consult admits the re-probe, flipping them placeable)."""
        from spark_rapids_tpu.resilience.breaker import get_breaker

        out = []
        with self._lock:
            candidates = list(self._workers.values())
        for w in candidates:
            if w.state in (ALIVE, DEGRADED):
                out.append(w)
            elif w.state == QUARANTINED:
                if get_breaker().consult((BREAKER_OP, w.worker_id),
                                         self.breaker_ttl_s) is None:
                    with self._lock:
                        if w.state == QUARANTINED:
                            w.state = ALIVE
                            out.append(w)
                    self._diag_event("worker_probed", w.worker_id,
                                     "quarantine TTL expired")
        return out

    def live_worker_count(self) -> int:
        with self._lock:
            return sum(1 for w in self._workers.values()
                       if w.state == ALIVE)

    def worker_state(self, wid: str) -> Optional[str]:
        with self._lock:
            w = self._workers.get(wid)
            return w.state if w is not None else None

    def redrive_backlog(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._redrives.values())

    def wait_for_workers(self, n: int, timeout_s: float = 15.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.live_worker_count() >= n:
                return True
            time.sleep(0.02)
        return self.live_worker_count() >= n

    def place(self, exch: int, n_parts: int,
              est_bytes: Optional[int] = None) -> Dict[int, str]:
        """Spread one exchange's reduce partitions over placeable
        workers, least-loaded-by-capacity first (``est_bytes`` comes
        from the partition-sizing estimate when the planner had one)."""
        workers = self.placeable_workers()
        if not workers:
            raise WorkerLost("<none>", "no placeable workers")
        per_pid = (est_bytes / n_parts) if est_bytes else 1.0
        loads = {w.worker_id: 0.0 for w in workers}
        # capacity-weighted with DEGRADED demotion (ISSUE 20): a
        # straggler's advertised memory counts at 1/slowFactor, so it
        # receives proportionally fewer partitions while demoted but is
        # never starved outright
        cap = {w.worker_id: (w.mem_bytes / self.slow_factor
                             if w.state == DEGRADED else
                             float(w.mem_bytes))
               for w in workers}
        out: Dict[int, str] = {}
        with self._lock:
            self._wire_of.setdefault(exch, next(self._wire_ids))
            for pid in range(n_parts):
                wid = min(loads,
                          key=lambda i: (loads[i] / cap[i], i))
                loads[wid] += per_pid
                out[pid] = wid
                self._placement[(exch, pid)] = wid
        return out

    def _wire(self, exch: int) -> int:
        """The never-reused wire identifier for one exchange (falls
        back to the raw id for ops against unplaced exchanges)."""
        with self._lock:
            return self._wire_of.get(exch, exch)

    def owner_of(self, exch: int, pid: int) -> str:
        with self._lock:
            wid = self._placement.get((exch, pid))
        if wid is None:
            raise KeyError(f"partition ({exch}, {pid}) is not placed")
        return wid

    def placement_of(self, exch: int) -> Dict[int, str]:
        with self._lock:
            return {p: w for (e, p), w in self._placement.items()
                    if e == exch}

    def wire_of(self, exch: int) -> int:
        """Public wire-id accessor (ISSUE 16): the identifier a stage
        lease journals — the one that survives a driver restart,
        because worker stores key blocks under it."""
        return self._wire(exch)

    def worker_inventory(self) -> Dict[str, List[Tuple[int, int, int,
                                                       int]]]:
        """Every live worker's re-HELLO-enumerated holdings:
        worker_id -> [(wire_exch, pid, n_blocks, max_seq), ...].  Empty
        lists for workers that joined fresh — the lease-adoption check
        in lifecycle/journal.py matches journaled block counts against
        this."""
        with self._lock:
            return {wid: list(w.held)
                    for wid, w in self._workers.items()
                    if w.state == ALIVE}

    def adopt_exchange(self, wire: int, placement: Dict[int, str],
                       counts: Optional[Dict[int, int]] = None) -> None:
        """Rebuild one journaled exchange's placement from re-attached
        workers' inventories (ISSUE 16).  The exchange registers under
        its ORIGINAL wire id (that is the key the worker stores hold),
        holdings are restored so the leak gate and gauges track the
        adopted blocks, and the wire-id counter reseeds past it so a
        fresh place() can never mint a colliding id."""
        import itertools as _it

        with self._lock:
            self._wire_of[wire] = wire
            for pid, wid in placement.items():
                self._placement[(wire, pid)] = wid
                if counts:
                    self._holdings[(wire, pid)] = int(
                        counts.get(pid, 0))
            nxt = next(self._wire_ids)
            self._wire_ids = _it.count(max(nxt, wire + 1))
        self._diag_event("exchange_adopted", "-",
                         f"wire={wire} n_parts={len(placement)}")

    def release_orphan_holdings(self, keep: Set[int]) -> int:
        """Release every re-HELLO-held wire id that is neither in
        ``keep`` (still-adoptable journaled leases) nor currently placed
        (an adoption mid-serve) — blocks a dead incarnation shipped but
        never lease-committed must not outlive its journal (ISSUE 16:
        the zero-stranded-partitions pin).  Returns wires released."""
        with self._lock:
            placed = set(self._wire_of.values())
            victims: Dict[str, Set[int]] = {}
            for wid, w in self._workers.items():
                if w.state != ALIVE or not w.held:
                    continue
                drop = {e for (e, _p, _n, _mx) in w.held
                        if e not in keep and e not in placed}
                if drop:
                    victims[wid] = drop
                    w.held = [h for h in w.held if h[0] not in drop]
        n = 0
        for wid, wires in sorted(victims.items()):
            for wire in sorted(wires):
                try:
                    self._request(wid, {"op": "release", "exch": wire},
                                  cancellable=False)
                    n += 1
                except (WorkerLost, RuntimeError, OSError):
                    # a dead/slow worker's store dies with its process
                    pass
            self._diag_event("orphans_released", wid,
                             f"wires={sorted(wires)}")
        return n

    def claim_redrives(self, exch: int) -> Set[int]:
        """Atomically take (and clear) the exchange's pending re-drive
        pids — the producer-side client re-pushes them from its spilled
        partition queues."""
        with self._lock:
            return self._redrives.pop(exch, set())

    def mark_redrive(self, exch: int, pid: int) -> None:
        """Queue one partition for re-drive (the consumer found a
        worker's copy incomplete — e.g. it restarted empty)."""
        with self._lock:
            self._redrives.setdefault(exch, set()).add(pid)

    # -- data plane ------------------------------------------------------
    def _data_conn_locked_args(self, wid: str):
        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state in (LOST, LEFT):
                raise WorkerLost(wid, f"state={'?' if w is None else w.state}")
            lock = self._conn_locks.setdefault(wid, threading.Lock())
            return w, lock

    def _request(self, wid: str, header: Dict, blobs=(),
                 cancellable: bool = True) -> Tuple[Dict, List[bytes]]:
        """One data-plane request to one worker, with bounded transient
        retry (connection refused/reset/timeout may heal); exhausted
        retries or a LOST/unknown worker raise :class:`WorkerLost` after
        declaring the loss.  ``cancellable=False`` is the CLEANUP
        contract: a release broadcast for a cancelled query must still
        reach the workers (remote copies must never outlive the query),
        so it does not observe the tripped CancelToken."""
        from spark_rapids_tpu.lifecycle.context import check_cancel
        from spark_rapids_tpu.resilience.classify import (
            TRANSIENT,
            classify_failure,
        )

        attempt = 0
        while True:
            if cancellable:
                check_cancel()
            w, lock = self._data_conn_locked_args(wid)
            t0 = time.monotonic()
            try:
                with lock:
                    conn = self._conns.get(wid)
                    if conn is None:
                        conn = P.connect(w.host, w.data_port,
                                         self.op_timeout_s)
                        with self._lock:
                            self._conns[wid] = conn
                    try:
                        out = P.request(conn, header, blobs)
                    except (OSError, ConnectionError):
                        # one reconnect-and-retry inside the same
                        # attempt: the pooled conn may simply be stale
                        with self._lock:
                            if self._conns.get(wid) is conn:
                                del self._conns[wid]
                        try:
                            conn.close()
                        except OSError:
                            pass
                        conn = P.connect(w.host, w.data_port,
                                         self.op_timeout_s)
                        with self._lock:
                            self._conns[wid] = conn
                        out = P.request(conn, header, blobs)
                # per-op latency feed (ISSUE 20): every served data-
                # plane op walls into the worker's p95 EWMA and steps
                # the degrade/promote streaks
                self.note_op_latency(wid, time.monotonic() - t0)
                return out
            except (OSError, ConnectionError, socket.timeout,
                    P.RemoteOpError, P.ProtocolCorruption) as e:
                # ALWAYS evict the pooled conn: a corrupted frame in
                # particular leaves the TCP stream mid-frame
                # desynchronized — reusing it would fail every later op
                # against this worker with bad-magic noise
                with self._lock:
                    if self._conns.get(wid) is not None:
                        try:
                            self._conns.pop(wid).close()
                        except OSError:
                            pass
                attempt += 1
                # RemoteOpError: the worker answered but could not
                # serve (ENOSPC on its spill dir, a racing release) —
                # treat like a dead socket: declare + re-place, never
                # let it escape as DETERMINISTIC and indict the
                # query's operator breaker.  ProtocolCorruption retries
                # on a FRESH connection (frame desync heals with the
                # socket; persistent corruption becomes a loss).
                retryable = isinstance(e, P.ProtocolCorruption) \
                    or (not isinstance(e, P.RemoteOpError)
                        and classify_failure(e) == TRANSIENT)
                if retryable and attempt <= self.put_retries:
                    _full_jitter_sleep(attempt)
                    continue
                with self._lock:
                    ww = self._workers.get(wid)
                    is_degraded = ww is not None \
                        and ww.state == DEGRADED
                if is_degraded:
                    # a DEGRADED worker that cannot serve this op is
                    # still heartbeating — speculate whatever it still
                    # owns (demoted placement may have landed keys on
                    # it after the demotion) and surface the typed
                    # degradation (the caller re-drives) without a loss
                    # declaration or the quarantine breaker
                    with self._lock:
                        owned = [k for k, o in self._placement.items()
                                 if o == wid]
                        healthy = any(x.state == ALIVE for x in
                                      self._workers.values())
                    if owned and healthy:
                        moved = self._replace_owner(owned)
                        if moved:
                            with self._lock:
                                for (e2, _p2) in moved:
                                    self._former_owners.setdefault(
                                        e2, set()).add(wid)
                            PC.bump("speculative_redrives", len(moved))
                    raise WorkerDegraded(
                        wid, f"{type(e).__name__}: {e}") from e
                self.declare_lost(wid, f"{type(e).__name__}: {e}")
                raise WorkerLost(wid, f"{type(e).__name__}: {e}") from e

    def _trace_fields(self) -> Dict:
        """The trace/span stamp for one outgoing data-plane header
        (ISSUE 15): the active query's trace id (minted at lifecycle
        collect start) and the diagnostics current-operator path.  Empty
        when tracing is off or no lifecycle-managed query is active —
        the worker then records counters but no attributed spans."""
        if not self.trace_enabled:
            return {}
        from spark_rapids_tpu.lifecycle.context import current

        ctx = current()
        if ctx is None:
            return {}
        fields = {"trace": getattr(ctx, "trace_id", "") or ctx.query_id}
        from spark_rapids_tpu.diagnostics import context as _DIAG

        span = _DIAG.CURRENT_OP.get() if _DIAG.RECORDER is not None \
            else None
        if span:
            fields["span"] = span
        return fields

    def _ensure_live_owner(self, exch: int, pid: int) -> str:
        """The partition's owner, re-placed first if a concurrent loss
        left it mapped to a dead worker (the dead worker's own
        declare_lost snapshotted its keys BEFORE this one landed there,
        so nobody else will heal it).  The re-placement queues the pid
        for re-drive like any other loss."""
        wid = self.owner_of(exch, pid)
        with self._lock:
            w = self._workers.get(wid)
            dead = w is None or w.state in (LOST, LEFT)
        if dead:
            replaced = self._replace_owner([(exch, pid)])
            wid = replaced.get((exch, pid))
            if wid is None:
                raise WorkerLost(
                    "<none>", f"partition ({exch}, {pid}) owner dead "
                              f"and no placeable survivors")
        return wid

    def put_block(self, exch: int, pid: int, seq: int,
                  blob: bytes, redrive: bool = False) -> str:
        """Ship one block to the partition's current owner; returns the
        owner id (raises WorkerLost when the owner died and retries
        were exhausted — the caller re-drives after re-placement).
        ``redrive=True`` marks a lineage replay so the worker's
        `store_redrive_puts` counter (and its `redrive_put` span kind)
        makes recovery traffic countable on the worker side."""
        wid = self._ensure_live_owner(exch, pid)
        header = {"op": "put", "exch": self._wire(exch),
                  "pid": pid, "seq": seq, **self._trace_fields()}
        if redrive:
            header["redrive"] = 1
        self._request(wid, header, [blob])
        with self._lock:
            # distinct-block count, not send count: replays re-send
            # sequences the worker's idempotent store deduplicates, and
            # inflated holdings would skew re-placement load weighting
            self._holdings[(exch, pid)] = max(
                self._holdings.get((exch, pid), 0), seq + 1)
            self._shipped_blocks += 1
        PC.bump("dist_blocks_shipped")
        PC.bump("dist_block_bytes", len(blob))
        return wid

    def fetch_blocks(self, exch: int, pid: int, after_seq: int = -1,
                     max_bytes: int = 0
                     ) -> Tuple[List[int], List[bytes], int]:
        """One PAGE of a partition from its owner (sequences above
        ``after_seq``, ~``max_bytes`` per page) — a reduce partition
        far larger than one wire frame streams out page by page
        instead of materializing whole on the worker.  Returns (seqs,
        blobs, the worker's total block count for the partition)."""
        wid = self._ensure_live_owner(exch, pid)
        rep, blobs = self._request(
            wid, {"op": "fetch", "exch": self._wire(exch), "pid": pid,
                  "after_seq": after_seq, "max_bytes": max_bytes,
                  **self._trace_fields()})
        return ([int(s) for s in rep.get("seqs", [])], blobs,
                int(rep.get("n_total", len(blobs))))

    def worker_stats(self, wid: str) -> Dict:
        rep, _ = self._request(wid, {"op": "stats"})
        return rep

    # -- federated telemetry (ISSUE 15) ---------------------------------
    def dump_worker(self, wid: str) -> Optional[Dict]:
        """Pull one LIVE worker's full telemetry via the DUMP control
        op and fold it into the mirror.  Runs on a FRESH connection
        with no loss-declaration side effects (observability must never
        kill membership — a slow dump is just a None).  Returns the
        folded view (counters + full mirror ring + clock offset) or
        None when the worker is gone/slow."""
        with self._lock:
            w = self._workers.get(wid)
            if w is None or w.state in (LOST, LEFT):
                return None
            host, port = w.host, w.data_port
        try:
            s = P.connect(host, port, self.op_timeout_s)
            try:
                rep, _ = P.request(s, {"op": "dump",
                                       **self._trace_fields()})
            finally:
                s.close()
        except (OSError, ConnectionError, RuntimeError,
                P.ProtocolCorruption):
            return None
        with self._lock:
            w = self._workers.get(wid)
            if w is None:
                return None
            self._fold_telemetry_locked(w, rep)
            view = self._worker_view_locked(w)
        PC.bump("dist_worker_dumps")
        return view

    def _worker_view_locked(self, w: WorkerInfo,
                            trace_id: Optional[str] = None) -> Dict:
        ring = [e for e in w.mirror
                if not trace_id or e.get("trace") == trace_id]
        return {"worker_id": w.worker_id, "state": w.state,
                "pid": w.pid, "clock_offset_s": w.clock_offset_s,
                "counters": dict(w.counters),
                "store_stats": dict(w.store_stats),
                "ring": ring}

    def collect_trace(self, trace_id: Optional[str] = None,
                      pull_live: bool = False) -> List[Dict]:
        """Every worker's federated telemetry view, ring filtered to
        ``trace_id`` when given.  ``pull_live`` first DUMPs each ALIVE
        worker so the view includes spans newer than the last heartbeat
        (the query-end merge uses this; LOST workers contribute their
        last-shipped mirror — the whole point of the piggyback)."""
        if pull_live:
            with self._lock:
                live = [w.worker_id for w in self._workers.values()
                        if w.state in (ALIVE, DEGRADED)]
            for wid in live:
                self.dump_worker(wid)
        out = []
        with self._lock:
            for w in self._workers.values():
                view = self._worker_view_locked(w, trace_id)
                if view["ring"] or view["counters"]:
                    out.append(view)
        return out

    def worker_telemetry(self) -> Dict[str, Dict]:
        """Per-worker federated counter snapshots for the sampler fold
        (peek-only: latest heartbeat-reported values, no network)."""
        with self._lock:
            return {w.worker_id: {"state": w.state,
                                  "counters": dict(w.counters),
                                  "store_stats": dict(w.store_stats),
                                  "clock_offset_s": w.clock_offset_s,
                                  "lat_ewma_ms": (w.lat_ewma_s or 0.0)
                                  * 1000.0}
                    for w in self._workers.values() if w.counters}

    def federated_store_bytes(self) -> Dict[str, int]:
        """Last-heartbeat store bytes per worker (peek-only) — the
        resource bill's worker-side bytes when a query's window caught
        no worker_telemetry events (ISSUE 18)."""
        with self._lock:
            return {w.worker_id: int(w.store_stats.get("bytes", 0))
                    for w in self._workers.values() if w.store_stats}

    def postmortem_worker(self, wid: str, detail: str = "") -> Optional[Dict]:
        """On-demand merged post-mortem (the DUMP-op twin of the
        worker-loss bundle): pull the worker's ring + counters and dump
        a flight-recorder bundle naming it.  Returns the bundle or None
        (telemetry off / worker gone with an empty mirror)."""
        from spark_rapids_tpu.telemetry import context as TEL

        hub = TEL.HUB
        if hub is None:
            return None
        view = self.dump_worker(wid)
        if view is None:
            with self._lock:
                w = self._workers.get(wid)
                view = self._worker_view_locked(w) if w is not None \
                    else None
        if view is None:
            return None
        try:
            return hub.postmortem(
                "worker_dump", detail=detail or wid, force=True,
                extra={"worker_id": wid, "worker_diagnostics": view,
                       "trace_ids": sorted(
                           {e.get("trace") for e in view["ring"]
                            if e.get("trace")})})
        # tpulint: disable=cancel-swallow (telemetry isolation: a dump
        # failure must never break the caller)
        except Exception:
            return None

    def note_worker_ok(self, wid: str) -> None:
        """A probed (previously quarantined) worker served successfully:
        close its breaker entry so future joins are clean."""
        from spark_rapids_tpu.resilience.breaker import get_breaker

        get_breaker().record_success((BREAKER_OP, wid))

    # -- release / leak accounting --------------------------------------
    def release_exchange(self, exch: int) -> None:
        """Drop one exchange everywhere: placement, holdings, pending
        re-drives, and a best-effort release broadcast to every worker
        that held any of its partitions (the query committed or died —
        remote copies must not outlive it)."""
        with self._lock:
            owners = {w for (e, _), w in self._placement.items()
                      if e == exch}
            # speculation moved partitions off still-running DEGRADED
            # workers — their store copies need the release broadcast
            # too (a LOST former owner just fails the request quietly)
            owners |= self._former_owners.pop(exch, set())
            for k in [k for k in self._placement if k[0] == exch]:
                del self._placement[k]
                self._holdings.pop(k, None)
            self._redrives.pop(exch, None)
            wire = self._wire_of.pop(exch, exch)
        for wid in sorted(owners):
            try:
                self._request(wid, {"op": "release", "exch": wire,
                                    **self._trace_fields()},
                              cancellable=False)
            except (WorkerLost, RuntimeError, OSError):
                # a dead/slow worker cannot hold up query cleanup; its
                # store dies with its process
                pass

    def release_all(self) -> None:
        with self._lock:
            exchanges = {e for (e, _) in self._placement}
        for e in sorted(exchanges):
            self.release_exchange(e)

    def leak_report(self) -> List[str]:
        """One line per exchange still placed remotely — wired into
        ``lifecycle.leak_report_all`` so the conftest gate fails the
        owning test on a leftover remote partition."""
        with self._lock:
            by_exch: Dict[int, int] = {}
            for (e, _p), w in self._placement.items():
                by_exch[e] = by_exch.get(e, 0) + 1
            return [
                f"LEAK: distributed exchange {e} still placed "
                f"({n} partitions on remote workers)"
                for e, n in sorted(by_exch.items())]

    # -- observability ---------------------------------------------------
    def _diag_event(self, kind: str, wid: str, detail: str) -> None:
        from spark_rapids_tpu.diagnostics import context as _DIAG

        rec = _DIAG.RECORDER
        if rec is not None:
            with self._lock:
                n_workers = sum(1 for w in self._workers.values()
                                if w.state == ALIVE)
                n_parts = len(self._placement)
            rec.distributed(kind, wid, detail, n_workers, n_parts)

    def _flight_event(self, kind: str, **fields) -> None:
        from spark_rapids_tpu.telemetry import context as TEL

        hub = TEL.HUB
        if hub is not None:
            try:
                hub.record_event(kind, **fields)
            # tpulint: disable=cancel-swallow (telemetry isolation: a
            # hub failure must never break membership handling)
            except Exception:
                pass

    def _postmortem(self, wid: str, reason: str, plan: List[Dict],
                    kind: str = "worker_lost") -> None:
        """The worker-loss flight-recorder bundle: the driver's view
        (placement table + re-drive plan + membership) MERGED with the
        lost worker's last-shipped diagnostics ring + counter snapshot
        (ISSUE 15) — a SIGKILLed process cannot answer a DUMP, so what
        its heartbeats already piggybacked is the post-mortem.  ISSUE
        20 reuses the bundle with ``kind="worker_degraded"``: same
        evidence shape, the worker merely stays a member."""
        from spark_rapids_tpu.telemetry import context as TEL

        hub = TEL.HUB
        if hub is None:
            return
        with self._lock:
            placement = [
                {"exch": e, "pid": p, "worker": w,
                 "blocks": self._holdings.get((e, p), 0)}
                for (e, p), w in sorted(self._placement.items())]
            members = [{"worker_id": w.worker_id, "state": w.state,
                        "host": w.host, "data_port": w.data_port,
                        "pid": w.pid}
                       for w in self._workers.values()]
            lost = self._workers.get(wid)
            diagnostics = self._worker_view_locked(lost) \
                if lost is not None else None
        trace_ids = sorted({e.get("trace")
                            for e in (diagnostics or {}).get("ring", [])
                            if e.get("trace")})
        try:
            hub.postmortem(
                kind, detail=f"{wid}: {reason}", force=True,
                extra={"worker_id": wid,
                       "placement_table": placement,
                       "redrive_plan": plan,
                       "membership": members,
                       "worker_diagnostics": diagnostics,
                       "trace_ids": trace_ids})
        # tpulint: disable=cancel-swallow (telemetry isolation: a dump
        # failure must never break loss recovery)
        except Exception:
            pass

    def gauges(self) -> Dict[str, float]:
        """Sampler hook (peek-only): live worker count, re-placement
        backlog, and the put-receipt drift (ISSUE 15)."""
        with self._lock:
            live = sum(1 for w in self._workers.values()
                       if w.state == ALIVE)
            quarantined = sum(1 for w in self._workers.values()
                              if w.state == QUARANTINED)
            degraded = sum(1 for w in self._workers.values()
                           if w.state == DEGRADED)
            backlog = sum(len(v) for v in self._redrives.values())
            acked = self._acked_retired + sum(
                int(w.counters.get("store_puts", 0))
                + int(w.counters.get("store_put_dedups", 0))
                for w in self._workers.values())
            unacked = max(self._shipped_blocks - acked, 0)
            lat = [w.lat_ewma_s for w in self._workers.values()
                   if w.state in (ALIVE, DEGRADED)
                   and w.lat_ewma_s is not None]
        return {"dist_workers_live": float(live),
                "dist_workers_quarantined": float(quarantined),
                # gray failure (ISSUE 20): current straggler count and
                # the fleet's worst per-worker p95 latency EWMA — the
                # tail the governor's fleet pressure component watches
                "dist_workers_degraded": float(degraded),
                "dist_fleet_lat_p95_ms": (max(lat) * 1000.0
                                          if lat else 0.0),
                "dist_replacement_backlog": float(backlog),
                # shipped-but-never-reported blocks: transiently nonzero
                # within one heartbeat of shipping; persistently nonzero
                # means silent frame loss (or a dead worker's unreported
                # tail — cross-check worker_lost)
                "dist_blocks_unacked": float(unacked)}

    def describe(self) -> str:
        with self._lock:
            states = {w.worker_id: w.state
                      for w in self._workers.values()}
        return json.dumps({"port": self.port, "workers": states})

    def shutdown(self) -> None:
        self._stop.set()
        # hard_close, not close(): a bare close leaves the accept loop
        # (and every control-conn reader) blocked on its socket forever
        P.hard_close(self._listener)
        with self._lock:
            socks = list(self._conns.values()) + [
                w.control for w in self._workers.values()
                if w.control is not None]
            self._conns.clear()
            # membership ends with the coordinator: mark everyone LEFT
            # so in-flight reader/monitor threads waking on the closed
            # sockets below cannot declare stray losses (bumping
            # counters and dumping bundles into whatever runs next)
            for w in self._workers.values():
                if w.state in (ALIVE, QUARANTINED, DEGRADED):
                    w.state = LEFT
        for s in socks:
            P.hard_close(s)
        me = threading.current_thread()
        for t in self._threads:
            if t is not me:
                t.join(self.heartbeat_s * 2 + 1.0)
