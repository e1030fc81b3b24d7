"""DistributedExchange — the producer/consumer driver of one exchange
over the worker tier, with lineage retry.

Contract (the fault-tolerance core of the cross-host tier):

  * every partition slice is CRC-framed ONCE (``exec/ici.ici_host_frame``,
    the PR 4 ``TKU2`` block) and lands in TWO places: the placed worker
    (``Coordinator.put_block``) and the producer-side spill-backed
    partition queue (``shuffle/partition_queues.py``) — the durable
    lineage copy;
  * the producer RETAINS its copy until the consuming stage COMMITS the
    partition (one ``release_partition`` per fully-drained pid), so a
    worker lost at any point before commit is recoverable;
  * a loss (heartbeat silence or dead socket) re-places the dead
    worker's partitions on survivors; this client claims the re-drive
    queue at every produce/consume step and re-pushes the retained
    blocks to the new owners — ``partitions_replayed`` counts each
    re-driven partition;
  * the consumer verifies completeness by SEQUENCE SET (a worker that
    restarted empty under the same id returns fewer blocks than the
    producer shipped) and re-drives instead of returning short data;
    corrupted blocks surface as deterministic ``ShuffleCorruption`` at
    deserialize time — never silent wrong rows.

``redriveMaxAttempts`` bounds how many times one partition may be
re-driven (repeated losses), after which :class:`WorkerLost` escapes to
the operator fault domain — classified WORKER_LOST, which falls back to
the CPU oracle without indicting the operator's breaker key.

Hedged fetches (ISSUE 20, docs/distributed.md): because the producer
retains every framed slice until commit, the lineage queue IS a free
replica of every un-committed partition.  A paged fetch that blows the
owner's soft deadline (``Coordinator.soft_deadline_s`` — softDeadline
Factor x the worker's p95 latency EWMA) therefore hedges against
``queues.peek_blobs`` instead of waiting out the straggler:
first-complete-wins, the remote's eventual reply is discarded, and any
duplicate a re-drive later ships is dropped by the worker store's
per-seq idempotence.  ``fetch_hedges`` counts launches, ``hedges_won``
counts lineage wins; on a healthy fleet both stay 0 (pinned by the
bench rung4_dist A/B at <= 2% overhead).
"""
from __future__ import annotations

import contextvars
import threading
from typing import Dict, Iterator, List, Optional

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu.distributed.protocol import WorkerLost

# test hook (chaos/kill-timing): called as (exch, pid, seq) after every
# successfully shipped block; assigned only by tests/harnesses
TEST_SHIP_HOOK = None

# fetch page size: one reduce partition streams back in ~this many
# bytes per wire frame, so a partition far larger than the frame cap
# (or the worker's memory) never materializes whole on the worker
FETCH_PAGE_BYTES = 8 << 20


class DistributedExchange:
    """One exchange's view of the worker tier (driver side)."""

    def __init__(self, coordinator, exch_id: int, n_parts: int,
                 schema, codec: Optional[str], queues,
                 est_bytes: Optional[int] = None,
                 redrive_max_attempts: int = 4):
        self.coord = coordinator
        self.exch_id = exch_id
        self.n_parts = n_parts
        self.schema = schema
        self.codec = codec
        self.queues = queues          # SpillBackedPartitionQueues
        self.redrive_max_attempts = max(int(redrive_max_attempts), 1)
        self._counts: Dict[int, int] = {}
        self._redriven: Dict[int, int] = {}
        self._lock = threading.Lock()
        self.placement = coordinator.place(exch_id, n_parts, est_bytes)

    def block_counts(self) -> Dict[int, int]:
        """Per-partition shipped-block counts (sequences are contiguous
        from 0, so count == the completeness bar a consumer — or a
        recovery lease, ISSUE 16 — checks against)."""
        with self._lock:
            return dict(self._counts)

    # -- produce ---------------------------------------------------------
    def add_slice(self, pid: int, batch) -> None:
        """Frame one partition slice, retain it in the lineage queue,
        and ship it to the placed worker."""
        if batch is None or batch.num_rows == 0:
            return
        from spark_rapids_tpu.exec.ici import ici_host_frame

        blob = ici_host_frame(batch, codec=self.codec)
        with self._lock:
            seq = self._counts.get(pid, 0)
            self._counts[pid] = seq + 1
        self.queues.append_framed(pid, blob)
        self._drain_redrives()
        self._ship(pid, seq, blob)

    def _ship(self, pid: int, seq: int, blob: bytes) -> None:
        while True:
            try:
                self.coord.put_block(self.exch_id, pid, seq, blob)
                if TEST_SHIP_HOOK is not None:
                    TEST_SHIP_HOOK(self.exch_id, pid, seq)
                return
            except WorkerLost:
                # the owner died mid-put: the coordinator already
                # declared the loss and re-placed its partitions; claim
                # the re-drive queue (which re-pushes every retained
                # block of the affected pids, including this one's
                # earlier seqs) and re-send this block to the new owner
                self._bump_redrive_budget(pid)
                self._drain_redrives(include=pid)

    def _bump_redrive_budget(self, pid: int) -> None:
        with self._lock:
            used = self._redriven.get(pid, 0) + 1
            self._redriven[pid] = used
        if used > self.redrive_max_attempts:
            raise WorkerLost(
                str(self.placement.get(pid, "?")),
                f"partition {pid} exceeded {self.redrive_max_attempts} "
                f"re-drive attempts")

    def _drain_redrives(self, include: Optional[int] = None) -> None:
        """Claim and replay every partition a loss re-placed.  Replays
        the FULL retained block list of each claimed pid to its new
        owner (worker stores are idempotent per seq, so overlap with
        already-landed blocks is harmless).  A REPLACEMENT owner dying
        mid-replay folds its re-placed pids back into this pass and
        restarts the current pid from sequence 0 — blocks already
        pushed in the aborted attempt went to the dead owner."""
        pending = self.coord.claim_redrives(self.exch_id)
        if include is not None:
            pending.add(include)
        while pending:
            pid = min(pending)
            pending.discard(pid)
            blobs = self.queues.peek_blobs(pid)
            if not blobs:
                # nothing retained: never produced, or the consuming
                # stage already committed this partition — either way
                # there is nothing left to protect
                continue
            seq = 0
            while seq < len(blobs):
                try:
                    # redrive-flagged: the worker counts the replay
                    # (store_redrive_puts) and records a `redrive_put`
                    # span, so recovery traffic is visible cluster-wide
                    self.coord.put_block(self.exch_id, pid, seq,
                                         blobs[seq], redrive=True)
                    seq += 1
                except WorkerLost:
                    # the replacement died too: budget-check, fold ITS
                    # re-placed pids into this pass, and restart this
                    # pid's replay against the next owner
                    self._bump_redrive_budget(pid)
                    pending |= self.coord.claim_redrives(self.exch_id)
                    pending.discard(pid)
                    seq = 0
            # counted only once the partition's blocks all LANDED on the
            # new owner — a replay that died against every survivor must
            # not satisfy "recovered" pins via the CPU-oracle fallback
            PC.bump("partitions_replayed")
            self._diag_redrive(pid, len(blobs))

    def _diag_redrive(self, pid: int, n_blocks: int) -> None:
        from spark_rapids_tpu.diagnostics import context as _DIAG

        rec = _DIAG.RECORDER
        if rec is not None:
            rec.distributed(
                "partition_replayed",
                str(self.placement.get(pid, "?")),
                f"pid={pid} blocks={n_blocks}", 0, 0)

    # -- consume ---------------------------------------------------------
    def read_partition_chunks(self, pid: int,
                              target_bytes: int = 0) -> Iterator:
        """Drain one reduce partition from its owning worker as device
        batches of ~``target_bytes``, STREAMING page by page — the
        driver's working set is one decode group, never the whole
        partition (the same residency discipline the lineage buffer
        keeps on the produce side).  Commits (releases the lineage
        copy) only after the full partition deserialized."""
        from spark_rapids_tpu.lifecycle.context import check_cancel
        from spark_rapids_tpu.shuffle.serializer import deserialize_concat

        expected = self._counts.get(pid, 0)
        if expected == 0:
            self.queues.release_partition(pid)
            return
        self._ensure_remote_complete(pid, expected)
        # the owner holds exactly sequences 0..expected-1 (producer
        # seqs are contiguous and the store dedups), so pages stream
        # out in ascending order with no gaps possible.  A WorkerLost
        # AFTER the first yield propagates — rows already delivered
        # downstream cannot be retracted, so the fault domain's
        # whole-query fallback takes over (mid-stream loss before any
        # yield re-enters the completeness loop via the caller retry).
        group: List[bytes] = []
        group_bytes = 0
        next_seq = 0
        while next_seq < expected:
            check_cancel()
            seqs, blobs, _n = self._fetch_page(pid, next_seq)
            if not seqs:
                raise WorkerLost(
                    str(self.placement.get(pid, "?")),
                    f"partition {pid} truncated mid-stream "
                    f"(at seq {next_seq}/{expected})")
            for s, blob in zip(seqs, blobs):
                next_seq = s + 1
                if group and target_bytes \
                        and group_bytes + len(blob) > target_bytes:
                    yield deserialize_concat(group, self.schema,
                                             codec=self.codec)
                    check_cancel()
                    group, group_bytes = [], 0
                group.append(blob)
                group_bytes += len(blob)
        if group:
            yield deserialize_concat(group, self.schema,
                                     codec=self.codec)
        # success against this owner: a probed (previously quarantined)
        # worker earns its breaker entry back
        self.coord.note_worker_ok(self.coord.owner_of(self.exch_id, pid))
        # the consuming stage committed this partition: lineage copy
        # released (a later loss can no longer need it)
        self.queues.release_partition(pid)

    def _fetch_page(self, pid: int, next_seq: int):
        """One page of the partition (sequences above ``next_seq - 1``)
        from its owning worker, HEDGED (ISSUE 20): the remote fetch
        runs on a side thread racing the owner's soft deadline; blowing
        it launches a hedge against the producer-side lineage buffer —
        which retains every framed slice until commit, so it can serve
        the whole remainder locally.  First-complete-wins: a hedge win
        abandons the straggler's in-flight reply (its wall still feeds
        the worker's latency EWMA when it lands) and counts the miss
        toward the owner's DEGRADED demotion."""
        def remote():
            return self.coord.fetch_blocks(
                self.exch_id, pid, after_seq=next_seq - 1,
                max_bytes=FETCH_PAGE_BYTES)

        deadline = None
        owner = None
        if getattr(self.coord, "hedge_enabled", False):
            try:
                owner = self.coord.owner_of(self.exch_id, pid)
                deadline = self.coord.soft_deadline_s(owner)
            except KeyError:
                pass
        if deadline is None:
            return remote()
        box: Dict[str, object] = {}
        done = threading.Event()
        # the side thread must carry the query's context (trace id,
        # current operator span): a bare thread starts with an empty
        # one and the worker would record the fetch under no trace
        ctx = contextvars.copy_context()

        def run():
            try:
                box["out"] = ctx.run(remote)
            except BaseException as e:
                box["err"] = e
            finally:
                done.set()

        t = threading.Thread(target=run, daemon=True,
                             name="srt-dist-hedge-fetch")
        t.start()
        if not done.wait(deadline):
            PC.bump("fetch_hedges")
            self.coord.note_soft_deadline_miss(owner)
            blobs = self.queues.peek_blobs(pid)
            if len(blobs) > next_seq:
                # the lineage copy holds the remainder (it always does
                # before commit): serve it and discard whatever the
                # straggler eventually answers — byte-identical by
                # construction, these ARE the shipped frames
                PC.bump("hedges_won")
                return (list(range(next_seq, len(blobs))),
                        blobs[next_seq:], len(blobs))
            # lineage already committed/empty (cannot happen before the
            # final release, but never hang on it): take the remote
            done.wait()
        err = box.get("err")
        if err is not None:
            raise err
        return box["out"]

    def _ensure_remote_complete(self, pid: int, expected: int) -> None:
        """Re-drive until the owner's store holds the full partition
        (``n_total == expected`` — producer sequences are contiguous
        and the store dedups, so the count IS the completeness check),
        WITHOUT materializing any data; bounded by
        ``redriveMaxAttempts``."""
        while True:
            self._drain_redrives()
            try:
                # ask for the blocks AFTER the last expected one: a
                # complete partition answers with the count alone (a
                # fetch always ships at least one block it finds, and a
                # probe that hauled a data block would also teach the
                # owner's latency EWMA that bulk replies are normal
                # before the first hedged fetch could miss)
                _seqs, _blobs, n_total = self.coord.fetch_blocks(
                    self.exch_id, pid, after_seq=expected - 1,
                    max_bytes=1)
            except WorkerLost:
                self._bump_redrive_budget(pid)
                self._drain_redrives(include=pid)
                continue
            if n_total >= expected:
                return
            # short read: the worker restarted empty (or missed blocks)
            # under the same id — re-drive the producer's retained copy
            self._bump_redrive_budget(pid)
            self.coord.mark_redrive(self.exch_id, pid)

    # -- cleanup ---------------------------------------------------------
    def close(self) -> None:
        """Error-unwind/commit cleanup: drop the lineage queues and the
        remote copies (idempotent; the shuffle-manager unregister path
        broadcasts the release too)."""
        self.queues.close()
        try:
            self.coord.release_exchange(self.exch_id)
        except WorkerLost:
            pass
