"""Spill-backed exchange partition queues (ISSUE 10).

Reference analog: RapidsShuffleInternalManagerBase's block store plus
SpillableColumnarBatch (SURVEY.md §2.3/§2.7) — but organized the way the
out-of-core exchange consumes them: one queue per reduce partition,
appended map-side slice by slice, drained partition by partition.

Residency discipline: slices up to a conf'd device budget stay resident
as :class:`SpillFramework` handles (the pool's LRU sheds them down-tier
under pressure, so device residency is bounded by the HBM pool no matter
how large the exchange input is); slices beyond the budget cross the
host boundary immediately as CRC-framed serializer blocks
(``shuffle/serializer.py`` — a flipped bit anywhere surfaces as a
deterministic :class:`ShuffleCorruption` instead of silent wrong rows).
Every append/read observes the current query's CancelToken, so a tripped
deadline unwinds a wide exchange instead of finishing it.

Wall inside the queue (serialize / track / materialize) lands in the
``exchange_spill_ns`` counter; host-boundary blocks count into
``exchange_host_blocks`` / ``exchange_host_block_bytes`` — bench.py
decomposes exchange walls from these.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.accounting import context as _ACCT
from spark_rapids_tpu.columnar.batch import ColumnarBatch


class SpillBackedPartitionQueues:
    """Per-partition queues of exchange output slices with bounded
    device residency (the spill-backed exchange's block store)."""

    def __init__(self, n_parts: int, schema: T.StructType,
                 device_budget: int, codec: Optional[str] = None,
                 host_budget: int = 0,
                 spill_dir: Optional[str] = None):
        from spark_rapids_tpu.memory.spill import get_spill_framework

        self.n_parts = n_parts
        self.schema = schema
        self.device_budget = max(int(device_budget), 0)
        # host-memory budget for retained CRC blobs (0 = unbounded):
        # past it blobs land as files in the spill dir — the distributed
        # lineage buffer (ISSUE 14) retains a whole exchange until its
        # partitions commit, which must not pin the driver's RAM
        self.host_budget = max(int(host_budget), 0)
        self._spill_dir = spill_dir
        self._made_spill_dir = False
        self.codec = codec
        self._fw = get_spill_framework()
        # per-partition entries:
        #   ("dev", handle) | ("host", crc_blob) | ("hostfile", path)
        self._queues: Dict[int, List[Tuple[str, object]]] = {
            p: [] for p in range(n_parts)}
        self._device_bytes = 0
        self._host_mem_bytes = 0
        self._next_file = 0
        self.host_blocks = 0
        self.host_block_bytes = 0

    @property
    def device_bytes(self) -> int:
        """Device bytes currently queued as resident handles (the
        queue's own budget accounting; the SpillFramework pool bound is
        the second, global, limit)."""
        return self._device_bytes

    def append(self, pid: int, batch: ColumnarBatch) -> None:
        """Queue one map-side slice for reduce partition ``pid``."""
        from spark_rapids_tpu.lifecycle.context import check_cancel

        check_cancel()
        if batch is None or batch.num_rows == 0:
            return
        with PC.span("srt.exchange.queue", feeds="exchange_spill_ns"):
            self._append(pid, batch)

    def _append(self, pid: int, batch: ColumnarBatch) -> None:
        nb = batch.nbytes()
        if self._device_bytes + nb <= self.device_budget:
            if _ACCT.LEDGERS is not None:
                # stamp the reduce partition driving this admission so
                # LRU spills it triggers bill against pid (ISSUE 18)
                tok = _ACCT.PARTITION.set(pid)
                try:
                    handle = self._fw.track(batch)
                finally:
                    _ACCT.PARTITION.reset(tok)
            else:
                handle = self._fw.track(batch)
            self._queues[pid].append(("dev", handle))
            self._device_bytes += nb
        else:
            # host boundary: CRC-framed serializer block (ShuffleCorruption
            # on bit rot — never silent wrong rows); ONE framing site for
            # the ICI/exchange host boundary (exec/ici.ici_host_frame)
            from spark_rapids_tpu.exec.ici import ici_host_frame

            blob = ici_host_frame(batch, codec=self.codec)
            self._queues[pid].append(self._host_entry(blob))
            self.host_blocks += 1
            self.host_block_bytes += len(blob)
            PC.bump("exchange_host_blocks")
            PC.bump("exchange_host_block_bytes", len(blob))

    def _host_entry(self, blob: bytes) -> Tuple[str, object]:
        """One host-tier entry: in memory up to ``host_budget``, past
        it a file in the spill dir (blobs are already CRC-framed, so
        disk rot surfaces at decode time as ShuffleCorruption)."""
        if not self.host_budget \
                or self._host_mem_bytes + len(blob) <= self.host_budget:
            self._host_mem_bytes += len(blob)
            return ("host", blob)
        import os
        import tempfile

        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="srt_exch_lineage_")
            self._made_spill_dir = True
        os.makedirs(self._spill_dir, exist_ok=True)
        path = os.path.join(self._spill_dir,
                            f"lineage_{id(self):x}_{self._next_file}.blk")
        self._next_file += 1
        with open(path, "wb") as f:
            f.write(blob)
        return ("hostfile", path)

    def _release_entry(self, kind: str, x) -> None:
        """Drop one entry's backing resource (host accounting / spill
        file / device handle)."""
        if kind == "host":
            self._host_mem_bytes -= len(x)
        elif kind == "hostfile":
            import os

            try:
                os.unlink(x)
            except OSError:
                pass
        elif kind == "dev":
            self._device_bytes -= x.device_bytes
            x.close()

    def append_framed(self, pid: int, blob: bytes) -> None:
        """Queue one PRE-FRAMED host-boundary block (the distributed
        tier frames each slice once — ``exec/ici.ici_host_frame`` — and
        retains the same bytes here as its lineage copy).  Counted like
        any other host-boundary block."""
        from spark_rapids_tpu.lifecycle.context import check_cancel

        check_cancel()
        if not blob:
            return
        self._queues[pid].append(self._host_entry(blob))
        self.host_blocks += 1
        self.host_block_bytes += len(blob)
        PC.bump("exchange_host_blocks")
        PC.bump("exchange_host_block_bytes", len(blob))

    def peek_blobs(self, pid: int) -> List[bytes]:
        """The partition's retained host-boundary blocks WITHOUT
        draining — the re-drive source after a worker loss (ISSUE 14;
        spilled blobs read back from disk).  Only meaningful for queues
        run at device budget 0 (every entry framed): device-resident
        entries are not wire blocks and are skipped."""
        out: List[bytes] = []
        for kind, x in (self._queues.get(pid) or []):
            if kind == "host":
                out.append(x)
            elif kind == "hostfile":
                with open(x, "rb") as f:
                    out.append(f.read())
        return out

    def snapshot_framed(self, pid: int) -> List[bytes]:
        """EVERY queued entry of one partition as CRC-framed
        host-boundary blocks WITHOUT draining — the stage-checkpoint
        source (ISSUE 16).  Unlike :meth:`peek_blobs` this covers
        device-resident entries too: each handle pins, serializes
        through the one framing site, and unpins with the entry still
        queued (the checkpoint is a copy; the read phase drains the
        queue as usual afterwards)."""
        from spark_rapids_tpu.exec.ici import ici_host_frame

        out: List[bytes] = []
        for kind, x in (self._queues.get(pid) or []):
            if kind == "host":
                out.append(x)
            elif kind == "hostfile":
                with open(x, "rb") as f:
                    out.append(f.read())
            else:
                x.pin()
                try:
                    out.append(ici_host_frame(x.get_batch(),
                                              codec=self.codec))
                finally:
                    x.unpin()
        return out

    def release_partition(self, pid: int) -> None:
        """Commit one partition: the consuming stage fully read it, so
        the lineage copy (resident handles, retained blobs, spill
        files) can go."""
        entries = self._queues.get(pid) or []
        self._queues[pid] = []
        for kind, x in entries:
            self._release_entry(kind, x)

    def read(self, pid: int) -> Optional[ColumnarBatch]:
        """Drain reduce partition ``pid`` into one device batch (the
        chunked ``read_chunks`` is the exchange's streaming path; this
        concat form serves callers that want the whole partition)."""
        chunks = list(self.read_chunks(pid))
        if not chunks:
            return None
        return (chunks[0] if len(chunks) == 1
                else ColumnarBatch.concat(chunks))

    def read_chunks(self, pid: int, target_bytes: int = 0):
        """Drain reduce partition ``pid`` as a stream of device batches,
        each ~``target_bytes`` (0: one chunk per queued entry group of
        unbounded size — callers pass the session batch-size goal).  The
        out-of-core invariant lives here: one CHUNK at a time pins /
        materializes / releases, so the drain's device working set is
        one chunk — never the whole partition (a partition far larger
        than the pool would otherwise re-materialize whole and bust the
        residency bound as a single unspillable batch)."""
        from spark_rapids_tpu.lifecycle.context import check_cancel
        from spark_rapids_tpu.shuffle.serializer import deserialize_concat

        check_cancel()
        entries = self._queues.get(pid) or []
        if not entries:
            return
        self._queues[pid] = []
        group: List[Tuple[str, object]] = []
        group_bytes = 0

        def _entry_bytes(kind, x):
            if kind == "dev":
                return x.device_bytes
            if kind == "hostfile":
                import os

                try:
                    return os.path.getsize(x)
                except OSError:
                    return 0
            return len(x)

        def _drain_group():
            with PC.span("srt.exchange.queue", feeds="exchange_spill_ns"):
                return _drain_group_impl()

        def _drain_group_impl():
            # stamp the DRAINING partition: restores its materialization
            # pulls up-tier — and spills that restoring displaces — bill
            # against pid, localizing out-of-core pressure (ISSUE 18)
            _tok = _ACCT.PARTITION.set(pid) \
                if _ACCT.LEDGERS is not None else None
            handles = [h for kind, h in group if kind == "dev"]
            try:
                for h in handles:
                    h.pin()
                parts: List[ColumnarBatch] = []
                host_blobs = []
                for kind, x in group:
                    if kind == "dev":
                        parts.append(x.get_batch())
                    elif kind == "hostfile":
                        with open(x, "rb") as f:
                            host_blobs.append(f.read())
                    else:
                        host_blobs.append(x)
                if host_blobs:
                    # CRC-verified host-boundary decode
                    # (ShuffleCorruption on mismatch), concat-friendly
                    # across the group's blobs at once
                    parts.append(deserialize_concat(
                        host_blobs, self.schema, codec=self.codec))
                out = (parts[0] if len(parts) == 1
                       else ColumnarBatch.concat(parts))
            finally:
                for h in handles:
                    h.unpin()
                if _tok is not None:
                    _ACCT.PARTITION.reset(_tok)
            for kind, x in group:
                self._release_entry(kind, x)
            return out

        for kind, x in entries:
            nb = _entry_bytes(kind, x)
            if group and target_bytes and group_bytes + nb > target_bytes:
                yield _drain_group()
                check_cancel()
                group, group_bytes = [], 0
            group.append((kind, x))
            group_bytes += nb
        if group:
            yield _drain_group()

    def close(self) -> None:
        """Release every remaining entry (the error-unwind path; a clean
        drain already released everything in read())."""
        from spark_rapids_tpu.lifecycle import QueryCancelled

        for pid, entries in self._queues.items():
            for kind, x in entries:
                try:
                    self._release_entry(kind, x)
                except QueryCancelled:
                    raise
                except Exception:
                    pass
            self._queues[pid] = []
        self._device_bytes = 0
        self._host_mem_bytes = 0
        if self._made_spill_dir and self._spill_dir:
            import shutil

            shutil.rmtree(self._spill_dir, ignore_errors=True)
            self._spill_dir = None
            self._made_spill_dir = False


def queue_device_budget(conf) -> int:
    """Resolve the queues' device budget: the conf when set, else a
    pool-derived default (2x one target partition's working set, so the
    next partition's slices can stage while the current one computes)."""
    from spark_rapids_tpu.config import (
        EXCHANGE_DEVICE_RESIDENT_BYTES,
        EXCHANGE_TARGET_PARTITION_FRACTION,
    )
    from spark_rapids_tpu.memory.device_manager import get_device_manager

    fixed = conf.get(EXCHANGE_DEVICE_RESIDENT_BYTES)
    if fixed:
        return int(fixed)
    pool = get_device_manager().pool_bytes
    frac = conf.get(EXCHANGE_TARGET_PARTITION_FRACTION)
    return max(int(pool * frac * 2), 1 << 20)


def host_boundary_codec(conf) -> Optional[str]:
    """Codec for the CRC-framed host-boundary blocks: the ici override
    when set, else the shuffle codec."""
    from spark_rapids_tpu.config import (
        ICI_HOST_BOUNDARY_CODEC,
        SHUFFLE_COMPRESSION_CODEC,
    )

    return conf.get(ICI_HOST_BOUNDARY_CODEC) \
        or conf.get(SHUFFLE_COMPRESSION_CODEC)
