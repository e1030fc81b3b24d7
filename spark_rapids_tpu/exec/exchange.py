"""TpuShuffleExchangeExec — partitioning + shuffle boundary.

Reference analog: GpuShuffleExchangeExecBase + GpuPartitioning
(SURVEY.md §2.4 Exchange, §2.7): slices each batch by partition id and hands
the slices to the shuffle manager.  Partition ids are Spark-exact
(murmur3-based pmod — ops/hashing.py) so a TPU stage can interoperate with
CPU stages, exactly as the reference's GpuHashPartitioning matches Spark's
Murmur3 partitioning.

In-process execution pushes slices through the shuffle manager
(shuffle/manager.py) which serializes batches in the concat-friendly layout
(Kudo analog) or keeps them device-resident; on a mesh the ICI mode turns
this into an XLA all-to-all (parallel/).
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import jax
from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu.perfcounters import tpu_jit
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.expr.base import EvalContext
from spark_rapids_tpu.ops.filterops import compact_columns
from spark_rapids_tpu.ops.hashing import spark_partition_ids
from spark_rapids_tpu.plan.nodes import (
    HashPartitioning,
    RangePartitioning,
    RoundRobinPartitioning,
    SinglePartitioning,
)


# what jax calls an exchange program in a trace, by its registry kind
_PROGRAM_NAMES = {"partsort": "partition"}


class TpuShuffleExchangeExec(TpuExec):
    # GpuShuffleExchangeExec write/fetch metric pair, plus the ISSUE 10
    # decomposition: wall inside the partition-id/slice programs vs wall
    # inside the spill-backed queue (serialize/track/materialize)
    EXTRA_METRICS = {"shuffleWriteTime": "MODERATE",
                     "shuffleReadTime": "MODERATE",
                     "exchangePartitionTime": "MODERATE",
                     "exchangeSpillTime": "MODERATE"}

    def __init__(self, partitioning, child: TpuExec, ansi: bool = False,
                 conf=None):
        super().__init__([child])
        self.partitioning = partitioning
        self.ansi = ansi
        self.conf = conf

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        d = getattr(self, "sized_decision", None)
        return (f"TpuShuffleExchange {self.partitioning.describe()}"
                + (f" [{d}]" if d else ""))

    @property
    def num_partitions(self) -> int:
        return getattr(self.partitioning, "num_partitions", 1)

    def aot_output_rows(self):
        # single partition = identity pipe over the child; hash/rr/range
        # partition splits are data-dependent
        if isinstance(self.partitioning, SinglePartitioning) \
                or self.num_partitions == 1:
            return self.aot_input_rows()
        return None

    def aot_output_caps(self):
        if isinstance(self.partitioning, SinglePartitioning) \
                or self.num_partitions == 1:
            return self.aot_input_caps()
        return None

    def aot_emits_single_batch(self):
        return (isinstance(self.partitioning, SinglePartitioning)
                or self.num_partitions == 1) \
            and self.aot_child_single_batch()

    def _registry_scope(self, kind: str):
        from spark_rapids_tpu.compilecache.keys import (
            conf_fp,
            exprs_fp,
            schema_fp,
        )

        p = self.partitioning
        if isinstance(p, HashPartitioning):
            efp = exprs_fp(p.keys)
        elif isinstance(p, RangePartitioning):
            efp = exprs_fp([e for e, _ in p.orders])
            if efp is not None:
                efp = efp + tuple((s.ascending, s.nulls_first)
                                  for _, s in p.orders)
        else:
            efp = ()
        if efp is None:
            return None
        return ("exchange", kind, type(p).__name__, efp,
                self.num_partitions, schema_fp(self.output),
                bool(self.ansi), conf_fp())

    def _cached_jit(self, attr: str, kind: str, builder):
        jitted = getattr(self, attr, None)
        if jitted is None:
            from spark_rapids_tpu.compilecache.registry import (
                cached_jit_program,
            )

            jitted = cached_jit_program(
                self._registry_scope(kind), builder,
                label=f"exchange:{kind}",
                name="exchange_" + _PROGRAM_NAMES.get(kind, kind))
            setattr(self, attr, jitted)
        return jitted

    def partition_batch(self, batch: ColumnarBatch) -> List[ColumnarBatch]:
        """Every partition slice of one batch as a list (the legacy
        shuffle-manager contract: index == pid, empties included)."""
        return [sl for _, sl in self.partition_slices(batch)]

    def partition_slices(
            self, batch: ColumnarBatch
    ) -> Iterator[Tuple[int, ColumnarBatch]]:
        """Slice one batch into per-partition batches, LAZILY — yielded
        one (pid, slice) at a time in pid order so the consumer can
        serialize/spill each slice before the next materializes instead
        of holding every output slice live at once (ISSUE 10).

        Reference analog: GpuPartitioning.sliceInternalGpuOrCpu."""
        p = self.partitioning
        if isinstance(p, SinglePartitioning) or self.num_partitions == 1:
            yield 0, batch
            return
        # the partition-id and sort programs and the one sync for the
        # bounds; closed before the first slice is yielded
        with PC.span("srt.exchange.partition",
                     feeds="exchange_partition_ns") as part:
            if isinstance(p, HashPartitioning):
                ids = self._hash_ids(batch)
            elif isinstance(p, RoundRobinPartitioning):
                ids = (jnp.arange(batch.capacity, dtype=jnp.int32)
                       % self.num_partitions)
            elif isinstance(p, RangePartitioning):
                ids = self._range_ids(batch)
            else:
                raise NotImplementedError(type(p).__name__)
            # ONE device program: stable-sort rows by partition id; each
            # partition is then a contiguous range (searchsorted bounds
            # since ids are sorted).  One host sync for the boundary
            # vector instead of num_partitions sequential compactions.
            n_parts = self.num_partitions
            schema = batch.schema   # capture only the schema, not the batch

            def sort_fn(cols, ids, num_rows):
                b = ColumnarBatch(list(cols), num_rows, schema)
                cap = b.capacity
                key = jnp.where(b.row_mask, ids.astype(jnp.int32), n_parts)
                perm = jax.lax.sort(
                    (key, jnp.arange(cap, dtype=jnp.int32)),
                    num_keys=1, is_stable=True)[1]
                from spark_rapids_tpu.ops.filterops import gather_columns

                sorted_cols = gather_columns(perm, b.row_mask[perm],
                                             b.columns)
                sorted_key = key[perm]
                bounds = jnp.searchsorted(
                    sorted_key, jnp.arange(n_parts + 1, dtype=jnp.int32),
                    side="left").astype(jnp.int32)
                return tuple(sorted_cols), bounds

            cols, bounds = self._cached_jit(
                "_sort_jit", "partsort", sort_fn)(
                tuple(batch.columns), ids, jnp.int32(batch.num_rows))
            import numpy as _np

            bounds_np = _np.asarray(bounds).tolist()   # one transfer
        self.metric("exchangePartitionTime").add(part.ns)
        sorted_batch = ColumnarBatch(list(cols), batch.num_rows, schema)
        for pid in range(n_parts):
            lo, hi = bounds_np[pid], bounds_np[pid + 1]
            yield pid, (sorted_batch.slice_rows(lo, hi - lo)
                        if hi > lo else
                        ColumnarBatch([c.slice_to(1) for c in cols], 0,
                                      batch.schema))

    def _hash_ids(self, batch: ColumnarBatch):
        schema = batch.schema
        keys, n_parts, ansi = (self.partitioning.keys,
                               self.num_partitions, self.ansi)

        def fn(cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            ctx = EvalContext(b, ansi=ansi)
            key_cols = [k.eval_tpu(ctx) for k in keys]
            return spark_partition_ids(key_cols, n_parts)

        return self._cached_jit("_ids_jit", "hashids", fn)(
            tuple(batch.columns), jnp.int32(batch.num_rows))

    def _range_ids(self, batch: ColumnarBatch):
        """Range partitioning via sampled bounds (GpuRangePartitioner).

        Round-1 simplification: bounds from this batch's sorted sample."""
        from spark_rapids_tpu.ops.sortkeys import sort_permutation

        orders = self.partitioning.orders

        schema = batch.schema
        n_parts, ansi = self.num_partitions, self.ansi

        def fn(cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            ctx = EvalContext(b, ansi=ansi)
            key_cols = [e.eval_tpu(ctx) for e, _ in orders]
            specs = [s for _, s in orders]
            perm = sort_permutation(key_cols, specs, b.row_mask)
            # rank of each row / rows-per-partition
            cap = b.capacity
            inv = jnp.zeros(cap, jnp.int32).at[perm].set(
                jnp.arange(cap, dtype=jnp.int32))
            per = jnp.maximum(
                (num_rows + n_parts - 1) // n_parts, 1)
            return jnp.clip(inv // per, 0, n_parts - 1)

        return self._cached_jit("_range_jit", "rangeids", fn)(
            tuple(batch.columns), jnp.int32(batch.num_rows))

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        """Shuffle the input, partition boundaries preserved in output
        order so downstream per-partition operators see real reduce
        partitions.

        Default path (ISSUE 10): partition slices stream through
        spill-backed partition queues — device residency bounded by the
        queue budget + the SpillFramework pool, host-boundary blocks
        CRC-framed — so an exchange input far larger than HBM completes
        instead of materializing whole.  Legacy path
        (exchange.spill.enabled=false or CACHE_ONLY mode): the shuffle
        manager, each input batch a "map task" whose slices are written
        (serialized in MULTITHREADED mode — the Kudo wire-format path)
        and each reduce partition assembled by the concat-friendly
        reader."""
        from spark_rapids_tpu.config import (
            DISTRIBUTED_ENABLED,
            EXCHANGE_SPILL_ENABLED,
            SHUFFLE_MODE,
            get_conf,
        )
        from spark_rapids_tpu.plan.nodes import SinglePartitioning
        from spark_rapids_tpu.shuffle.manager import get_shuffle_manager

        if isinstance(self.partitioning, SinglePartitioning):
            # device-resident pipe: a single reduce partition receives every
            # map output in order, so the exchange is an identity over the
            # child's batches — no serialize/deserialize round trip (the
            # degenerate case of ICI shuffle mode 2's device-resident design)
            for b in self.children[0].execute_columnar():
                yield self._count_output(b)
            return
        c = self.conf if self.conf is not None else get_conf()
        # Crash-consistent recovery (ISSUE 16, docs/recovery.md): with
        # recovery on, this stage boundary is a durable checkpoint —
        # serve a prior incarnation's committed output instead of
        # re-executing the child, and commit this incarnation's output
        # once the write phase lands.  Off (default): one conf read,
        # zero journal-module calls (cProfile-pinned by
        # tests/test_recovery.py).
        ckpt = None
        from spark_rapids_tpu.config import RECOVERY_ENABLED

        if bool(c.get(RECOVERY_ENABLED)):
            ckpt = self._recovery_ckpt(c)
            if ckpt is not None:
                served = self._serve_recovered(c, *ckpt)
                if served is not None:
                    yield from served
                    return
        if c.get(DISTRIBUTED_ENABLED):
            # cross-host tier (ISSUE 14): route reduce partitions over
            # the worker processes when a coordinator with placeable
            # workers exists; otherwise fall through to the in-process
            # paths (elastic membership — zero workers is a valid state
            # between queries, not an error)
            from spark_rapids_tpu.distributed import peek_coordinator

            coord = peek_coordinator()
            if coord is not None and coord.placeable_workers():
                yield from self._execute_distributed(c, coord, ckpt)
                return
        if c.get(EXCHANGE_SPILL_ENABLED) \
                and str(c.get(SHUFFLE_MODE)).upper() != "CACHE_ONLY":
            yield from self._execute_spill_backed(c, ckpt)
            return
        mgr = get_shuffle_manager(self.conf)
        shuffle_id = mgr.register_shuffle()
        try:
            with self.metric("shuffleWriteTime").timed():
                for map_id, b in enumerate(
                        self.children[0].execute_columnar()):
                    mgr.write_map_output(shuffle_id, map_id,
                                         self.partition_batch(b))
            schema = self.output
            from spark_rapids_tpu.lifecycle.context import check_cancel

            for pid in range(self.num_partitions):
                # cooperative cancellation between reduce partitions: a
                # wide shuffle read must not outlive its query's deadline
                check_cancel()
                with self.metric("shuffleReadTime").timed():
                    out = mgr.read_partition(shuffle_id, pid, schema)
                if out is not None and out.num_rows > 0:
                    yield self._count_output(out)
        finally:
            mgr.unregister_shuffle(shuffle_id)

    # -- crash-consistent recovery (ISSUE 16) ---------------------------
    def _recovery_ckpt(self, c):
        """(journal, plan-stage fingerprint) for this exchange, or None
        when recovery cannot apply: unsafe partitioning exprs (no
        stable fingerprint) or a journal root that cannot open.  The
        fingerprint extends the compile-registry scope with the CHILD
        SUBTREE's plan identity — two exchanges with identical
        partitioning + output schema but different children must never
        trade checkpoints."""
        from spark_rapids_tpu.lifecycle import journal as _jn

        scope = self._registry_scope("ckpt")
        if scope is None:
            return None
        from spark_rapids_tpu.compilecache.keys import fingerprint

        fp = fingerprint(scope, _jn.plan_tree_fp(self.children[0]))
        try:
            return _jn.get_journal(c), fp
        # tpulint: disable=cancel-swallow (durability isolation: an
        # unopenable journal disables recovery for this query, never
        # fails it)
        except Exception:
            return None

    def _serve_recovered(self, c, jn, fp):
        """A generator over a prior incarnation's committed output for
        this stage, or None (no adoptable checkpoint — execute
        normally).  Local checkpoints are fully CRC-validated before
        the first yield; lease serves stream from the re-attached
        workers (a worker dying mid-serve raises WorkerLost into the
        fault domain like any distributed read)."""
        from spark_rapids_tpu.shuffle.partition_queues import (
            host_boundary_codec,
        )

        hit = jn.lookup_stage(fp)
        if hit is None:
            return None
        from spark_rapids_tpu.lifecycle.context import current

        ctx = current()
        qid = ctx.query_id if ctx is not None else "-"
        codec = host_boundary_codec(c)
        if hit[0] == "local":
            return self._gen_recovered_local(jn, fp, qid, codec, hit[1])
        _, wire, _placement, counts = hit
        return self._gen_recovered_lease(c, jn, fp, qid, codec, wire,
                                         counts)

    def _gen_recovered_local(self, jn, fp, qid, codec, parts):
        from spark_rapids_tpu.shuffle.serializer import deserialize_concat

        for pid in range(self.num_partitions):
            blobs = parts.get(pid) or []
            if not blobs:
                continue
            with self.metric("shuffleReadTime").timed():
                out = deserialize_concat(blobs, self.output, codec=codec)
            if out.num_rows > 0:
                yield self._count_output(out)
        jn.mark_recovered(fp, qid, len(parts))

    def _gen_recovered_lease(self, c, jn, fp, qid, codec, wire, counts):
        from spark_rapids_tpu.config import BATCH_SIZE_BYTES
        from spark_rapids_tpu.distributed import (
            ProtocolCorruption,
            peek_coordinator,
        )
        from spark_rapids_tpu.lifecycle.context import check_cancel
        from spark_rapids_tpu.shuffle.serializer import deserialize_concat

        coord = peek_coordinator()
        goal = int(c.get(BATCH_SIZE_BYTES))
        try:
            for pid in sorted(counts):
                check_cancel()
                expected = counts[pid]
                next_seq = 0
                while next_seq < expected:
                    with self.metric("shuffleReadTime").timed():
                        seqs, blobs, _n = coord.fetch_blocks(
                            wire, pid, after_seq=next_seq - 1,
                            max_bytes=goal)
                    if not seqs:
                        raise ProtocolCorruption(
                            f"recovered stage {fp}: worker returned no "
                            f"blocks for pid {pid} at seq "
                            f"{next_seq}/{expected}")
                    next_seq = seqs[-1] + 1
                    out = deserialize_concat(blobs, self.output,
                                             codec=codec)
                    if out.num_rows > 0:
                        yield self._count_output(out)
        finally:
            # adopted placements must not outlive the serve — release
            # on success AND on unwind (a failed serve re-executes; the
            # workers' copies are no longer adoptable either way)
            coord.release_exchange(wire)
        jn.mark_recovered(fp, qid, len(counts))

    def _commit_stage(self, ckpt, commit_fn) -> None:
        """Run one checkpoint commit, isolating durability failures
        from the query (a stage that cannot commit simply is not
        recoverable)."""
        from spark_rapids_tpu.lifecycle import QueryCancelled

        try:
            commit_fn()
        except QueryCancelled:
            raise
        # tpulint: disable=cancel-swallow (durability isolation: a
        # failed checkpoint commit must never fail the query)
        except Exception:
            pass

    def _execute_distributed(self, c, coord,
                             ckpt=None) -> Iterator[ColumnarBatch]:
        """Cross-host execution (ISSUE 14): partition slices are framed
        once (TKU2), shipped to coordinator-placed worker processes,
        AND retained in a producer-side spill-backed queue (device
        budget 0 — every entry a wire block) until the consuming side
        commits each partition.  A worker lost mid-shuffle is recovered
        by re-placement + re-drive of the retained blocks; the shuffle
        manager registration ties remote holdings to this query, so the
        query-end cleanup sweep releases them even on a mid-batch
        unwind."""
        from spark_rapids_tpu.config import (
            BATCH_SIZE_BYTES,
            DISTRIBUTED_REDRIVE_MAX,
            SPILL_DIR,
        )
        from spark_rapids_tpu.distributed.client import DistributedExchange
        from spark_rapids_tpu.exec.partition_sizing import (
            estimate_input_bytes,
        )
        from spark_rapids_tpu.lifecycle import QueryCancelled
        from spark_rapids_tpu.lifecycle.context import check_cancel
        from spark_rapids_tpu.shuffle.manager import get_shuffle_manager
        from spark_rapids_tpu.shuffle.partition_queues import (
            SpillBackedPartitionQueues,
            host_boundary_codec,
        )

        mgr = get_shuffle_manager(self.conf)
        exch_id = mgr.register_shuffle()
        # everything fallible — incl. placement inside
        # DistributedExchange.__init__, which raises WorkerLost when the
        # last placeable worker died since the execute_columnar check —
        # sits inside the try so the finally always unregisters the
        # shuffle id and closes whatever was built
        queues = None
        dist = None
        try:
            try:
                est = estimate_input_bytes(self.children[0], c)
            except QueryCancelled:
                raise
            except Exception:
                est = None
            # lineage buffer: device budget 0 (every entry a wire
            # block), host residency bounded by the shuffle host-store
            # limit with disk overflow — retaining a whole exchange
            # until its partitions commit must not pin the driver's RAM
            from spark_rapids_tpu.shuffle.manager import (
                SHUFFLE_HOST_STORE_LIMIT,
            )

            queues = SpillBackedPartitionQueues(
                self.num_partitions, self.output, device_budget=0,
                codec=host_boundary_codec(c),
                host_budget=int(c.get(SHUFFLE_HOST_STORE_LIMIT)),
                spill_dir=c.get(SPILL_DIR))
            dist = DistributedExchange(
                coord, exch_id, self.num_partitions, self.output,
                host_boundary_codec(c), queues, est_bytes=est,
                redrive_max_attempts=int(c.get(DISTRIBUTED_REDRIVE_MAX)))
            goal = int(c.get(BATCH_SIZE_BYTES))
            from spark_rapids_tpu.governor import context as _GOV

            _gov = _GOV.GOVERNOR
            if _gov is not None:
                goal = _gov.degraded_goal(goal)
            with self.metric("shuffleWriteTime").timed():
                for b in self.children[0].execute_columnar():
                    for pid, sl in self.partition_slices(b):
                        with self.metric("exchangeSpillTime").timed():
                            dist.add_slice(pid, sl)
            if ckpt is not None:
                # stage boundary reached: the worker-held partitions
                # ARE the checkpoint — journal a lease pinning them
                # past driver death (ISSUE 16).  The read phase below
                # does not release worker copies (only dist.close()
                # does), so a driver killed ANY time after this record
                # finds the full inventory on re-attach
                jn, fp = ckpt
                from spark_rapids_tpu.lifecycle.context import current

                _ctx = current()
                self._commit_stage(ckpt, lambda: jn.commit_lease(
                    fp, _ctx.query_id if _ctx is not None else "-",
                    coord.wire_of(exch_id), coord.placement_of(exch_id),
                    dist.block_counts()))
            for pid in range(self.num_partitions):
                check_cancel()
                it = dist.read_partition_chunks(pid, target_bytes=goal)
                while True:
                    with self.metric("shuffleReadTime").timed():
                        out = next(it, None)
                    if out is None:
                        break
                    if out.num_rows > 0:
                        yield self._count_output(out)
        finally:
            if dist is not None:
                dist.close()
            elif queues is not None:
                queues.close()
            mgr.unregister_shuffle(exch_id)

    def _execute_spill_backed(self, c,
                              ckpt=None) -> Iterator[ColumnarBatch]:
        """Stream partition slices through spill-backed queues: per
        input batch ONE partition program, each slice registered (or
        CRC-framed to host past the device budget) before the next
        materializes; reduce partitions drain in pid order — in
        batch-size-goal CHUNKS, never one whole-partition concat (a
        partition larger than the pool would re-materialize as a single
        unspillable batch and bust the residency bound) — released as
        they are read.  CancelToken observed at every append/read."""
        from spark_rapids_tpu.config import BATCH_SIZE_BYTES
        from spark_rapids_tpu.shuffle.partition_queues import (
            SpillBackedPartitionQueues,
            host_boundary_codec,
            queue_device_budget,
        )

        queues = SpillBackedPartitionQueues(
            self.num_partitions, self.output, queue_device_budget(c),
            codec=host_boundary_codec(c))
        goal = int(c.get(BATCH_SIZE_BYTES))
        # overload governor (ISSUE 13): under YELLOW/RED the drain
        # chunks shrink so each reduce step pins a smaller working set
        from spark_rapids_tpu.governor import context as _GOV

        _gov = _GOV.GOVERNOR
        if _gov is not None:
            goal = _gov.degraded_goal(goal)
        try:
            with self.metric("shuffleWriteTime").timed():
                for b in self.children[0].execute_columnar():
                    for pid, sl in self.partition_slices(b):
                        with self.metric("exchangeSpillTime").timed():
                            queues.append(pid, sl)
            if ckpt is not None:
                # stage boundary reached: snapshot every partition as
                # framed blobs and commit durably (atomic tmp+rename +
                # journal record) BEFORE the read phase drains the
                # queues — a driver killed past this point resumes by
                # serving the checkpoint instead of re-executing the
                # child (ISSUE 16)
                jn, fp = ckpt
                from spark_rapids_tpu.lifecycle.context import current

                _ctx = current()
                self._commit_stage(ckpt, lambda: jn.commit_local_stage(
                    fp, _ctx.query_id if _ctx is not None else "-",
                    {pid: queues.snapshot_framed(pid)
                     for pid in range(self.num_partitions)}))
            for pid in range(self.num_partitions):
                it = queues.read_chunks(pid, target_bytes=goal)
                while True:
                    with self.metric("shuffleReadTime").timed(), \
                            self.metric("exchangeSpillTime").timed():
                        out = next(it, None)
                    if out is None:
                        break
                    if out.num_rows > 0:
                        yield self._count_output(out)
        finally:
            queues.close()


class TpuBroadcastExchangeExec(TpuExec):
    """GpuBroadcastExchangeExec analog: materialize + (on mesh) replicate."""

    def __init__(self, child: TpuExec):
        super().__init__([child])

    @property
    def output(self):
        return self.children[0].output

    def aot_output_rows(self):
        rows = self.aot_input_rows()
        return None if rows is None else [sum(rows)]

    def aot_output_caps(self):
        caps = super().aot_output_caps()
        return caps if caps is not None else self.aot_input_concat_caps()

    def aot_emits_single_batch(self):
        return True

    def execute_columnar(self):
        batches = list(self.children[0].execute_columnar())
        if not batches:
            return
        out = (batches[0] if len(batches) == 1
               else ColumnarBatch.concat(batches))
        yield self._count_output(out)


class TpuAdaptiveShuffleReaderExec(TpuExec):
    """GpuCustomShuffleReaderExec analog (general AQE): reads an exchange's reduce partitions while RECORDING their
    measured rows/bytes, then coalesces ADJACENT SMALL partitions
    (below ``spark.rapids.tpu.exchange.coalesceSmallPartitionBytes``)
    into one read window up to the batch-size goal before emitting —
    the runtime-stats partition coalescing AQE performs on real
    clusters (SURVEY §2.4; fewer, right-sized batches for every
    downstream operator; each elided partition is one fewer program
    launch).  Partitions at or above the small
    threshold emit alone (an already-right-sized partition must not
    drag its neighbors into a doubled window).  Each window of k>1
    partitions bumps ``partitions_coalesced`` by k-1.

    ``stats`` (per-partition (rows, bytes)) and ``decision`` are exposed
    for explain/metrics, mirroring TpuAdaptiveJoinExec."""

    EXTRA_METRICS = {"partitionsCoalesced": "MODERATE"}

    def __init__(self, exchange: TpuShuffleExchangeExec,
                 target_bytes: int, small_bytes: int = 4 << 20):
        super().__init__([exchange])
        self.target_bytes = target_bytes
        self.small_bytes = small_bytes
        self.stats = []
        self.decision = None

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        d = f" decided={self.decision}" if self.decision else ""
        return (f"TpuAdaptiveShuffleReader(target="
                f"{self.target_bytes}B small={self.small_bytes}B){d}")

    def _flush(self, pending):
        from spark_rapids_tpu.columnar.batch import ColumnarBatch

        if len(pending) > 1:
            PC.bump("partitions_coalesced", len(pending) - 1)
            self.metric("partitionsCoalesced").add(len(pending) - 1)
        return (pending[0] if len(pending) == 1
                else ColumnarBatch.concat(pending))

    def execute_columnar(self):
        pending = []
        pending_bytes = 0
        n_in = 0
        n_out = 0
        for b in self.children[0].execute_columnar():
            n_in += 1
            nb = b.nbytes()
            self.stats.append((b.num_rows, nb))
            if nb >= self.small_bytes:
                # right-sized already: flush the open window, emit alone
                if pending:
                    n_out += 1
                    out = self._flush(pending)
                    pending, pending_bytes = [], 0
                    yield self._count_output(out)
                n_out += 1
                yield self._count_output(b)
                continue
            if pending and pending_bytes + nb > self.target_bytes:
                n_out += 1
                out = self._flush(pending)
                pending, pending_bytes = [], 0
                yield self._count_output(out)
            pending.append(b)
            pending_bytes += nb
            if pending_bytes >= self.target_bytes:
                n_out += 1
                out = self._flush(pending)
                pending, pending_bytes = [], 0
                yield self._count_output(out)
        if pending:
            n_out += 1
            yield self._count_output(self._flush(pending))
        self.decision = f"coalesced {n_in}->{n_out} partitions"
