"""ICI mesh execution — whole plan stages as one SPMD collective program.

Reference analog: the reference's distributed execution is Spark tasks
pulling shuffle blocks peer-to-peer over UCX (SURVEY.md §2.7/§5.8,
RapidsShuffleClient/Server).  TPU-first replacement: the stage pair

    HashAggregate(FINAL) <- [Coalesce] <- ShuffleExchange <-
    HashAggregate(PARTIAL, fused scan ops)

compiles to two shard_map programs over the device mesh with one host
sync between them:

    (a) per device:  local partial _agg_fn (the unchanged single-chip program)
                  -> spark murmur3 partition ids over the group keys
                  -> the row of the send matrix: groups for each peer
    host:            reads the n_dev x n_dev matrix; the per-peer quota is
                     its largest entry on the row-bucket ladder
    (b) per device:  all-to-all of every partial-buffer column over ICI at
                     that quota -> final _agg_fn on the received buffer rows

The per-device program IS the single-chip code path — shard_map only wires
the collectives around it (the "same program, sharded data" SPMD design the
scaling-book recipe prescribes).  Global (no-key) aggregates skip the
all-to-all: partial buffers are all-gathered and every device finalizes the
replicated merge (one row; replication is free).

The Spark-async vs SPMD-collective impedance mismatch (SURVEY.md §7 hard
part #1) is resolved by epoching: an exchange is already a full barrier in
Spark semantics, so executing it as one collective step loses no generality.

Quota layout: the aggregate's all-to-all reserves the counted quota per
peer (received capacity = n_dev x quota); the join, sort, window and
repartition stages reserve their input's local capacity per peer.
"""
from __future__ import annotations

import time
from typing import Iterator, List, Optional

import jax
from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu.perfcounters import tpu_jit
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.base import TpuExec


def _mesh_program(per_device, name: Optional[str] = None,
                  **shard_map_kwargs):
    """One mesh stage program: the ``shard_map`` body compiled as ONE
    jitted SPMD program (``name``: what the trace calls it).  Called
    un-jitted, shard_map's eager path compiles every primitive of the
    body as a program of its own and keeps none of them: ~1,200 compiles
    on EVERY collect of a grouped aggregate (measured on 4 virtual
    devices, PR 23) — minutes per collect where a compile costs what it
    does on the chip."""
    if name is not None:
        per_device.__name__ = per_device.__qualname__ = name
    return tpu_jit(shard_map(per_device, **shard_map_kwargs))


# ---------------------------------------------------------------------------
# The balanced row layout of a mesh stage's input
# ---------------------------------------------------------------------------

def _balanced_shards(batch: ColumnarBatch, mesh, axis: str):
    """(columns, per): the batch's columns row-sharded over ``mesh`` with
    its rows spread evenly: device d holds rows [d * per, (d + 1) * per)
    at the front of its shard of capacity / n_dev rows (the contiguous
    blocks of the padded batch would leave the last devices short)."""
    n_dev = int(mesh.devices.size)
    batch = _ceil_to_mesh(batch, n_dev)
    cap = batch.capacity // n_dev
    per = max(-(-batch.num_rows // n_dev), 1)
    rows = NamedSharding(mesh, P(axis))

    def lay(arr):
        a = arr[:n_dev * per].reshape((n_dev, per) + arr.shape[1:])
        a = jnp.pad(a, [(0, 0), (0, cap - per)] + [(0, 0)] * (arr.ndim - 1))
        return jax.device_put(a.reshape(arr.shape), rows)

    return [_map_col_arrays(c, lay) for c in batch.columns], per


# ---------------------------------------------------------------------------
# ICI shuffle accounting + the host boundary (ISSUE 10)
# ---------------------------------------------------------------------------

def _row_bytes(cols) -> int:
    """Bytes one row takes in every array of ``cols`` (an exchange routes
    each of them)."""
    return sum(a.dtype.itemsize * int(np.prod(a.shape[1:]))
               for a in jax.tree_util.tree_leaves(list(cols)))


def _ici_account(stage: str, n_dev: int, rows: int, nbytes: int,
                 dur_ns: int) -> None:
    """Per-collective-epoch accounting shared by every ICI stage exec:
    the ``ici_*`` counters (epochs; rows and bytes that left their chip,
    read from the program's own counts; wall of the collective step) and
    the per-query ``ici_shuffle`` diagnostics event.  The exchanged bytes
    never cross the host — the zero-host-bytes pin in
    tests/test_multichip.py holds the all-device path to that."""
    PC.bump("ici_epochs")
    PC.bump("ici_rows_exchanged", int(rows))
    PC.bump("ici_bytes_moved", int(nbytes))
    PC.bump("ici_shuffle_ns", int(dur_ns))
    from spark_rapids_tpu.diagnostics import context as _DIAG

    rec = _DIAG.RECORDER
    if rec is not None:
        rec.ici_shuffle(stage, n_dev, int(rows), int(nbytes), int(dur_ns))


def ici_host_frame(batch: ColumnarBatch,
                   codec: Optional[str] = None) -> bytes:
    """Frame an ICI/exchange batch crossing the HOST boundary as one
    CRC32-checked wire block (the PR 4 ``TKU2`` serializer): a flipped
    bit anywhere between write and read surfaces as a deterministic
    :class:`ShuffleCorruption` instead of silent wrong rows.  The
    spill-backed exchange queues frame every over-budget slice through
    here; device-to-device collective traffic never does."""
    from spark_rapids_tpu.shuffle.serializer import serialize_batch

    return serialize_batch(batch, codec=codec)


def ici_host_unframe(blob: bytes, schema,
                     codec: Optional[str] = None) -> ColumnarBatch:
    """Verify + decode one host-boundary block (raises
    ShuffleCorruption on CRC/codec rejection)."""
    from spark_rapids_tpu.shuffle.serializer import deserialize_concat

    return deserialize_concat([blob], schema, codec=codec)


def _pad_chars(chars, w):
    if chars.shape[-1] == w:
        return chars
    pad = [(0, 0)] * (chars.ndim - 1) + [(0, w - chars.shape[-1])]
    return jnp.pad(chars, pad)


def _concat_cols(a: DeviceColumn, b: DeviceColumn) -> DeviceColumn:
    """Row-concat two buffer-form device columns (flat or string)."""
    validity = jnp.concatenate([a.validity, b.validity])
    if a.is_string:
        w = max(a.width, b.width)
        return DeviceColumn(
            a.dtype, validity,
            chars=jnp.concatenate([_pad_chars(a.chars, w),
                                   _pad_chars(b.chars, w)]),
            lengths=jnp.concatenate([a.lengths, b.lengths]))
    return DeviceColumn(a.dtype, validity,
                        data=jnp.concatenate([a.data, b.data]))


def _epoch_batches(it, epoch_bytes: int):
    """Group a batch iterator into ~epoch_bytes concats (skipping empty
    batches) — the shared epoch bucketing of every ICI stage exec."""
    pending, size = [], 0
    for b in it:
        if b.num_rows == 0:
            continue
        pending.append(b)
        size += b.nbytes()
        if size >= epoch_bytes:
            yield (pending[0] if len(pending) == 1
                   else ColumnarBatch.concat(pending))
            pending, size = [], 0
    if pending:
        yield (pending[0] if len(pending) == 1
               else ColumnarBatch.concat(pending))


def _slice_cols(cols, cap):
    return tuple(
        DeviceColumn(c.dtype, c.validity[:cap],
                     data=None if c.data is None else c.data[:cap],
                     chars=None if c.chars is None else c.chars[:cap],
                     lengths=None if c.lengths is None else c.lengths[:cap])
        for c in cols)


def _map_col_arrays(c: DeviceColumn, f) -> DeviceColumn:
    """Rebuild a DeviceColumn with ``f`` applied to every row-major array
    (validity/data/chars/lengths/elem_valid, recursing into struct
    children) — the one place column-layout completeness lives for the
    mesh helpers below."""
    return DeviceColumn(
        c.dtype, f(c.validity),
        data=None if c.data is None else f(c.data),
        chars=None if c.chars is None else f(c.chars),
        lengths=None if c.lengths is None else f(c.lengths),
        elem_valid=None if c.elem_valid is None else f(c.elem_valid),
        children=None if c.children is None
        else tuple(_map_col_arrays(k, f) for k in c.children))


def _fit_cols(cols, cap):
    """Slice or zero-pad columns to exactly ``cap`` rows."""
    def fit(arr):
        n = arr.shape[0]
        if cap <= n:
            return arr[:cap]
        return jnp.pad(arr, [(0, cap - n)] + [(0, 0)] * (arr.ndim - 1))

    return tuple(_map_col_arrays(c, fit) for c in cols)


def _rebucket_sharded(cols, per_dev_cap: int, tgt_cap: int, n_dev: int,
                      mesh, axis: str):
    """Re-bucket device-sharded prefix-compacted columns from per_dev_cap
    to tgt_cap rows per device (the agg accumulator's resize, shared by
    the window/repartition stages)."""
    def rs(arr):
        shp = arr.shape
        a = arr.reshape((n_dev, per_dev_cap) + shp[1:])
        if tgt_cap <= per_dev_cap:
            a = a[:, :tgt_cap]
        else:
            a = jnp.pad(a, [(0, 0), (0, tgt_cap - per_dev_cap)]
                        + [(0, 0)] * (arr.ndim - 1))
        out = a.reshape((n_dev * tgt_cap,) + shp[1:])
        return jax.device_put(out, NamedSharding(mesh, P(axis)))

    return [_map_col_arrays(c, rs) for c in cols]


def _ceil_to_mesh(batch: ColumnarBatch, n_dev: int) -> ColumnarBatch:
    """Pad a batch's capacity up to a multiple of the device count."""
    cap = batch.capacity
    if cap % n_dev or cap < n_dev:
        return ColumnarBatch(
            [c.slice_to(-(-cap // n_dev) * n_dev) for c in batch.columns],
            batch.num_rows, batch.schema)
    return batch


def _shard_cols(batch: ColumnarBatch, mesh, axis: str):
    """Row-shard every column array of a batch over the mesh axis."""
    def put(arr):
        return jax.device_put(arr, NamedSharding(mesh, P(axis)))

    return [_map_col_arrays(c, put) for c in batch.columns]


class TpuIciShuffleAggExec(TpuExec):
    """Fused distributed aggregation stage over a jax Mesh.

    Epoch-streamed: the child's batches flow through the collective
    programs in bounded epochs.  Grouped, per epoch:

      (a) ``ici_agg_partial``, per device: local partial agg, the
          murmur3 partition id of each of its groups, and how many groups
          go to each peer (one row of the n_dev x n_dev send matrix);
      host: ONE sync reads the matrix.  The quota Q is its largest entry
          on the row-bucket ladder, and G the ladder rung of the most
          groups a device holds;
      (b) ``ici_agg_exchange``, per device: all-to-all of the partial's
          first G rows at Q slots per peer (received capacity n_dev x Q),
          then MERGE with the device-resident accumulator (an epoch with
          more to come; the unfinalized buffer form, re-bucketed to the
          smallest pow2 per-device capacity that holds every device's
          groups) or, in the last epoch, the FINAL aggregate over the
          accumulator and the received rows: merge and finalize in one
          sort, so a one-epoch stage runs two programs.

    Global (no-key) aggregates run one epoch program (partial ->
    all-gather -> merge) and one finalize program after the last epoch.

    A grouped epoch is row-sharded in the balanced layout
    (``_balanced_shards``), counted in ``mesh_reshard_bytes``.  The shards of
    a resident table's batches (``scan.cacheDeviceBatches``: the same
    arrays every collect) are kept, so only the first collect moves the
    table between chips."""

    def __init__(self, partial, final, mesh, axis: str = "dp",
                 epoch_bytes: int = 1 << 28):
        super().__init__(list(partial.children))
        self.partial = partial
        self.final = final
        self.mesh = mesh
        self.axis = axis
        self.epoch_bytes = epoch_bytes
        self._programs = {}
        self._finalize_p = None
        self._shards = {}       # a resident input's shards, by its arrays

    @property
    def output(self):
        return self.final.output

    def describe(self):
        n = self.mesh.devices.size
        return (f"TpuIciShuffleAgg[{n}dev] "
                f"partial=({self.partial.describe()})"
                f" final=({self.final.describe()})")

    # -- grouped: programs (a) and (b) ----------------------------------
    def _build_partial_program(self):
        axis = self.axis
        n_dev = int(self.mesh.devices.size)
        partial = self.partial
        nkeys = len(partial.grouping)

        def per_device(cols, num_rows, per):
            from spark_rapids_tpu.ops.hashing import spark_partition_ids
            from spark_rapids_tpu.parallel.mesh import peer_counts

            idx = jax.lax.axis_index(axis).astype(jnp.int32)
            nloc = jnp.clip(num_rows - idx * per, 0, per)
            pcols, ng = partial._agg_fn(cols, nloc)
            grows = jnp.arange(pcols[0].capacity) < ng
            tgt = spark_partition_ids(list(pcols[:nkeys]), n_dev)
            sent = peer_counts(grows, tgt, n_dev)
            return tuple(pcols), tgt, sent.reshape(1, n_dev)

        return _mesh_program(
            per_device, "ici_agg_partial", mesh=self.mesh,
            in_specs=(P(axis), P(), P()),
            out_specs=(P(axis), P(axis), P(axis)),
            check_vma=False)

    def _build_exchange_program(self, groups_cap: int, quota: int,
                                acc_cap_local: int, last: bool):
        axis = self.axis
        n_dev = int(self.mesh.devices.size)
        final = self.final

        def per_device(pcols, tgt, sent, *acc):
            from spark_rapids_tpu.parallel.mesh import ici_all_to_all_columns

            grows = jnp.arange(groups_cap) < jnp.sum(sent)
            rcols, rok = ici_all_to_all_columns(
                list(_fit_cols(pcols, groups_cap)), grows,
                tgt[:groups_cap], n_dev, axis, quota=quota)
            if acc_cap_local:
                acc_cols, acc_ng = acc
                acc_ok = (jnp.arange(acc_cap_local, dtype=jnp.int32)
                          < acc_ng[0])
                rcols = [_concat_cols(a, r)
                         for a, r in zip(acc_cols, rcols)]
                rok = jnp.concatenate([acc_ok, rok])
            fn = final._agg_fn if last else final._merge_fn
            mcols, mng = fn(tuple(rcols), jnp.int32(rcols[0].capacity),
                            row_valid=rok)
            return tuple(mcols), mng.astype(jnp.int32).reshape(1)

        spec = P(axis)
        return _mesh_program(
            per_device, "ici_agg_exchange", mesh=self.mesh,
            in_specs=(spec, spec, spec) + ((spec, spec) if acc_cap_local
                                           else ()),
            out_specs=(spec, spec),
            check_vma=False)

    def _mesh_rows(self, batch: ColumnarBatch, kept):
        """(row-sharded columns, rows a device holds at most) of an epoch.
        ``kept`` (a resident input's collect, else None) receives the
        shards under the batch's arrays, and shards the last collect kept
        for the same arrays are used again; whatever is laid out anew is
        counted in ``mesh_reshard_bytes``."""
        leaves = jax.tree_util.tree_leaves(list(batch.columns))
        key = (batch.num_rows,) + tuple(map(id, leaves))
        # an entry holds its arrays, so no live array can take their ids
        hit = self._shards.get(key) if kept is not None else None
        if hit is None:
            PC.bump("mesh_reshard_bytes", batch.nbytes())
            hit = (leaves,) + tuple(
                _balanced_shards(batch, self.mesh, self.axis))
        if kept is not None:
            kept[key] = hit
        return hit[1], hit[2]

    def _run_grouped_epoch(self, batch: ColumnarBatch, acc, acc_ng,
                           last: bool, kept):
        from spark_rapids_tpu.columnar.column import (DEFAULT_ROW_BUCKETS,
                                                      round_up_bucket)

        n_dev = int(self.mesh.devices.size)
        with PC.span("srt.ici.partial"):
            cols, per = self._mesh_rows(batch, kept)
            if "partial" not in self._programs:
                self._programs["partial"] = self._build_partial_program()
            self.partial._launch_full_width(cols[0].capacity // n_dev)
            pcols, tgt, sent = self._programs["partial"](
                tuple(cols), jnp.int32(batch.num_rows), jnp.int32(per))
            sent_np = np.asarray(sent)      # the one sync of (a)
        local_cap = pcols[0].capacity // n_dev
        groups_cap = min(round_up_bucket(max(int(sent_np.sum(1).max()), 1),
                                         DEFAULT_ROW_BUCKETS), local_cap)
        quota = min(round_up_bucket(max(int(sent_np.max()), 1),
                                    DEFAULT_ROW_BUCKETS), groups_cap)
        PC.bump("ici_quota_rows", quota)
        acc_cap_local = 0 if acc is None else acc[0].capacity // n_dev
        key = (local_cap, groups_cap, quota, acc_cap_local, last)
        with PC.span("srt.ici.exchange") as sp:
            if key not in self._programs:
                self._programs[key] = self._build_exchange_program(
                    groups_cap, quota, acc_cap_local, last)
            args = (pcols, tgt, sent)
            if acc is not None:
                args = args + (tuple(acc), acc_ng)
            if last:
                self.final._launch_full_width(n_dev * quota + acc_cap_local)
            mcols, mng = self._programs[key](*args)
            mng_np = np.asarray(mng)        # one host sync per epoch
        moved = int(sent_np.sum() - np.trace(sent_np))
        _ici_account(self.node_name, n_dev, moved,
                     moved * _row_bytes(pcols), sp.ns)
        if last:
            return list(mcols), mng_np
        mcl = mcols[0].capacity // n_dev
        need = max(int(mng_np.max()), 1)
        tgt_cap = 1 << (need - 1).bit_length()
        if tgt_cap != mcl:
            mcols = _rebucket_sharded(mcols, mcl, tgt_cap, n_dev,
                                      self.mesh, self.axis)
        return list(mcols), mng

    # -- global: one epoch program, one finalize -------------------------
    def _build_epoch_program(self, first: bool):
        """One global epoch: partial -> all-gather -> merge into the
        accumulator (``first`` epochs have none)."""
        axis = self.axis
        partial = self.partial
        final = self.final

        def per_device(cols, num_rows, *acc):
            local_cap = cols[0].capacity
            idx = jax.lax.axis_index(axis)
            nloc = jnp.clip(num_rows - idx.astype(jnp.int32) * local_cap,
                            0, local_cap)
            pcols, ng = partial._agg_fn(cols, nloc)
            grows = jnp.arange(pcols[0].capacity) < ng
            rcols = []
            for c in pcols:
                validity = jax.lax.all_gather(c.validity, axis, tiled=True)
                if c.is_string:
                    rcols.append(DeviceColumn(
                        c.dtype, validity,
                        chars=jax.lax.all_gather(c.chars, axis, tiled=True),
                        lengths=jax.lax.all_gather(c.lengths, axis,
                                                   tiled=True)))
                else:
                    rcols.append(DeviceColumn(
                        c.dtype, validity,
                        data=jax.lax.all_gather(c.data, axis, tiled=True)))
            rok = jax.lax.all_gather(grows, axis, tiled=True)
            if not first:
                acc_cols, acc_ng = acc
                acc_cap = acc_cols[0].capacity
                acc_ok = jnp.arange(acc_cap, dtype=jnp.int32) < acc_ng[0]
                rcols = [_concat_cols(a, r)
                         for a, r in zip(acc_cols, rcols)]
                rok = jnp.concatenate([acc_ok, rok])
            mcols, _ = final._merge_fn(
                tuple(rcols), jnp.int32(rcols[0].capacity), row_valid=rok)
            return tuple(mcols), jnp.int32(1).reshape(1)

        in_specs = (P(axis), P()) + (() if first else (P(), P()))
        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(P(), P()),
            check_vma=False)

    def _build_finalize_program(self, acc_cap: int):
        final = self.final

        def per_device(acc_cols, acc_ng):
            acc_ok = jnp.arange(acc_cap, dtype=jnp.int32) < acc_ng[0]
            fcols, fng = final._agg_fn(
                acc_cols, jnp.int32(acc_cap), row_valid=acc_ok)
            return tuple(fcols), fng.astype(jnp.int32).reshape(1)

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(), P()),
            out_specs=(P(), P()),
            check_vma=False)

    def _run_global_epoch(self, batch: ColumnarBatch, acc, acc_ng):
        n_dev = int(self.mesh.devices.size)
        batch = _ceil_to_mesh(batch, n_dev)
        PC.bump("mesh_reshard_bytes", batch.nbytes())
        sharded = self._shard_batch(batch)
        first = acc is None
        key = (batch.capacity, first, 0 if first else acc[0].capacity)
        if key not in self._programs:
            self._programs[key] = self._build_epoch_program(first)
        args = (tuple(sharded), jnp.int32(batch.num_rows))
        if not first:
            args = args + (tuple(acc), acc_ng)
        with PC.span("srt.ici.exchange") as sp:
            mcols, mng = self._programs[key](*args)
            np.asarray(mng)                 # one host sync per epoch
        # each device's one partial row goes to every other device
        _ici_account(self.node_name, n_dev, n_dev * (n_dev - 1),
                     n_dev * (n_dev - 1) * _row_bytes(mcols), sp.ns)
        return [c.slice_to(1) for c in mcols], mng

    # ------------------------------------------------------------------
    def _epochs(self, it, resident: bool) -> Iterator[ColumnarBatch]:
        """Epochs, each with whether it is the last.  A resident input's
        batches are epochs as they come: their shards are kept, and a
        concat would be new arrays every collect."""
        batches = it if resident else _epoch_batches(it, self.epoch_bytes)
        prev = None
        for b in batches:
            if b.num_rows == 0:
                continue
            if prev is not None:
                yield prev, False
            prev = b
        if prev is not None:
            yield prev, True

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.exec.basic import TpuLocalTableScanExec

        grouped = bool(self.final.grouping)
        child = self.children[0]
        resident = (grouped and isinstance(child, TpuLocalTableScanExec)
                    and child.cache_device)
        kept = {} if resident else None
        acc = None
        acc_ng = None
        with self.metrics["opTime"].timed():
            for epoch, last in self._epochs(child.execute_columnar(),
                                            resident):
                if grouped:
                    acc, acc_ng = self._run_grouped_epoch(epoch, acc,
                                                          acc_ng, last, kept)
                else:
                    acc, acc_ng = self._run_global_epoch(epoch, acc, acc_ng)
            if resident:
                self._shards = kept
            if acc is None:
                yield from self._empty_input()
                return
            if not grouped:
                with PC.span("srt.ici.finalize"):
                    acc_cap = acc[0].capacity
                    if (self._finalize_p is None
                            or self._finalize_p[0] != acc_cap):
                        self._finalize_p = (
                            acc_cap, self._build_finalize_program(acc_cap))
                    fcols, fng = self._finalize_p[1](tuple(acc), acc_ng)
                    np.asarray(fng)         # one host sync
            else:
                # the last epoch's program (b) ran the final aggregate and
                # synced its per-device row counts
                fcols, fng_np = acc, acc_ng
        out_schema = self.final.output
        if not grouped:
            yield self._count_output(
                ColumnarBatch([c.gather(jnp.arange(1)) for c in fcols],
                              1, out_schema))
            return
        with PC.span("srt.ici.emit"):
            out = [ColumnarBatch(cols, int(fng_np[d]), out_schema)
                   for d, cols in enumerate(self._per_device(fcols))
                   if fng_np[d]]
        for b in out:
            yield self._count_output(b)

    def _per_device(self, cols):
        """Each device's own block of row-sharded columns, as columns on
        that device (its shards: no copy, nothing crosses a chip)."""
        n_dev = int(self.mesh.devices.size)
        per_dev_cap = cols[0].capacity // n_dev

        def block(arr, d):
            return next(s.data for s in arr.addressable_shards
                        if (s.index[0].start or 0) == d * per_dev_cap)

        return [[_map_col_arrays(c, lambda a, d=d: block(a, d))
                 for c in cols] for d in range(n_dev)]

    def _shard_batch(self, batch: ColumnarBatch) -> List[DeviceColumn]:
        """Row-shard every column array over the mesh axis."""
        def put(arr):
            if arr is None:
                return None
            spec = P(self.axis) if arr.ndim >= 1 else P()
            return jax.device_put(arr, NamedSharding(self.mesh, spec))

        return [DeviceColumn(c.dtype, put(c.validity), data=put(c.data),
                             chars=put(c.chars), lengths=put(c.lengths),
                             elem_valid=put(c.elem_valid))
                for c in batch.columns]

    def _empty_input(self):
        """Empty scan: reproduce the single-chip chain's semantics — the
        partial emits its initial buffer row (global agg) which the final
        merges and finalizes; grouped aggregates emit nothing."""
        from spark_rapids_tpu.columnar.batch import empty_batch

        if self.final.grouping:
            yield self._count_output(empty_batch(self.final.output))
            return
        pb = self.partial._global_agg_empty()
        merged = self.final._merge_batch(pb)
        yield self._count_output(self.final._finalize(merged))


class TpuIciShuffleJoinExec(TpuExec):
    """Distributed shuffled equi-join over the mesh — the UCX-shuffle
    join's TPU-native replacement (SURVEY.md §5.8 mode 2).

    Two SPMD steps (mirroring the agg exec's epoch design):

      1. COLLECTIVE program: both inputs row-shard over the mesh; each
         device computes murmur3 partition ids of its join keys and
         all-to-alls both sides over ICI (null-keyed rows stay put), then
         sorts its received build keys and probes counts — returning the
         received shards + gather-plan arrays, all still device-sharded.
      2. LOCAL program: with the per-device pair counts synced once to the
         host (the static output capacity), a collective-free shard_map
         materializes each device's join output via the same searchsorted
         gather maps the single-chip join uses.

    Supported: INNER (incl. residual conditions,
    filtered in the materialization program) / LEFT_OUTER / LEFT_SEMI /
    LEFT_ANTI / RIGHT_OUTER (mirror-swapped to LEFT_OUTER, columns
    reordered on emit — the single-chip _execute_right_outer design) /
    FULL_OUTER (LEFT_OUTER streaming + device-resident matched-build mask
    + one unmatched-build tail program after the last epoch).
    """

    # AQE skew-split count (OptimizeSkewedJoin analog)
    EXTRA_METRICS = {"skewSplits": "DEBUG"}

    def __init__(self, join, left_inner, right_inner, mesh,
                 axis: str = "dp", epoch_bytes: int = 1 << 28):
        from spark_rapids_tpu.plan.nodes import JoinType

        self._orig_output = join.output
        # the mesh programs take no emit list: they run the join at its
        # full output and the emitted columns are selected at the end
        self._emit_sel = join.emit
        join = join.with_full_output()
        self._mirror_nl = None
        if join.join_type == JoinType.RIGHT_OUTER:
            from spark_rapids_tpu.exec.join import (
                TpuShuffledSymmetricHashJoinExec,
            )

            swapped_schema = T.StructType(
                list(right_inner.output.fields)
                + [T.StructField(f.name, f.dataType, True)
                   for f in left_inner.output.fields])
            join = TpuShuffledSymmetricHashJoinExec(
                right_inner, left_inner, join.right_keys, join.left_keys,
                JoinType.LEFT_OUTER, join.condition, swapped_schema,
                join.ansi)
            left_inner, right_inner = right_inner, left_inner
            self._mirror_nl = len(left_inner.output.fields)
        super().__init__([left_inner, right_inner])
        self.join = join            # TpuShuffledSymmetricHashJoinExec
        self.mesh = mesh
        self.axis = axis
        self.epoch_bytes = epoch_bytes
        self._pbuild = None
        self._pprobe = {}
        self._p2 = {}
        self._ptail = None

    @property
    def output(self):
        return self._orig_output

    def describe(self):
        n = self.mesh.devices.size
        jt = ("right_outer(mirrored)" if self._mirror_nl is not None
              else self.join.join_type.value)
        return (f"TpuIciShuffleJoin[{n}dev] "
                f"{jt} "
                f"[{self.join.describe()}]")

    # ------------------------------------------------------------------
    def _keys_and_valid(self, cols, schema, keys, nloc, ansi):
        from spark_rapids_tpu.exec.join import _key_words_of
        from spark_rapids_tpu.expr.base import EvalContext

        cap = cols[0].capacity
        b = ColumnarBatch(list(cols), nloc, schema)
        ctx = EvalContext(b, ansi=ansi)
        key_cols = [k.eval_tpu(ctx) for k in keys]
        rows = jnp.arange(cap) < nloc
        kvalid = rows
        for kc in key_cols:
            kvalid = kvalid & kc.validity
        return key_cols, rows, kvalid

    def _build_pbuild(self, r_schema):
        """One-time collective: all-to-all the BUILD side by key hash and
        sort each device's received keys.  The returned arrays stay
        device-resident across every probe epoch."""
        axis = self.axis
        n_dev = int(self.mesh.devices.size)
        join = self.join

        def per_device(rcols, r_rows):
            from spark_rapids_tpu.exec.join import _key_words_of
            from spark_rapids_tpu.ops.hashing import spark_partition_ids
            from spark_rapids_tpu.parallel.mesh import (ici_all_to_all_columns,
                                                        off_chip_rows)

            idx = jax.lax.axis_index(axis)
            rcap = rcols[0].capacity
            nloc_r = jnp.clip(r_rows - idx.astype(jnp.int32) * rcap, 0, rcap)
            rkeys, rrows, rkvalid = self._keys_and_valid(
                rcols, r_schema, join.right_keys, nloc_r, join.ansi)
            tgt_r = jnp.where(
                rkvalid,
                spark_partition_ids(rkeys, n_dev),
                idx.astype(jnp.int32))  # null-keyed rows stay local
            rr, rr_ok = ici_all_to_all_columns(list(rcols), rrows, tgt_r,
                                               n_dev, axis)
            bkeys, _, bkvalid = self._keys_and_valid(
                rr, r_schema, join.right_keys,
                jnp.int32(rr[0].capacity), join.ansi)
            bkvalid = bkvalid & rr_ok
            bwords = _key_words_of(bkeys)
            inv = (~bkvalid).astype(jnp.int64)
            iota = jnp.arange(rr[0].capacity, dtype=jnp.int32)
            srt = jax.lax.sort(tuple([inv] + bwords + [iota]),
                               num_keys=1 + len(bwords), is_stable=True)
            swords = list(srt[1:-1])
            row_index = srt[-1]
            n_valid = jnp.sum(bkvalid.astype(jnp.int32))
            return (tuple(rr), tuple(swords), row_index,
                    n_valid.reshape(1), rr_ok,
                    off_chip_rows(rrows, tgt_r, axis))

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(axis), P()),
            out_specs=(P(axis),) * 6,
            check_vma=False)

    def _build_pprobe(self, l_schema):
        """Per probe epoch: all-to-all the epoch's PROBE rows and count
        matches against the resident sorted build keys.  FULL OUTER also
        ORs covered build positions (sorted space, diff-array) into the
        device-resident matched accumulator."""
        from spark_rapids_tpu.plan.nodes import JoinType

        axis = self.axis
        n_dev = int(self.mesh.devices.size)
        join = self.join
        full = join.join_type == JoinType.FULL_OUTER

        def per_device(lcols, l_rows, swords, n_valid, *acc):
            from spark_rapids_tpu.exec.join import (
                _key_words_of,
                _multiword_searchsorted,
            )
            from spark_rapids_tpu.ops.hashing import spark_partition_ids
            from spark_rapids_tpu.parallel.mesh import (ici_all_to_all_columns,
                                                        off_chip_rows)

            idx = jax.lax.axis_index(axis)
            lcap = lcols[0].capacity
            nloc_l = jnp.clip(l_rows - idx.astype(jnp.int32) * lcap, 0, lcap)
            lkeys, lrows, lkvalid = self._keys_and_valid(
                lcols, l_schema, join.left_keys, nloc_l, join.ansi)
            tgt_l = jnp.where(
                lkvalid,
                spark_partition_ids(lkeys, n_dev),
                idx.astype(jnp.int32))
            rl, rl_ok = ici_all_to_all_columns(list(lcols), lrows, tgt_l,
                                               n_dev, axis)
            pkeys, _, pkvalid = self._keys_and_valid(
                rl, l_schema, join.left_keys,
                jnp.int32(rl[0].capacity), join.ansi)
            pkvalid = pkvalid & rl_ok
            qwords = _key_words_of(pkeys)
            lo = _multiword_searchsorted(list(swords), n_valid[0], qwords,
                                         "left")
            hi = _multiword_searchsorted(list(swords), n_valid[0], qwords,
                                         "right")
            counts = jnp.where(pkvalid, hi - lo, 0)
            total = jnp.sum(counts.astype(jnp.int64))
            unmatched = rl_ok & (counts == 0)
            n_unmatched = jnp.sum(unmatched.astype(jnp.int64))
            out = (tuple(rl), lo, counts, unmatched, rl_ok,
                   jnp.stack([total, n_unmatched]).reshape(1, 2))
            if full:
                bcap = swords[0].shape[0]
                diff = jnp.zeros(bcap + 1, jnp.int32)
                has = counts > 0
                start = jnp.where(has, lo, bcap)
                end = jnp.where(has, lo + counts, bcap)
                diff = diff.at[start].add(1, mode="drop")
                diff = diff.at[end].add(-1, mode="drop")
                covered_sorted = jnp.cumsum(diff[:-1]) > 0
                out = out + (acc[0] | covered_sorted,)
            return out + (off_chip_rows(lrows, tgt_l, axis),)

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(axis), P(), P(axis), P(axis))
            + ((P(axis),) if full else ()),
            out_specs=(P(axis),) * (8 if full else 7),
            check_vma=False)

    def _build_p2(self, out_cap, l_schema, r_schema, n_l):
        """Collective-free per-device materialization."""
        axis = self.axis
        join = self.join
        jt = join.join_type
        from spark_rapids_tpu.plan.nodes import JoinType

        def per_device(flat, row_index, lo, counts, unmatched, rl_ok,
                       totals):
            from spark_rapids_tpu.ops.filterops import (
                compact_columns,
                gather_columns,
            )

            lcols = list(flat[:n_l])
            rcols = list(flat[n_l:])
            total = totals[0, 0]
            n_um = totals[0, 1]
            if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
                keep = ((counts == 0) if jt == JoinType.LEFT_ANTI
                        else (counts > 0)) & rl_ok
                out, cnt = compact_columns(keep, lcols)
                return tuple(out), cnt.astype(jnp.int64).reshape(1)
            from spark_rapids_tpu.exec.join import _slots_to_probe_rows

            n = counts.shape[0]
            offsets = jnp.cumsum(counts.astype(jnp.int64))
            excl = offsets - counts.astype(jnp.int64)
            j = jnp.arange(out_cap, dtype=jnp.int64)
            probe_row = _slots_to_probe_rows(excl, counts, out_cap)
            k = j - excl[probe_row]
            build_pos = lo[probe_row].astype(jnp.int64) + k
            bcap = row_index.shape[0]
            build_row = row_index[jnp.clip(build_pos, 0,
                                           bcap - 1).astype(jnp.int32)]
            in_pairs = j < total
            with_um = jt in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER)
            probe_idx = jnp.where(in_pairs, probe_row, 0)
            out_rows = total + (n_um if with_um else 0)
            if with_um:
                um_pos = jnp.cumsum(unmatched.astype(jnp.int64)) - 1
                um_slot = total + um_pos
                scatter_to = jnp.where(unmatched, um_slot,
                                       out_cap).astype(jnp.int64)
                probe_idx_full = jnp.zeros(out_cap, jnp.int32).at[
                    jnp.clip(scatter_to, 0, out_cap)].set(
                    jnp.arange(n, dtype=jnp.int32), mode="drop")
                probe_idx = jnp.where(in_pairs, probe_row, probe_idx_full)
            row_valid = j < out_rows
            out_l = gather_columns(probe_idx, row_valid, lcols)
            out_r = gather_columns(
                jnp.where(in_pairs, build_row, 0), row_valid & in_pairs,
                rcols)
            if join.condition is not None and jt == JoinType.INNER:
                # residual condition: evaluate over the materialized
                # pairs and compact (single-chip _apply_condition, fused
                # into this program)
                from spark_rapids_tpu.expr.base import EvalContext

                b = ColumnarBatch(list(out_l) + list(out_r), out_rows,
                                  join.output)
                ctx = EvalContext(b, ansi=join.ansi)
                pred = join.condition.eval_tpu(ctx)
                keep = pred.data & pred.validity & row_valid
                out, cnt = compact_columns(keep, list(out_l) + list(out_r))
                return tuple(out), cnt.astype(jnp.int64).reshape(1)
            return (tuple(out_l + out_r),
                    out_rows.astype(jnp.int64).reshape(1))

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(axis),) * 7,
            out_specs=(P(axis), P(axis)),
            check_vma=False)

    def _build_ptail(self, bcap_local: int):
        """FULL OUTER tail: per device, compact the build rows never
        covered by any probe epoch (single-chip _unmatched_build_tail,
        device-resident)."""
        axis = self.axis

        def per_device(rr, row_index, matched_sorted, rok):
            from spark_rapids_tpu.ops.filterops import compact_columns

            matched_orig = jnp.zeros(bcap_local, jnp.bool_).at[
                row_index].set(matched_sorted, mode="drop")
            keep = rok & ~matched_orig
            out, cnt = compact_columns(keep, list(rr))
            return tuple(out), cnt.astype(jnp.int64).reshape(1)

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(axis),) * 4,
            out_specs=(P(axis), P(axis)),
            check_vma=False)

    def _null_cols(self, fields, cap: int):
        """All-null columns for the unmatched side of an outer emit."""
        cols = []
        for f in fields:
            if isinstance(f.dataType, T.StringType):
                cols.append(DeviceColumn(
                    f.dataType, jnp.zeros(cap, jnp.bool_),
                    chars=jnp.zeros((cap, 8), jnp.uint8),
                    lengths=jnp.zeros(cap, jnp.int32)))
            else:
                cols.append(DeviceColumn(
                    f.dataType, jnp.zeros(cap, jnp.bool_),
                    data=jnp.zeros(cap, T.storage_dtype(f.dataType))))
        return cols

    # ------------------------------------------------------------------
    def _collect_side(self, child) -> ColumnarBatch:
        batches = list(child.execute_columnar())
        if not batches:
            from spark_rapids_tpu.columnar.batch import empty_batch

            return empty_batch(child.output)
        return (batches[0] if len(batches) == 1
                else ColumnarBatch.concat(batches))

    def _pad_for_mesh(self, batch: ColumnarBatch) -> ColumnarBatch:
        n_dev = int(self.mesh.devices.size)
        cap = batch.capacity
        if cap % n_dev or cap < n_dev:
            batch = ColumnarBatch(
                [c.slice_to(-(-cap // n_dev) * n_dev)
                 for c in batch.columns], batch.num_rows, batch.schema)
        return batch

    def _shard(self, batch: ColumnarBatch) -> List[DeviceColumn]:
        def put(arr):
            if arr is None:
                return None
            return jax.device_put(
                arr, NamedSharding(self.mesh, P(self.axis)))

        return [DeviceColumn(c.dtype, put(c.validity), data=put(c.data),
                             chars=put(c.chars), lengths=put(c.lengths),
                             elem_valid=put(c.elem_valid))
                for c in batch.columns]

    def _epochs(self, it) -> Iterator[ColumnarBatch]:
        return _epoch_batches(it, self.epoch_bytes)

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        """Build once, then stream the probe side through the mesh in
        epochs: per-device memory is the exchanged build side + one probe
        epoch (the reference's streamed-side iteration; build residency is
        hash-join's inherent requirement, sub-partitioning being its
        escape hatch on the single-chip path)."""
        from spark_rapids_tpu.plan.nodes import JoinType

        n_dev = int(self.mesh.devices.size)
        right = self._pad_for_mesh(self._collect_side(self.children[1]))
        l_schema = self.children[0].output
        r_schema = right.schema
        jt = self.join.join_type
        out_schema = self.join.output
        keep_cols = len(out_schema.fields)
        full = jt == JoinType.FULL_OUTER
        with self.metrics["opTime"].timed():
            rs = self._shard(right)
            if self._pbuild is None:
                self._pbuild = self._build_pbuild(r_schema)
            t0 = time.perf_counter_ns()
            rr, swords, row_index, n_valid, rr_ok, b_moved = self._pbuild(
                tuple(rs), jnp.int32(right.num_rows))
            # the build's rows that left their chip are read with the
            # first probe epoch's sync (no sync of their own)
            build_account = [b_moved, _row_bytes(rs),
                             time.perf_counter_ns() - t0]

        def account(moved_np, row_bytes, dur_ns):
            moved = int(np.asarray(moved_np).sum())
            _ici_account(self.node_name, n_dev, moved, moved * row_bytes,
                         dur_ns)

        matched = None
        if full:
            matched = jax.device_put(
                jnp.zeros(swords[0].shape[0], jnp.bool_),
                NamedSharding(self.mesh, P(self.axis)))
        from spark_rapids_tpu.config import (SKEW_JOIN_ENABLED,
                                             SKEW_JOIN_FACTOR,
                                             SKEW_JOIN_MIN_ROWS, get_conf)

        conf = get_conf()
        skew_on = conf.get(SKEW_JOIN_ENABLED) and jt not in (
            JoinType.LEFT_SEMI, JoinType.LEFT_ANTI)
        skew_factor = conf.get(SKEW_JOIN_FACTOR)
        skew_min_rows = conf.get(SKEW_JOIN_MIN_ROWS)
        self.skew_splits = 0     # plan-visible evidence for tests/metrics

        # epochs are processed through an explicit stack so a skewed epoch
        # can SPLIT: when one device's matched total exceeds
        # skewedPartitionFactor x the device mean (AQE OptimizeSkewedJoin
        # analog, detected from the per-epoch totals the exec syncs
        # anyway), the epoch halves and re-routes — per-device output
        # capacity stays near the mean instead of the hot key's total
        pending: List[ColumnarBatch] = []

        def refill(epoch):
            pending.append(epoch)

        for epoch0 in self._epochs(self.children[0].execute_columnar()):
            refill(epoch0)
            while pending:
                epoch = pending.pop()
                with self.metrics["opTime"].timed():
                    epoch = self._pad_for_mesh(epoch)
                    ls = self._shard(epoch)
                    pkey = (epoch.capacity,)
                    if pkey not in self._pprobe:
                        self._pprobe[pkey] = self._build_pprobe(l_schema)
                    acc = (matched,) if full else ()
                    t0 = time.perf_counter_ns()
                    res = self._pprobe[pkey](tuple(ls),
                                             jnp.int32(epoch.num_rows),
                                             swords, n_valid, *acc)
                    (rl, lo, counts, unmatched, rl_ok, totals) = res[:6]
                    if full:
                        # OR-ing covered build rows is idempotent, so a
                        # skew re-run of the halves is safe
                        matched = res[6]
                    b_moved = (build_account[0] if build_account
                               else None)
                    # one host sync/epoch
                    totals_np, moved_np, b_moved = PC.sync_get(
                        (totals, res[-1], b_moved))
                    if build_account:
                        account(b_moved, *build_account[1:])
                        build_account = None
                    account(moved_np, _row_bytes(ls),
                            time.perf_counter_ns() - t0)
                    per_dev_rows = totals_np[:, 0] + (
                        totals_np[:, 1]
                        if jt in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER)
                        else 0)
                    if (skew_on
                            and epoch.num_rows > max(skew_min_rows, 1)
                            and per_dev_rows.max() > skew_factor
                            * max(per_dev_rows.mean(), 1.0)):
                        # split depth straight from the measured ratio
                        # (Spark AQE sizes splits from stats the same
                        # way) — a single hot key keeps max/mean
                        # constant under halving, so per-level
                        # re-probing would pay log2(n) wasted probes
                        import math as _math

                        ratio = per_dev_rows.max() / max(
                            per_dev_rows.mean(), 1.0)
                        k = max(1, _math.ceil(
                            _math.log2(ratio / skew_factor)) + 1)
                        parts = min(1 << k, 16, max(
                            epoch.num_rows // max(skew_min_rows, 1), 2))
                        step = -(-epoch.num_rows // parts)
                        self.skew_splits += 1
                        self.metric("skewSplits").add(1)
                        from spark_rapids_tpu.columnar.column import (
                            DEFAULT_ROW_BUCKETS,
                            round_up_bucket,
                        )

                        # bucketed capacities: sub-epochs land on the
                        # standard row-bucket ladder so the probe/p2
                        # programs compiled for those buckets are reused
                        # (arbitrary capacities would each compile fresh,
                        # seconds to minutes per program)
                        cap2 = round_up_bucket(max(step, 1),
                                               DEFAULT_ROW_BUCKETS)
                        for s0 in range(0, epoch.num_rows, step):
                            ln = min(step, epoch.num_rows - s0)
                            sub = epoch.slice_rows(s0, ln)
                            if sub.capacity != cap2:
                                sub = ColumnarBatch(
                                    [c.slice_to(cap2) for c in
                                     sub.columns], sub.num_rows,
                                    sub.schema)
                            pending.append(sub)
                        continue
                    flat = tuple(rl) + tuple(rr)
                    if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
                        out_cap = rl[0].capacity // n_dev
                    else:
                        # pow2 ladder floored at the probe epoch's shard
                        # cap so repeated epochs reuse one program
                        out_cap = max(int(per_dev_rows.max()), 1,
                                      rl[0].capacity // n_dev)
                        out_cap = 1 << (out_cap - 1).bit_length()
                    key2 = (out_cap, epoch.capacity)
                    if key2 not in self._p2:
                        self._p2[key2] = self._build_p2(
                            out_cap, l_schema, r_schema, len(rl))
                    out_cols, out_rows = self._p2[key2](
                        flat, row_index, lo, counts, unmatched, rl_ok,
                        totals)
                    rows_np = np.asarray(out_rows)  # one host sync/epoch
                per_dev_cap = out_cols[0].capacity // n_dev
                for d in range(n_dev):
                    ng = int(rows_np[d])
                    if ng == 0:
                        continue
                    lo_i = d * per_dev_cap
                    cols = [c.gather(jnp.arange(lo_i, lo_i + per_dev_cap))
                            for c in out_cols[:keep_cols]]
                    yield self._emit(cols, ng)
        if build_account:           # no probe epoch read it
            account(*build_account)
        if full:
            with self.metrics["opTime"].timed():
                bcap_local = swords[0].shape[0] // n_dev
                if self._ptail is None:
                    self._ptail = self._build_ptail(bcap_local)
                tail_cols, tail_rows = self._ptail(rr, row_index, matched,
                                                   rr_ok)
                tail_np = np.asarray(tail_rows)  # one host sync
            n_l = len(l_schema.fields)
            per_dev_cap = tail_cols[0].capacity // n_dev
            for d in range(n_dev):
                ng = int(tail_np[d])
                if ng == 0:
                    continue
                lo_i = d * per_dev_cap
                bcols = [c.gather(jnp.arange(lo_i, lo_i + per_dev_cap))
                         for c in tail_cols]
                lcols = self._null_cols(out_schema.fields[:n_l],
                                        per_dev_cap)
                yield self._emit(lcols + list(bcols), ng)

    def _emit(self, cols, ng):
        """Emit one output batch, reordering mirrored RIGHT OUTER columns
        back to the original left-then-right order."""
        if self._mirror_nl is not None:
            nl = self._mirror_nl
            cols = cols[nl:] + cols[:nl]
        if self._emit_sel is not None:
            cols = [cols[i] for i in self._emit_sel]
        return self._count_output(
            ColumnarBatch(list(cols), ng, self._orig_output))


class TpuIciSortExec(TpuExec):
    """Distributed global sort over the mesh — the third ICI stage
    shape: sampled global range bounds, range all-to-all
    exchange, per-device local sorts, ordered emit.

    Reference analog: GpuRangePartitioner (sample-based bounds) +
    GpuShuffleExchangeExec + per-partition GpuSortExec/
    GpuOutOfCoreSortIterator (SURVEY.md §2.4 Sort/Partitioning).

    Epoch-streamed: pass A spills the child's batches and samples their
    sort-key words host-side; global splitters are the sample quantiles
    (fixing r2 weak #3 — bounds are GLOBAL, not per-batch).  Pass B runs
    each epoch through one SPMD program (range-partition by splitter
    searchsorted, all-to-all over ICI, local sort of the received rows),
    emitting one sorted RUN per device per epoch.  Each device's runs then
    stream through the memory-bounded k-way merge the single-chip
    out-of-core sort uses, and devices emit in rank order — a globally
    ordered stream with per-device peak memory ~ one epoch shard + the
    merge windows."""

    SAMPLES_PER_EPOCH = 512

    def __init__(self, sort, mesh, axis: str = "dp",
                 epoch_bytes: int = 1 << 28):
        super().__init__(list(sort.children))
        self.sort = sort            # single-chip TpuSortExec (reused)
        self.orders = sort.orders
        self.mesh = mesh
        self.axis = axis
        self.epoch_bytes = epoch_bytes
        self._key_fns = {}
        self._part_programs = {}

    @property
    def output(self):
        return self.sort.output

    def describe(self):
        n = self.mesh.devices.size
        return f"TpuIciSort[{n}dev] [{self.sort.describe()}]"

    # -- key sampling (host-side, word space) ---------------------------
    def _key_fn(self, schema, cap):
        key = cap
        if key not in self._key_fns:
            orders = self.orders
            ansi = self.sort.ansi

            def fn(cols, num_rows):
                from spark_rapids_tpu.expr.base import EvalContext
                from spark_rapids_tpu.ops.sortkeys import pack_sort_keys

                batch = ColumnarBatch(list(cols), num_rows, schema)
                ctx = EvalContext(batch, ansi=ansi)
                key_cols = [e.eval_tpu(ctx) for e, _ in orders]
                specs = [s for _, s in orders]
                return tuple(pack_sort_keys(key_cols, specs,
                                            batch.row_mask))

            self._key_fns[key] = tpu_jit(fn)
        return self._key_fns[key]

    def _sample_words(self, batch: ColumnarBatch):
        n = batch.num_rows
        if n == 0:
            return None
        words = self._key_fn(batch.schema, batch.capacity)(
            tuple(batch.columns), jnp.int32(n))
        stride = max(n // self.SAMPLES_PER_EPOCH, 1)
        idx = np.arange(0, n, stride)
        return np.stack([np.asarray(w)[idx] for w in words])  # (nw, s)

    def _splitters(self, samples, n_dev):
        """(n_dev-1, nwords) int64 splitter matrix from pooled samples."""
        pooled = np.concatenate(samples, axis=1)  # (nw, total)
        nw, total = pooled.shape
        order = np.lexsort(pooled[::-1])
        q = [(total * (d + 1)) // n_dev for d in range(n_dev - 1)]
        picks = order[np.clip(q, 0, total - 1)]
        return pooled[:, picks].T.copy()          # (n_dev-1, nw)

    # -- partition + local-sort program ---------------------------------
    def _build_part_program(self, schema, nwords):
        axis = self.axis
        n_dev = int(self.mesh.devices.size)
        orders = self.orders
        ansi = self.sort.ansi

        def per_device(cols, num_rows, splitters):
            from spark_rapids_tpu.expr.base import EvalContext
            from spark_rapids_tpu.ops.sortkeys import (pack_sort_keys,
                                                       sort_permutation)
            from spark_rapids_tpu.parallel.mesh import (
                ici_all_to_all_columns, off_chip_rows)

            local_cap = cols[0].capacity
            idx = jax.lax.axis_index(axis)
            nloc = jnp.clip(num_rows - idx.astype(jnp.int32) * local_cap,
                            0, local_cap)
            rows = jnp.arange(local_cap) < nloc
            batch = ColumnarBatch(list(cols), nloc, schema)
            ctx = EvalContext(batch, ansi=ansi)
            key_cols = [e.eval_tpu(ctx) for e, _ in orders]
            specs = [s for _, s in orders]
            words = pack_sort_keys(key_cols, specs, rows)
            # target device = count of splitters <= key (lexicographic)
            tgt = jnp.zeros(local_cap, jnp.int32)
            for d in range(n_dev - 1):
                le = jnp.zeros(local_cap, jnp.bool_)
                eq = jnp.ones(local_cap, jnp.bool_)
                for wi, w in enumerate(words):
                    b = splitters[d, wi]
                    le = le | (eq & (b < w))
                    eq = eq & (b == w)
                tgt = tgt + (le | eq).astype(jnp.int32)
            rcols, rok = ici_all_to_all_columns(list(cols), rows, tgt,
                                                n_dev, axis)
            rbatch = ColumnarBatch(list(rcols), jnp.int32(rcols[0].capacity),
                                   schema)
            rctx = EvalContext(rbatch, ansi=ansi)
            rkeys = [e.eval_tpu(rctx) for e, _ in orders]
            perm = sort_permutation(rkeys, specs, rok)
            out = []
            for c in rcols:
                out.append(c.gather(perm))
            cnt = jnp.sum(rok.astype(jnp.int32))
            return (tuple(out), cnt.reshape(1),
                    off_chip_rows(rows, tgt, axis))

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(axis), P(), P()),
            out_specs=(P(axis), P(axis), P(axis)),
            check_vma=False)

    # -- execution ------------------------------------------------------
    def _spill_epochs(self, spillables):
        """Epoch bucketing over spill HANDLES (the sort retains its input
        as spillables for the second pass, unlike agg/join)."""
        pending, size = [], 0
        for s in spillables:
            pending.append(s)
            size += s.device_bytes
            if size >= self.epoch_bytes:
                yield pending
                pending, size = [], 0
        if pending:
            yield pending

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.spill import get_spill_framework

        fw = get_spill_framework()
        n_dev = int(self.mesh.devices.size)
        schema = self.children[0].output
        spillables = []
        samples = []
        # pass A: spill + sample
        for b in self.children[0].execute_columnar():
            if b.num_rows == 0:
                continue
            sw = self._sample_words(b)
            if sw is not None:
                samples.append(sw)
            spillables.append(fw.track(b))
        if not spillables:
            return
        with self.metrics["opTime"].timed():
            splitters = jnp.asarray(self._splitters(samples, n_dev))
            runs = [[] for _ in range(n_dev)]
            for group in self._spill_epochs(spillables):
                for s in group:
                    s.pin()
                try:
                    batches = [s.get_batch() for s in group]
                    batch = (batches[0] if len(batches) == 1
                             else ColumnarBatch.concat(batches))
                finally:
                    for s in group:
                        s.unpin()
                for s in group:
                    s.close()
                cap = batch.capacity
                if cap % n_dev or cap < n_dev:
                    batch = ColumnarBatch(
                        [c.slice_to(-(-cap // n_dev) * n_dev)
                         for c in batch.columns], batch.num_rows, schema)
                sharded = self._shard(batch)
                pkey = (batch.capacity, splitters.shape[0])
                if pkey not in self._part_programs:
                    self._part_programs[pkey] = self._build_part_program(
                        schema, splitters.shape[1])
                t0 = time.perf_counter_ns()
                out_cols, cnts, moved = self._part_programs[pkey](
                    tuple(sharded), jnp.int32(batch.num_rows), splitters)
                # one host sync per epoch
                cnts_np, moved_np = PC.sync_get((cnts, moved))
                moved = int(moved_np.sum())
                _ici_account(self.node_name, n_dev, moved,
                             moved * _row_bytes(sharded),
                             time.perf_counter_ns() - t0)
                per_dev_cap = out_cols[0].capacity // n_dev
                for d in range(n_dev):
                    nrows = int(cnts_np[d])
                    if nrows == 0:
                        continue
                    lo = d * per_dev_cap
                    idxs = jnp.arange(lo, lo + per_dev_cap)
                    cols = [c.gather(idxs) for c in out_cols]
                    runs[d].append(
                        [fw.track(ColumnarBatch(cols, nrows, schema)),
                         nrows, 0])
        # ordered emit: device 0's runs first, then device 1, ...
        for d in range(n_dev):
            if not runs[d]:
                continue
            if len(runs[d]) == 1:
                s = runs[d][0][0]
                s.pin()
                try:
                    yield self._count_output(s.get_batch())
                finally:
                    s.unpin()
                s.close()
                continue
            yield from (self._count_output(b)
                        for b in self.sort._merge_runs(runs[d], schema))

    def _shard(self, batch: ColumnarBatch):
        def put(arr):
            if arr is None:
                return None
            return jax.device_put(
                arr, NamedSharding(self.mesh, P(self.axis)))

        return [DeviceColumn(c.dtype, put(c.validity), data=put(c.data),
                             chars=put(c.chars), lengths=put(c.lengths),
                             elem_valid=put(c.elem_valid))
                for c in batch.columns]


def _build_exchange_epoch_program(mesh, axis: str, tgt_of):
    """Shared SPMD exchange program for the window/repartition stages:
    local rows -> target device ids (``tgt_of``) -> all-to-all over ICI ->
    prefix compaction.  Returns per-device (received cols, count)."""
    n_dev = int(mesh.devices.size)

    def per_device(cols, num_rows):
        from spark_rapids_tpu.ops.filterops import compact_columns
        from spark_rapids_tpu.parallel.mesh import (ici_all_to_all_columns,
                                                    off_chip_rows)

        local_cap = cols[0].capacity
        idx = jax.lax.axis_index(axis)
        nloc = jnp.clip(num_rows - idx.astype(jnp.int32) * local_cap,
                        0, local_cap)
        rows = jnp.arange(local_cap) < nloc
        tgt = tgt_of(cols, nloc, idx, local_cap)
        rcols, rok = ici_all_to_all_columns(list(cols), rows, tgt,
                                            n_dev, axis)
        out, cnt = compact_columns(rok, rcols)
        return (tuple(out), cnt.astype(jnp.int32).reshape(1),
                off_chip_rows(rows, tgt, axis))

    return _mesh_program(
        per_device, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis)),
        check_vma=False)


def _build_cross_slice_program(mesh, tgt_of):
    """Two-level (host x ici) exchange program: partition ids from
    ``tgt_of`` route hierarchically — intra-slice ICI hop to the local
    device index, then ONE hop per row across the host (DCN-analog)
    axis (parallel/crossslice.py's protocol, generalized to whole
    batches)."""
    n_host = int(mesh.shape["host"])
    n_ici = int(mesh.shape["ici"])

    def per_device(cols, num_rows):
        from spark_rapids_tpu.ops.filterops import compact_columns
        from spark_rapids_tpu.parallel.crossslice import (
            cross_slice_all_to_all_columns,
        )

        local_cap = cols[0].capacity
        hi = jax.lax.axis_index("host")
        ii = jax.lax.axis_index("ici")
        idx = (hi * n_ici + ii).astype(jnp.int32)
        nloc = jnp.clip(num_rows - idx * local_cap, 0, local_cap)
        rows = jnp.arange(local_cap) < nloc
        pid = tgt_of(cols, nloc, idx, local_cap)
        rcols, rok = cross_slice_all_to_all_columns(
            list(cols), rows, pid, n_host, n_ici)
        out, cnt = compact_columns(rok, list(rcols))
        moved = jnp.sum((rows & (pid != idx)).astype(jnp.int32))
        return (tuple(out), cnt.astype(jnp.int32).reshape(1),
                moved.reshape(1))

    return _mesh_program(
        per_device, mesh=mesh,
        in_specs=(P(("host", "ici")), P()),
        out_specs=(P(("host", "ici")),) * 3,
        check_vma=False)


def mesh_exchange_schema_supported(schema) -> bool:
    """The generic exchange stages ride _concat_cols/_fit_cols, which
    handle flat and plain-string layouts; nested columns keep the host
    path (the rewrites check this before claiming a stage)."""
    return not any(
        isinstance(f.dataType, (T.ArrayType, T.MapType, T.StructType))
        for f in schema.fields)


class _IciExchangeStageBase(TpuExec):
    """Shared epoch driver for the exchange-shaped ICI stages (window /
    generic repartition): pad to the mesh, shard, run the exchange
    program, sync received counts, re-bucket the compacted block."""

    def __init__(self, children, mesh, axis: str, epoch_bytes: int):
        super().__init__(children)
        self.mesh = mesh
        self.axis = axis
        self.epoch_bytes = epoch_bytes
        self._pex = {}

    def _tgt_of(self):
        raise NotImplementedError

    def _build_program(self):
        """The per-capacity SPMD exchange program; subclasses with a
        different routing topology (cross-slice) override."""
        return _build_exchange_epoch_program(self.mesh, self.axis,
                                             self._tgt_of())

    def _run_exchange_epoch(self, epoch: ColumnarBatch):
        n_dev = int(self.mesh.devices.size)
        epoch = _ceil_to_mesh(epoch, n_dev)
        sharded = _shard_cols(epoch, self.mesh, self.axis)
        pkey = epoch.capacity
        if pkey not in self._pex:
            self._pex[pkey] = self._build_program()
        t0 = time.perf_counter_ns()
        rcols, cnts, moved = self._pex[pkey](tuple(sharded),
                                             jnp.int32(epoch.num_rows))
        # one host sync per epoch
        cnts_np, moved_np = PC.sync_get((cnts, moved))
        cnts_np = cnts_np.reshape(-1)
        moved = int(moved_np.sum())
        _ici_account(self.node_name, n_dev, moved,
                     moved * _row_bytes(sharded), time.perf_counter_ns() - t0)
        per_dev_cap = rcols[0].capacity // n_dev
        need = max(int(cnts_np.max()), 1)
        blk_cap = min(1 << (need - 1).bit_length(), per_dev_cap)
        block = (_rebucket_sharded(rcols, per_dev_cap, blk_cap, n_dev,
                                   self.mesh, self.axis)
                 if blk_cap != per_dev_cap else list(rcols))
        return block, blk_cap, cnts_np

    def _cnt_dev(self, cnts_np):
        return jax.device_put(
            np.asarray(cnts_np, np.int32).reshape(-1),
            NamedSharding(self.mesh, P(self.axis)))

    def _emit_per_device(self, cols, cnts_np, schema):
        n_dev = int(self.mesh.devices.size)
        per_dev_cap = cols[0].capacity // n_dev
        for d in range(n_dev):
            ng = int(cnts_np[d])
            if ng == 0:
                continue
            lo = d * per_dev_cap
            out = [c.gather(jnp.arange(lo, lo + per_dev_cap))
                   for c in cols]
            yield self._count_output(ColumnarBatch(out, ng, schema))


class TpuIciWindowExec(_IciExchangeStageBase):
    """Distributed partitioned window over the mesh — the fourth ICI stage
    shape: hash all-to-all on the PARTITION BY keys
    co-locates every window partition on one device, then the unchanged
    single-chip window program (exec/window.TpuWindowExec._window_fn) runs
    per device inside shard_map.

    Reference analog: GpuWindowExec downstream of a hash-partitioned
    GpuShuffleExchangeExec (SURVEY.md §2.4 Window, §5.8): the reference
    relies on the exchange for partition co-location; on TPU the exchange
    IS the collective step of this exec.

    Epoch-streamed: each epoch runs one SPMD exchange program, the
    compacted block re-buckets to the smallest pow2 per-device capacity,
    and blocks fold into one device-resident accumulator; the window
    program runs once after the last epoch.  Programs: 1 exchange +
    [1 fold] per epoch + 1 window; one host sync per epoch."""

    def __init__(self, window, mesh, axis: str = "dp",
                 epoch_bytes: int = 1 << 28):
        super().__init__(list(window.children), mesh, axis, epoch_bytes)
        self.window = window            # single-chip TpuWindowExec (reused)
        self._pfold = {}
        self._pwin = {}

    @property
    def output(self):
        return self.window.output

    def describe(self):
        n = self.mesh.devices.size
        return f"TpuIciWindow[{n}dev] [{self.window.describe()}]"

    def _tgt_of(self):
        window = self.window
        n_dev = int(self.mesh.devices.size)
        schema = self.children[0].output

        def tgt(cols, nloc, idx, local_cap):
            from spark_rapids_tpu.expr.base import EvalContext
            from spark_rapids_tpu.ops.hashing import spark_partition_ids

            batch = ColumnarBatch(list(cols), nloc, schema)
            ctx = EvalContext(batch, ansi=window.ansi)
            pcols = [e.eval_tpu(ctx) for e in window.partition_by]
            return spark_partition_ids(pcols, n_dev)

        return tgt

    # ------------------------------------------------------------------
    def _build_fold_program(self, acc_cap: int, blk_cap: int, out_cap: int):
        """Concat the accumulator's and the new block's per-device valid
        prefixes into one prefix-compacted accumulator of out_cap rows."""
        axis = self.axis

        def per_device(acc_cols, acc_cnt, blk_cols, blk_cnt):
            from spark_rapids_tpu.ops.filterops import compact_columns

            rows_a = jnp.arange(acc_cap, dtype=jnp.int32) < acc_cnt[0]
            rows_b = jnp.arange(blk_cap, dtype=jnp.int32) < blk_cnt[0]
            cat = [_concat_cols(a, b)
                   for a, b in zip(acc_cols, blk_cols)]
            keep = jnp.concatenate([rows_a, rows_b])
            out, _cnt = compact_columns(keep, cat)
            return _fit_cols(out, out_cap)

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(axis),) * 4,
            out_specs=P(axis),
            check_vma=False)

    def _build_window_program(self, acc_cap: int):
        axis = self.axis
        window = self.window

        def per_device(cols, cnt):
            return tuple(window._window_fn(tuple(cols), cnt[0]))

        return _mesh_program(
            per_device, mesh=self.mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False)

    # ------------------------------------------------------------------
    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        n_dev = int(self.mesh.devices.size)
        acc = None
        acc_cnts = None
        for epoch in _epoch_batches(self.children[0].execute_columnar(),
                                    self.epoch_bytes):
            # per-epoch timing only: the child's execution must not be
            # charged to this stage's opTime
            with self.metrics["opTime"].timed():
                block, blk_cap, cnts_np = self._run_exchange_epoch(epoch)
                if acc is None:
                    acc, acc_cnts = block, cnts_np
                    continue
                acc_cap = acc[0].capacity // n_dev
                tot = acc_cnts + cnts_np
                need = max(int(tot.max()), 1)
                out_cap = min(1 << (need - 1).bit_length(),
                              acc_cap + blk_cap)
                fkey = (acc_cap, blk_cap, out_cap)
                if fkey not in self._pfold:
                    self._pfold[fkey] = self._build_fold_program(
                        acc_cap, blk_cap, out_cap)
                acc = list(self._pfold[fkey](
                    tuple(acc), self._cnt_dev(acc_cnts),
                    tuple(block), self._cnt_dev(cnts_np)))
                acc_cnts = tot
        if acc is None:
            return
        with self.metrics["opTime"].timed():
            acc_cap = acc[0].capacity // n_dev
            if acc_cap not in self._pwin:
                self._pwin[acc_cap] = self._build_window_program(acc_cap)
            out_cols = self._pwin[acc_cap](tuple(acc),
                                           self._cnt_dev(acc_cnts))
        yield from self._emit_per_device(out_cols, acc_cnts,
                                         self.window.output)


class TpuIciRepartitionExec(_IciExchangeStageBase):
    """Generic mesh repartition — the fifth ICI stage shape: ANY
    hash/round-robin shuffle exchange lowers to one SPMD
    all-to-all program per epoch, so exchanges that no specialized ICI
    stage claims still execute on the mesh instead of the host loop.

    Reference analog: GpuShuffleExchangeExec + RapidsShuffleManager
    (SURVEY.md §2.7) — the generic exchange every plan shape rides.

    Per epoch: partition ids (murmur3 pmod for hash, cycling offset for
    round-robin) -> all-to-all -> compact -> re-bucket -> emit one batch
    per device.  Downstream single-chip operators consume the emitted
    batches exactly as they would the host shuffle's partitions."""

    def __init__(self, exchange, mesh, axis: str = "dp",
                 epoch_bytes: int = 1 << 28, cross_hosts: int = 0):
        self.cross_hosts = 0
        n_dev = int(mesh.devices.size)
        if cross_hosts > 1 and n_dev % cross_hosts == 0 \
                and n_dev // cross_hosts >= 1:
            # two-level (host x ici) routing: rebuild the SAME devices
            # as the hierarchical mesh; the outer axis models the
            # slice-to-slice fabric (parallel/crossslice.py)
            from spark_rapids_tpu.parallel.crossslice import make_mesh2

            mesh = make_mesh2(cross_hosts, n_dev // cross_hosts,
                              devices=list(mesh.devices.reshape(-1)))
            axis = ("host", "ici")
            self.cross_hosts = cross_hosts
        super().__init__(list(exchange.children), mesh, axis, epoch_bytes)
        self.exchange = exchange
        self.partitioning = exchange.partitioning

    def _build_program(self):
        if self.cross_hosts:
            return _build_cross_slice_program(self.mesh, self._tgt_of())
        return super()._build_program()

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        n = self.mesh.devices.size
        lvl = (f" cross_slice={self.cross_hosts}x"
               f"{n // self.cross_hosts}" if self.cross_hosts else "")
        return (f"TpuIciRepartition[{n}dev{lvl}] "
                f"{self.partitioning.describe()}")

    def _tgt_of(self):
        from spark_rapids_tpu.plan.nodes import HashPartitioning

        part = self.partitioning
        n_dev = int(self.mesh.devices.size)
        schema = self.children[0].output
        ansi = getattr(self.exchange, "ansi", False)

        if isinstance(part, HashPartitioning):
            def tgt(cols, nloc, idx, local_cap):
                from spark_rapids_tpu.expr.base import EvalContext
                from spark_rapids_tpu.ops.hashing import spark_partition_ids

                batch = ColumnarBatch(list(cols), nloc, schema)
                ctx = EvalContext(batch, ansi=ansi)
                kcols = [e.eval_tpu(ctx) for e in part.keys]
                return spark_partition_ids(kcols, n_dev)
        else:
            def tgt(cols, nloc, idx, local_cap):
                return ((jnp.arange(local_cap, dtype=jnp.int32)
                         + idx.astype(jnp.int32)) % n_dev)

        return tgt

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        for epoch in _epoch_batches(self.children[0].execute_columnar(),
                                    self.epoch_bytes):
            with self.metrics["opTime"].timed():
                block, blk_cap, cnts_np = self._run_exchange_epoch(epoch)
            yield from self._emit_per_device(block, cnts_np, self.output)
