"""ColumnarBatch — a set of equal-row-count device columns.

Reference analog: Spark's ColumnarBatch holding GpuColumnVectors
(GpuColumnVector.from(Table) etc.).  Batches here carry:

  * columns: DeviceColumn pytrees (padded to a shared row capacity)
  * num_rows: the logical row count (host int — known when the batch is
    materialized; device-resident fused programs carry it as a scalar)
  * schema: StructType naming the columns

Batches are immutable; operators build new ones.  Registered as a pytree so a
whole fused plan-stage can be jitted over batches directly.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import (
    DEFAULT_ROW_BUCKETS,
    DeviceColumn,
    HostColumn,
    round_up_bucket,
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnarBatch:
    columns: List[DeviceColumn]
    num_rows: int
    schema: T.StructType

    def tree_flatten(self):
        return tuple(self.columns), (self.num_rows, self.schema)

    @classmethod
    def tree_unflatten(cls, aux, children):
        num_rows, schema = aux
        return cls(list(children), num_rows, schema)

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def column_by_name(self, name: str) -> DeviceColumn:
        for f, c in zip(self.schema.fields, self.columns):
            if f.name == name:
                return c
        raise KeyError(name)

    @property
    def row_mask(self) -> jax.Array:
        """(capacity,) bool — True for logical rows, False for padding."""
        return jnp.arange(self.capacity) < self.num_rows

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_host_columns(cols: Sequence[HostColumn], names: Sequence[str],
                          row_buckets=DEFAULT_ROW_BUCKETS) -> "ColumnarBatch":
        from spark_rapids_tpu.columnar.column import _np_tree_bytes
        from spark_rapids_tpu.perfcounters import count_h2d

        n = cols[0].num_rows if cols else 0
        cap = round_up_bucket(max(n, 1), row_buckets)
        # pad every column on host, then ONE device_put over the whole
        # column list: per-buffer uploads pay a dispatch round trip per
        # array, and a scan batch has 2-5 buffers per column (ISSUE 6
        # satellite — fold the batch into a single multi-array transfer)
        padded = [DeviceColumn._padded_host(c, capacity=cap)
                  for c in cols]
        count_h2d(_np_tree_bytes(padded),
                  logical=sum(c.nbytes() for c in cols))
        dcols = list(jax.device_put(padded))
        schema = T.StructType(
            [T.StructField(nm, c.dtype) for nm, c in zip(names, cols)])
        return ColumnarBatch(dcols, n, schema)

    @staticmethod
    def from_pydict(data: dict, schema: T.StructType,
                    row_buckets=DEFAULT_ROW_BUCKETS) -> "ColumnarBatch":
        cols = [HostColumn.from_pylist(data[f.name], f.dataType)
                for f in schema.fields]
        return ColumnarBatch.from_host_columns(
            cols, [f.name for f in schema.fields], row_buckets)

    def shrink_to_fit(self) -> "ColumnarBatch":
        """Compact to the row bucket of ``num_rows`` in ONE jitted program.

        A grouped aggregate / window / filter keeps its input's capacity, so
        a 600-group result can sit in 2M-row padded buffers; transferring
        that to host (collect, spill, shuffle wire) pays the full padded
        size.  One extra launch here cuts the transfer by the cap ratio —
        the single biggest lever on a latency/bandwidth-constrained link
."""
        out_cap = round_up_bucket(max(self.num_rows, 1), DEFAULT_ROW_BUCKETS)
        if out_cap >= self.capacity:
            return self
        cols = _shrink_cols(out_cap, tuple(self.columns))
        return ColumnarBatch(list(cols), self.num_rows, self.schema)

    def to_host_columns(
            self, max_shrink_waste_bytes: int = 0) -> List[HostColumn]:
        # one device_get for the whole batch: per-array np.asarray would pay
        # a device round trip PER BUFFER (sync latency dominates small
        # transfers); shrink first so padding never crosses the link
        import jax

        shrunk = self
        out_cap = round_up_bucket(max(self.num_rows, 1), DEFAULT_ROW_BUCKETS)
        if out_cap < self.capacity:
            # shrink elision (docs/whole_plan_fusion.md): the shrink is a
            # whole extra program launch; when the padding it would strip
            # is under the caller's waste budget, transferring the padded
            # buffers is cheaper than compiling + launching the compactor
            # (to_host(n) truncates rows on host either way)
            waste = self.nbytes() * (self.capacity - out_cap) \
                // self.capacity
            if waste <= max_shrink_waste_bytes:
                from spark_rapids_tpu import perfcounters as PC

                PC.bump("collect_shrinks_elided")
            else:
                shrunk = self.shrink_to_fit()
        # DeviceColumn is a pytree, so one device_get fetches every buffer
        # of every column (incl. struct children) in one logical round trip
        from spark_rapids_tpu.perfcounters import sync_get

        host = sync_get(shrunk.columns)
        n = self.num_rows
        return [c.to_host(n) for c in host]

    def to_pydict(self) -> dict:
        host = self.to_host_columns()
        return {f.name: c.to_pylist()
                for f, c in zip(self.schema.fields, host)}

    def to_rows(self) -> List[tuple]:
        cols = [c.to_pylist() for c in self.to_host_columns()]
        return list(zip(*cols)) if cols else [()] * self.num_rows

    def with_columns(self, columns: List[DeviceColumn],
                     schema: Optional[T.StructType] = None,
                     num_rows: Optional[int] = None) -> "ColumnarBatch":
        return ColumnarBatch(columns,
                             self.num_rows if num_rows is None else num_rows,
                             schema or self.schema)

    def select(self, indices: Sequence[int]) -> "ColumnarBatch":
        return ColumnarBatch(
            [self.columns[i] for i in indices], self.num_rows,
            T.StructType([self.schema.fields[i] for i in indices]))

    # -- concat (GpuCoalesceBatches building block) -------------------------
    @staticmethod
    def concat(batches: Sequence["ColumnarBatch"],
               row_buckets=DEFAULT_ROW_BUCKETS) -> "ColumnarBatch":
        """Concatenate batches (same schema) into one padded batch.

        Reference analog: cuDF Table.concatenate used by GpuCoalesceBatches.
        Device-resident: pure jnp ops, no host round-trip.
        """
        assert batches, "concat of zero batches"
        if len(batches) == 1:
            return batches[0]
        total = sum(b.num_rows for b in batches)
        cap = round_up_bucket(max(total, 1), row_buckets)
        schema = batches[0].schema
        ncols = batches[0].num_cols
        rows = [b.num_rows for b in batches]

        def _concat_col(cols: List[DeviceColumn]) -> DeviceColumn:
            dtype = cols[0].dtype
            if cols[0].is_struct:
                validity = jnp.zeros(cap, jnp.bool_)
                lengths = (jnp.zeros(cap, jnp.int32)
                           if cols[0].lengths is not None else None)
                off = 0
                for n, c in zip(rows, cols):
                    if n == 0:
                        continue
                    validity = jax.lax.dynamic_update_slice(
                        validity, c.validity[:n], (off,))
                    if lengths is not None:
                        lengths = jax.lax.dynamic_update_slice(
                            lengths, c.lengths[:n].astype(jnp.int32),
                            (off,))
                    off += n
                kids = tuple(
                    _concat_col([c.children[k] for c in cols])
                    for k in range(len(cols[0].children)))
                return DeviceColumn(dtype, validity, lengths=lengths,
                                    children=kids)
            if cols[0].is_string_array:
                ew = max(c.ewidth for c in cols)
                w = max(c.width for c in cols)
                chars = jnp.zeros((cap, ew, w), jnp.uint8)
                elens = jnp.zeros((cap, ew), jnp.int32)
                ev = jnp.zeros((cap, ew), jnp.bool_)
                lengths = jnp.zeros(cap, jnp.int32)
                validity = jnp.zeros(cap, jnp.bool_)
                off = 0
                for b, c in zip(batches, cols):
                    nn = b.num_rows
                    if nn == 0:
                        continue
                    cpad = jnp.pad(c.chars, ((0, 0), (0, ew - c.ewidth),
                                             (0, w - c.width)))[:nn]
                    chars = jax.lax.dynamic_update_slice(
                        chars, cpad.astype(jnp.uint8), (off, 0, 0))
                    elens = jax.lax.dynamic_update_slice(
                        elens,
                        jnp.pad(c.data, ((0, 0), (0, ew - c.ewidth))
                                )[:nn].astype(jnp.int32), (off, 0))
                    ev = jax.lax.dynamic_update_slice(
                        ev, jnp.pad(c.elem_valid,
                                    ((0, 0), (0, ew - c.ewidth)))[:nn],
                        (off, 0))
                    lengths = jax.lax.dynamic_update_slice(
                        lengths, c.lengths[:nn], (off,))
                    validity = jax.lax.dynamic_update_slice(
                        validity, c.validity[:nn], (off,))
                    off += nn
                return DeviceColumn(dtype, validity, chars=chars,
                                    data=elens, lengths=lengths,
                                    elem_valid=ev)
            if cols[0].is_string:
                width = max(c.width for c in cols)
                chars = jnp.zeros((cap, width), jnp.uint8)
                lengths = jnp.zeros(cap, jnp.int32)
                validity = jnp.zeros(cap, jnp.bool_)
                off = 0
                for b, c in zip(batches, cols):
                    n = b.num_rows
                    if n == 0:
                        continue
                    chars = jax.lax.dynamic_update_slice(
                        chars,
                        jnp.pad(c.chars[:, :],
                                ((0, 0), (0, width - c.width))).astype(jnp.uint8)[:n],
                        (off, 0))
                    lengths = jax.lax.dynamic_update_slice(lengths, c.lengths[:n], (off,))
                    validity = jax.lax.dynamic_update_slice(validity, c.validity[:n], (off,))
                    off += n
                return DeviceColumn(dtype, validity, chars=chars,
                                    lengths=lengths)
            if cols[0].is_array:
                ew = max(c.ewidth for c in cols)
                data = jnp.zeros((cap, ew), cols[0].data.dtype)
                ev = jnp.zeros((cap, ew), jnp.bool_)
                lengths = jnp.zeros(cap, jnp.int32)
                validity = jnp.zeros(cap, jnp.bool_)
                off = 0
                for b, c in zip(batches, cols):
                    n = b.num_rows
                    if n == 0:
                        continue
                    pad = ew - c.ewidth
                    data = jax.lax.dynamic_update_slice(
                        data, jnp.pad(c.data, ((0, 0), (0, pad)))[:n],
                        (off, 0))
                    ev = jax.lax.dynamic_update_slice(
                        ev, jnp.pad(c.elem_valid, ((0, 0), (0, pad)))[:n],
                        (off, 0))
                    lengths = jax.lax.dynamic_update_slice(
                        lengths, c.lengths[:n], (off,))
                    validity = jax.lax.dynamic_update_slice(
                        validity, c.validity[:n], (off,))
                    off += n
                return DeviceColumn(dtype, validity, data=data,
                                    lengths=lengths, elem_valid=ev)
            trail = cols[0].data.shape[1:]
            data = jnp.zeros((cap,) + trail, cols[0].data.dtype)
            validity = jnp.zeros(cap, jnp.bool_)
            off = 0
            for b, c in zip(batches, cols):
                n = b.num_rows
                if n == 0:
                    continue
                data = jax.lax.dynamic_update_slice(
                    data, c.data[:n], (off,) + (0,) * len(trail))
                validity = jax.lax.dynamic_update_slice(validity, c.validity[:n], (off,))
                off += n
            return DeviceColumn(dtype, validity, data=data)

        out_cols = [_concat_col([b.columns[ci] for b in batches])
                    for ci in range(ncols)]
        return ColumnarBatch(out_cols, total, schema)

    def slice_rows(self, start: int, length: int,
                   row_buckets=DEFAULT_ROW_BUCKETS) -> "ColumnarBatch":
        """Host-driven row slice (used by split-and-retry)."""
        cap = round_up_bucket(max(length, 1), row_buckets)

        def _slice_col(c: DeviceColumn) -> DeviceColumn:
            sl = slice(start, start + length)
            if c.is_string_array:
                return DeviceColumn(c.dtype, c.validity[sl],
                                    chars=c.chars[sl], data=c.data[sl],
                                    lengths=c.lengths[sl],
                                    elem_valid=c.elem_valid[sl]).slice_to(cap)
            if c.is_string:
                return DeviceColumn(c.dtype, c.validity[sl], chars=c.chars[sl],
                                    lengths=c.lengths[sl]).slice_to(cap)
            if c.is_array:
                return DeviceColumn(c.dtype, c.validity[sl], data=c.data[sl],
                                    lengths=c.lengths[sl],
                                    elem_valid=c.elem_valid[sl]).slice_to(cap)
            if c.is_struct:
                return DeviceColumn(
                    c.dtype, c.validity[sl],
                    children=tuple(_slice_col(k) for k in c.children)
                ).slice_to(cap)
            return DeviceColumn(c.dtype, c.validity[sl],
                                data=c.data[sl]).slice_to(cap)

        cols = [_slice_col(c) for c in self.columns]
        return ColumnarBatch(cols, length, self.schema)

    def __repr__(self):
        return (f"ColumnarBatch(rows={self.num_rows}, cap={self.capacity}, "
                f"schema={self.schema.simpleString})")


def _shrink_cols(out_cap: int, cols):
    """Slice every buffer of every column to ``out_cap`` leading rows,
    jitted once per (out_cap, batch structure)."""
    from spark_rapids_tpu.perfcounters import tpu_jit

    key = out_cap
    fn = _SHRINK_JITS.get(key)
    if fn is None:
        import functools

        fn = _SHRINK_JITS[key] = tpu_jit(
            functools.partial(_shrink_trace, out_cap))
    return fn(cols)


def _shrink_trace(out_cap: int, cols):
    return jax.tree_util.tree_map(lambda a: a[:out_cap], cols)


_SHRINK_JITS: dict = {}


def empty_batch(schema: T.StructType, capacity: int = 1) -> ColumnarBatch:
    cols = []
    for f in schema.fields:
        if isinstance(f.dataType, T.StringType):
            cols.append(DeviceColumn(f.dataType, jnp.zeros(capacity, jnp.bool_),
                                     chars=jnp.zeros((capacity, 8), jnp.uint8),
                                     lengths=jnp.zeros(capacity, jnp.int32)))
        else:
            sdt = T.storage_dtype(f.dataType)
            shape = ((capacity, 2)
                     if isinstance(f.dataType, T.DecimalType)
                     and f.dataType.is_128 else (capacity,))
            cols.append(DeviceColumn(f.dataType, jnp.zeros(capacity, jnp.bool_),
                                     data=jnp.zeros(shape, sdt)))
    return ColumnarBatch(cols, 0, schema)
