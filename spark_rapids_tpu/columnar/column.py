"""Device/host column vectors — the TPU answer to GpuColumnVector.

Reference analog: sql-plugin/src/main/java/com/nvidia/spark/rapids/
GpuColumnVector.java and RapidsHostColumnVector.java, which wrap cuDF device
columns (data + validity bitmask + offsets) as Spark ColumnVectors.

TPU-first design decisions (NOT a translation of the cuDF layout):

* **Padded capacities.** XLA compiles per shape.  Every column is padded to a
  row-capacity bucket (pow2 ladder, ``DEFAULT_ROW_BUCKETS`` below) so
  a query sees a handful of compiled programs, not one per batch size.  The
  logical row count rides alongside (host int) and as a device scalar inside
  fused programs; rows past ``num_rows`` are garbage and masked off.

* **Validity as bool vector, not bitmask.**  cuDF packs validity 1 bit/row
  because PCIe bytes are precious; on TPU the VPU operates on 8x128 lanes of
  bytes and XLA fuses the mask reads into consumers, so a bool vector is both
  faster and simpler.

* **Strings as length-bucketed padded char matrices.**  cuDF stores
  (chars, offsets); offset-indirection defeats XLA's static-shape tiling, so
  strings here are a ``(capacity, width)`` uint8 matrix plus an int32 length
  vector, with ``width`` drawn from a bucket ladder
  (``DEFAULT_WIDTH_BUCKETS`` below).  Lexicographic compare, hash,
  substring etc. become dense vector ops.  Memory overhead is bounded by the
  ladder and by width re-bucketing at coalesce time.

* **Decimals** are unscaled int64 (precision<=18); decimal128 is a two-limb
  (hi int64, lo uint-as-int64) pair — see expr/decimal128.py.

Columns are registered as JAX pytrees so whole-stage-fused programs take and
return them directly.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T

DEFAULT_ROW_BUCKETS = (1024, 8192, 65536, 262144, 1048576, 4194304)
DEFAULT_WIDTH_BUCKETS = (8, 32, 128, 512, 2048)


def _np_tree_bytes(tree) -> int:
    """Total numpy bytes across a pytree's array leaves (the actual
    transfer size of a padded host column set)."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "nbytes"))


def round_up_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    # beyond the ladder: next pow2
    p = 1
    while p < n:
        p <<= 1
    return p


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceColumn:
    """One column resident in TPU HBM.

    kind "flat": data (capacity,) of storage dtype; chars/lengths None.
    kind "string": chars (capacity, width) uint8; lengths (capacity,) int32;
                   data is None.
    kind "array":  data (capacity, ewidth) of element storage dtype;
                   elem_valid (capacity, ewidth) bool; lengths (capacity,)
                   int32 — a padded list-column (primitive elements), the
                   TPU answer to cuDF LIST columns (offsets + child).
    kind "struct": children = tuple of full child DeviceColumns (one per
                   struct field) — cuDF STRUCT columns are likewise a
                   validity mask over recursively stored children.
    kind "string_array": chars (capacity, ewidth, width) uint8;
                   data (capacity, ewidth) int32 holds PER-ELEMENT byte
                   lengths; lengths (capacity,) element counts;
                   elem_valid (capacity, ewidth) — array<string> as a 3-D
                   padded char tensor (cuDF: LIST of STRING offsets).
    validity: (capacity,) bool; True = valid (non-null).
    """

    dtype: T.DataType
    validity: jax.Array
    data: Optional[jax.Array] = None
    chars: Optional[jax.Array] = None
    lengths: Optional[jax.Array] = None
    elem_valid: Optional[jax.Array] = None
    children: Optional[tuple] = None  # tuple of DeviceColumn (structs)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.validity, self.data, self.chars, self.lengths,
                    self.elem_valid, self.children)
        return children, self.dtype

    @classmethod
    def tree_unflatten(cls, aux, children):
        validity, data, chars, lengths, elem_valid, kids = children
        return cls(dtype=aux, validity=validity, data=data, chars=chars,
                   lengths=lengths, elem_valid=elem_valid, children=kids)

    # -- properties ---------------------------------------------------------
    @property
    def is_string(self) -> bool:
        return self.chars is not None and self.chars.ndim == 2

    @property
    def is_array(self) -> bool:
        return self.elem_valid is not None and self.chars is None

    @property
    def is_struct(self) -> bool:
        return self.children is not None

    @property
    def is_string_array(self) -> bool:
        return self.chars is not None and self.chars.ndim == 3

    @property
    def is_dec128(self) -> bool:
        """decimal(p>18): data is (capacity, 2) int64 [hi, lo] limbs."""
        return isinstance(self.dtype, T.DecimalType) and self.dtype.is_128

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def width(self) -> int:
        if self.chars is None:
            return 0
        return int(self.chars.shape[-1])

    @property
    def ewidth(self) -> int:
        """Element capacity per row for array columns."""
        if self.is_string_array:
            return int(self.chars.shape[1])
        return int(self.data.shape[1]) if self.is_array else 0

    def nbytes(self) -> int:
        n = self.validity.size  # bool = 1 byte
        if self.data is not None:
            n += self.data.size * self.data.dtype.itemsize
        if self.chars is not None:
            n += self.chars.size + self.lengths.size * 4
        if self.elem_valid is not None:
            n += self.elem_valid.size + self.lengths.size * 4
        if self.children is not None:
            n += sum(c.nbytes() for c in self.children)
        return int(n)

    def gather(self, idx) -> "DeviceColumn":
        """Row gather (works for every column kind)."""
        if self.is_string_array:
            return DeviceColumn(self.dtype, self.validity[idx],
                                chars=self.chars[idx], data=self.data[idx],
                                lengths=self.lengths[idx],
                                elem_valid=self.elem_valid[idx])
        if self.is_string:
            return DeviceColumn(self.dtype, self.validity[idx],
                                chars=self.chars[idx],
                                lengths=self.lengths[idx])
        if self.is_array:
            return DeviceColumn(self.dtype, self.validity[idx],
                                data=self.data[idx],
                                lengths=self.lengths[idx],
                                elem_valid=self.elem_valid[idx])
        if self.is_struct:
            return DeviceColumn(
                self.dtype, self.validity[idx],
                lengths=None if self.lengths is None
                else self.lengths[idx],
                children=tuple(c.gather(idx) for c in self.children))
        return DeviceColumn(self.dtype, self.validity[idx],
                            data=self.data[idx])

    # -- constructors -------------------------------------------------------
    @staticmethod
    def _padded_host(h: "HostColumn", capacity: Optional[int] = None,
                     width_buckets: Sequence[int] = DEFAULT_WIDTH_BUCKETS,
                     row_buckets: Sequence[int] = DEFAULT_ROW_BUCKETS
                     ) -> "DeviceColumn":
        """Padded column with NUMPY leaves (no transfer yet).

        DeviceColumn is a registered pytree, so the result can be
        device_put as part of a larger structure — that is how
        ``ColumnarBatch.from_host_columns`` folds a whole batch's
        columns into ONE multi-array transfer instead of paying a
        dispatch per buffer per column (ISSUE 6 satellite)."""
        n = h.num_rows
        cap = capacity or round_up_bucket(max(n, 1), row_buckets)
        validity = np.zeros(cap, dtype=np.bool_)
        validity[:n] = h.validity[:n]
        if h.is_string_array:
            ew = h.chars.shape[1]
            w = h.chars.shape[2]
            chars = np.zeros((cap, max(ew, 1), max(w, 1)), np.uint8)
            chars[:n, :ew, :w] = h.chars[:n]
            elens = np.zeros((cap, max(ew, 1)), np.int32)
            elens[:n, :ew] = h.data[:n]
            ev = np.zeros((cap, max(ew, 1)), np.bool_)
            ev[:n, :ew] = h.elem_valid[:n]
            lengths = np.zeros(cap, np.int32)
            lengths[:n] = h.lengths[:n]
            return DeviceColumn(dtype=h.dtype, validity=validity,
                                chars=chars, data=elens, lengths=lengths,
                                elem_valid=ev)
        if h.is_string:
            max_len = int(h.lengths[:n].max()) if n else 0
            width = round_up_bucket(max(max_len, 1), width_buckets)
            chars = np.zeros((cap, width), dtype=np.uint8)
            chars[:n, : h.chars.shape[1]] = h.chars[:n, :min(width, h.chars.shape[1])]
            lengths = np.zeros(cap, dtype=np.int32)
            lengths[:n] = h.lengths[:n]
            return DeviceColumn(dtype=h.dtype, validity=validity,
                                chars=chars, lengths=lengths)
        if h.is_array:
            max_len = int(h.lengths[:n].max()) if n else 0
            width = round_up_bucket(max(max_len, 1), width_buckets)
            data = np.zeros((cap, width), dtype=h.data.dtype)
            ev = np.zeros((cap, width), dtype=np.bool_)
            w0 = min(width, h.data.shape[1])
            data[:n, :w0] = h.data[:n, :w0]
            ev[:n, :w0] = h.elem_valid[:n, :w0]
            lengths = np.zeros(cap, dtype=np.int32)
            lengths[:n] = h.lengths[:n]
            return DeviceColumn(dtype=h.dtype, validity=validity,
                                data=data, lengths=lengths, elem_valid=ev)
        if h.is_struct:
            kids = tuple(DeviceColumn._padded_host(
                c, capacity=cap, width_buckets=width_buckets,
                row_buckets=row_buckets) for c in h.children)
            lengths = None
            if h.lengths is not None:      # entries layout (array<struct>)
                lengths = np.zeros(cap, np.int32)
                lengths[:n] = h.lengths[:n]
            return DeviceColumn(dtype=h.dtype, validity=validity,
                                lengths=lengths, children=kids)
        data = np.zeros((cap,) + h.data.shape[1:], dtype=h.data.dtype)
        data[:n] = h.data[:n]
        return DeviceColumn(dtype=h.dtype, validity=validity, data=data)

    @staticmethod
    def from_host(h: "HostColumn", capacity: Optional[int] = None,
                  width_buckets: Sequence[int] = DEFAULT_WIDTH_BUCKETS,
                  row_buckets: Sequence[int] = DEFAULT_ROW_BUCKETS) -> "DeviceColumn":
        import jax as _jax

        from spark_rapids_tpu.perfcounters import count_h2d

        padded = DeviceColumn._padded_host(h, capacity, width_buckets,
                                           row_buckets)
        # bytes_h2d counts what actually crosses the link (the PADDED
        # buffers); the useful decoded size rides in bytes_h2d_logical
        count_h2d(_np_tree_bytes(padded), logical=h.nbytes())
        return _jax.device_put(padded)

    def to_host(self, num_rows: int) -> "HostColumn":
        validity = np.asarray(self.validity)[:num_rows]
        if self.is_string_array:
            return HostColumn(dtype=self.dtype, validity=validity,
                              chars=np.asarray(self.chars)[:num_rows],
                              data=np.asarray(self.data)[:num_rows],
                              lengths=np.asarray(self.lengths)[:num_rows],
                              elem_valid=np.asarray(
                                  self.elem_valid)[:num_rows])
        if self.is_string:
            return HostColumn(dtype=self.dtype, validity=validity,
                              chars=np.asarray(self.chars)[:num_rows],
                              lengths=np.asarray(self.lengths)[:num_rows])
        if self.is_array:
            return HostColumn(dtype=self.dtype, validity=validity,
                              data=np.asarray(self.data)[:num_rows],
                              lengths=np.asarray(self.lengths)[:num_rows],
                              elem_valid=np.asarray(self.elem_valid)[:num_rows])
        if self.is_struct:
            # entries layout (array<struct>): ArrayType with per-field
            # array-column children sharing ``lengths``
            return HostColumn(
                dtype=self.dtype, validity=validity,
                lengths=None if self.lengths is None
                else np.asarray(self.lengths)[:num_rows],
                children=[c.to_host(num_rows) for c in self.children])
        return HostColumn(dtype=self.dtype, validity=validity,
                          data=np.asarray(self.data)[:num_rows])

    def slice_to(self, capacity: int) -> "DeviceColumn":
        """Re-pad (grow or shrink capacity); keeps device residency."""
        if capacity == self.capacity:
            return self
        if capacity < self.capacity:
            if self.is_string_array:
                return DeviceColumn(self.dtype, self.validity[:capacity],
                                    chars=self.chars[:capacity],
                                    data=self.data[:capacity],
                                    lengths=self.lengths[:capacity],
                                    elem_valid=self.elem_valid[:capacity])
            if self.is_string:
                return DeviceColumn(self.dtype, self.validity[:capacity],
                                    chars=self.chars[:capacity],
                                    lengths=self.lengths[:capacity])
            if self.is_array:
                return DeviceColumn(self.dtype, self.validity[:capacity],
                                    data=self.data[:capacity],
                                    lengths=self.lengths[:capacity],
                                    elem_valid=self.elem_valid[:capacity])
            if self.is_struct:
                return DeviceColumn(
                    self.dtype, self.validity[:capacity],
                    lengths=None if self.lengths is None
                    else self.lengths[:capacity],
                    children=tuple(c.slice_to(capacity)
                                   for c in self.children))
            return DeviceColumn(self.dtype, self.validity[:capacity],
                                data=self.data[:capacity])
        pad = capacity - self.capacity
        validity = jnp.concatenate([self.validity, jnp.zeros(pad, jnp.bool_)])
        if self.is_string_array:
            return DeviceColumn(
                self.dtype, validity,
                chars=jnp.concatenate(
                    [self.chars,
                     jnp.zeros((pad,) + self.chars.shape[1:], jnp.uint8)]),
                data=jnp.concatenate(
                    [self.data, jnp.zeros((pad, self.ewidth), jnp.int32)]),
                lengths=jnp.concatenate(
                    [self.lengths, jnp.zeros(pad, jnp.int32)]),
                elem_valid=jnp.concatenate(
                    [self.elem_valid,
                     jnp.zeros((pad, self.ewidth), jnp.bool_)]))
        if self.is_string:
            return DeviceColumn(
                self.dtype, validity,
                chars=jnp.concatenate(
                    [self.chars, jnp.zeros((pad, self.width), jnp.uint8)]),
                lengths=jnp.concatenate(
                    [self.lengths, jnp.zeros(pad, jnp.int32)]))
        if self.is_array:
            return DeviceColumn(
                self.dtype, validity,
                data=jnp.concatenate(
                    [self.data,
                     jnp.zeros((pad, self.ewidth), self.data.dtype)]),
                lengths=jnp.concatenate(
                    [self.lengths, jnp.zeros(pad, jnp.int32)]),
                elem_valid=jnp.concatenate(
                    [self.elem_valid,
                     jnp.zeros((pad, self.ewidth), jnp.bool_)]))
        if self.is_struct:
            return DeviceColumn(
                self.dtype, validity,
                lengths=None if self.lengths is None
                else jnp.concatenate(
                    [self.lengths, jnp.zeros(pad, jnp.int32)]),
                children=tuple(c.slice_to(capacity) for c in self.children))
        return DeviceColumn(
            self.dtype, validity,
            data=jnp.concatenate(
                [self.data,
                 jnp.zeros((pad,) + self.data.shape[1:], self.data.dtype)]))


@dataclasses.dataclass
class HostColumn:
    """Host-side column (numpy), the RapidsHostColumnVector analog.

    Also the interchange point with pyarrow and with the CPU oracle.
    """

    dtype: T.DataType
    validity: np.ndarray
    data: Optional[np.ndarray] = None
    chars: Optional[np.ndarray] = None     # (n, width) uint8
    lengths: Optional[np.ndarray] = None   # (n,) int32
    elem_valid: Optional[np.ndarray] = None  # (n, ewidth) bool (arrays)
    children: Optional[List["HostColumn"]] = None  # structs

    def nbytes(self) -> int:
        n = self.validity.nbytes
        for buf in (self.data, self.chars, self.lengths, self.elem_valid):
            if buf is not None:
                n += buf.nbytes
        if self.children is not None:
            n += sum(c.nbytes() for c in self.children)
        return int(n)

    @property
    def is_string(self) -> bool:
        return self.chars is not None and self.chars.ndim == 2

    @property
    def is_array(self) -> bool:
        return self.elem_valid is not None and self.chars is None

    @property
    def is_string_array(self) -> bool:
        return self.chars is not None and self.chars.ndim == 3

    @property
    def is_struct(self) -> bool:
        return self.children is not None

    @property
    def num_rows(self) -> int:
        return int(self.validity.shape[0])

    def slice_rows(self, start: int, end: int) -> "HostColumn":
        """Row range view (all column kinds)."""
        if self.is_string_array:
            return HostColumn(self.dtype, self.validity[start:end],
                              chars=self.chars[start:end],
                              data=self.data[start:end],
                              lengths=self.lengths[start:end],
                              elem_valid=self.elem_valid[start:end])
        if self.is_string:
            return HostColumn(self.dtype, self.validity[start:end],
                              chars=self.chars[start:end],
                              lengths=self.lengths[start:end])
        if self.is_array:
            return HostColumn(self.dtype, self.validity[start:end],
                              data=self.data[start:end],
                              lengths=self.lengths[start:end],
                              elem_valid=self.elem_valid[start:end])
        if self.is_struct:
            return HostColumn(self.dtype, self.validity[start:end],
                              children=[c.slice_rows(start, end)
                                        for c in self.children])
        return HostColumn(self.dtype, self.validity[start:end],
                          data=self.data[start:end])

    # -- python interchange -------------------------------------------------
    @staticmethod
    def from_pylist(values: List, dtype: T.DataType) -> "HostColumn":
        n = len(values)
        validity = np.array([v is not None for v in values], dtype=np.bool_)
        if isinstance(dtype, T.MapType):
            # map rows are python dicts; device layout = (keys array col,
            # values array col) children sharing lengths
            keys = [list(v.keys()) if v is not None else None
                    for v in values]
            vals = [list(v.values()) if v is not None else None
                    for v in values]
            kcol = HostColumn.from_pylist(
                keys, T.ArrayType(dtype.keyType, containsNull=False))
            vcol = HostColumn.from_pylist(
                vals, T.ArrayType(dtype.valueType))
            return HostColumn(dtype, validity, children=[kcol, vcol])
        if isinstance(dtype, T.StructType):
            # rows are dicts (by field name) or sequences (by position);
            # null rows become all-null children (Spark reads null.field
            # as null)
            kids = []
            for fi, f in enumerate(dtype.fields):
                fv = []
                for v in values:
                    if v is None:
                        fv.append(None)
                    elif isinstance(v, dict):
                        fv.append(v.get(f.name))
                    else:
                        fv.append(v[fi])
                kids.append(HostColumn.from_pylist(fv, f.dataType))
            return HostColumn(dtype, validity, children=kids)
        if isinstance(dtype, T.ArrayType) and isinstance(
                dtype.elementType, T.StringType):
            # array<string>: 3-D padded char tensor
            ew = max((len(v) for v in values if v is not None),
                     default=1) or 1
            encoded = [[e.encode("utf-8") if e is not None else None
                        for e in v] if v is not None else None
                       for v in values]
            w = max((len(b) for row in encoded if row is not None
                     for b in row if b is not None), default=1) or 1
            chars = np.zeros((n, ew, w), np.uint8)
            elens = np.zeros((n, ew), np.int32)
            ev = np.zeros((n, ew), np.bool_)
            lengths = np.zeros(n, np.int32)
            for i, row in enumerate(encoded):
                if row is None:
                    continue
                lengths[i] = len(row)
                for j, b in enumerate(row):
                    if b is None:
                        continue
                    ev[i, j] = True
                    elens[i, j] = len(b)
                    chars[i, j, :len(b)] = np.frombuffer(b, np.uint8)
            return HostColumn(dtype, validity, chars=chars, data=elens,
                              lengths=lengths, elem_valid=ev)
        if isinstance(dtype, T.ArrayType) and isinstance(
                dtype.elementType, T.StructType):
            # entries layout: decompose rows of [{f1,f2}|tuple, ...] into
            # one ARRAY child per struct field sharing ``lengths``
            et = dtype.elementType
            lengths = np.zeros(n, np.int32)
            for i, v in enumerate(values):
                if v is not None:
                    lengths[i] = len(v)
            kids = []
            for fi, f in enumerate(et.fields):
                rows = []
                for v in values:
                    if v is None:
                        rows.append(None)
                        continue
                    fr = []
                    for e in v:
                        if e is None:
                            fr.append(None)
                        elif isinstance(e, dict):
                            fr.append(e.get(f.name))
                        else:
                            fr.append(e[fi])
                    rows.append(fr)
                kids.append(HostColumn.from_pylist(
                    rows, T.ArrayType(f.dataType)))
            return HostColumn(dtype, validity, lengths=lengths,
                              children=kids)
        if isinstance(dtype, T.ArrayType):
            elem_host = HostColumn.from_pylist(
                [e for v in values if v is not None for e in v],
                dtype.elementType)
            width = max((len(v) for v in values if v is not None),
                        default=1) or 1
            sdt = elem_host.data.dtype if elem_host.data is not None else None
            if sdt is None:
                raise NotImplementedError(
                    "nested array elements are not supported yet")
            data = np.zeros((n, width), dtype=sdt)
            ev = np.zeros((n, width), np.bool_)
            lengths = np.zeros(n, np.int32)
            pos = 0
            for i, v in enumerate(values):
                if v is None:
                    continue
                ln = len(v)
                lengths[i] = ln
                data[i, :ln] = elem_host.data[pos:pos + ln]
                ev[i, :ln] = elem_host.validity[pos:pos + ln]
                pos += ln
            return HostColumn(dtype, validity, data=data, lengths=lengths,
                              elem_valid=ev)
        if isinstance(dtype, T.StringType):
            encoded = [v.encode("utf-8") if v is not None else b"" for v in values]
            width = max((len(b) for b in encoded), default=1) or 1
            chars = np.zeros((n, width), dtype=np.uint8)
            lengths = np.zeros(n, dtype=np.int32)
            for i, b in enumerate(encoded):
                chars[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
                lengths[i] = len(b)
            return HostColumn(dtype, validity, chars=chars, lengths=lengths)
        sdt = T.storage_dtype(dtype)
        if isinstance(dtype, T.DecimalType) and dtype.is_128:
            from decimal import Decimal

            from spark_rapids_tpu.expr.decimal128 import limbs_of

            data = np.zeros((n, 2), dtype=np.int64)
            for i, v in enumerate(values):
                if v is not None:
                    d = Decimal(str(v)).scaleb(dtype.scale)
                    hi, lo = limbs_of(int(d.to_integral_value()))
                    data[i, 0] = hi
                    data[i, 1] = lo
            return HostColumn(dtype, validity, data=data)
        data = np.zeros(n, dtype=sdt)
        for i, v in enumerate(values):
            if v is not None:
                if isinstance(dtype, T.DecimalType):
                    # accept python Decimal/int/float as scaled value
                    from decimal import Decimal

                    d = Decimal(str(v)).scaleb(dtype.scale)
                    data[i] = int(d.to_integral_value())
                elif isinstance(dtype, T.BooleanType):
                    data[i] = bool(v)
                elif isinstance(dtype, T.DateType):
                    import datetime as _dt

                    data[i] = (v - _dt.date(1970, 1, 1)).days if isinstance(
                        v, _dt.date) else v
                elif isinstance(dtype, T.TimestampType):
                    import datetime as _dt

                    if isinstance(v, _dt.datetime):
                        epoch = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
                        vv = v if v.tzinfo else v.replace(tzinfo=_dt.timezone.utc)
                        data[i] = int((vv - epoch).total_seconds() * 1_000_000)
                    else:
                        data[i] = v
                else:
                    data[i] = v
        return HostColumn(dtype, validity, data=data)

    def to_pylist(self) -> List:
        if self.is_string_array:
            out = []
            for i in range(self.num_rows):
                if not self.validity[i]:
                    out.append(None)
                    continue
                ln = int(self.lengths[i])
                row = []
                for j in range(ln):
                    if not self.elem_valid[i, j]:
                        row.append(None)
                    else:
                        row.append(bytes(
                            self.chars[i, j, :self.data[i, j]]).decode(
                            "utf-8", "replace"))
                out.append(row)
            return out
        if isinstance(self.dtype, T.MapType):
            keys = self.children[0].to_pylist()
            vals = self.children[1].to_pylist()
            return [dict(zip(keys[i], vals[i])) if self.validity[i]
                    else None for i in range(self.num_rows)]
        if self.is_struct:
            if isinstance(self.dtype, T.ArrayType):
                # entries layout: children are per-field ARRAY columns
                kid_rows = [c.to_pylist() for c in self.children]
                out = []
                for i in range(self.num_rows):
                    if not self.validity[i]:
                        out.append(None)
                        continue
                    ln = int(self.lengths[i])
                    out.append([
                        tuple((kr[i][j] if kr[i] is not None
                               and j < len(kr[i]) else None)
                              for kr in kid_rows)
                        for j in range(ln)])
                return out
            kid_vals = [c.to_pylist() for c in self.children]
            return [tuple(kv[i] for kv in kid_vals) if self.validity[i]
                    else None for i in range(self.num_rows)]
        if self.is_array:
            elem_t = self.dtype.elementType
            out = []
            for i in range(self.num_rows):
                if not self.validity[i]:
                    out.append(None)
                    continue
                ln = int(self.lengths[i])
                row = HostColumn(elem_t, self.elem_valid[i, :ln],
                                 data=self.data[i, :ln])
                out.append(row.to_pylist())
            return out
        out: List = []
        for i in range(self.num_rows):
            if not self.validity[i]:
                out.append(None)
            elif self.is_string:
                ln = int(self.lengths[i])
                out.append(bytes(self.chars[i, :ln]).decode("utf-8", "replace"))
            elif isinstance(self.dtype, T.DecimalType):
                from decimal import Decimal

                if self.dtype.is_128:
                    from spark_rapids_tpu.expr.decimal128 import to_py

                    v = to_py(int(self.data[i, 0]), int(self.data[i, 1]))
                    out.append(Decimal(v).scaleb(-self.dtype.scale))
                else:
                    out.append(
                        Decimal(int(self.data[i])).scaleb(-self.dtype.scale))
            elif isinstance(self.dtype, T.BooleanType):
                out.append(bool(self.data[i]))
            elif isinstance(self.dtype, (T.FloatType, T.DoubleType)):
                out.append(float(self.data[i]))
            elif isinstance(self.dtype, T.DateType):
                import datetime as _dt

                out.append(_dt.date(1970, 1, 1) + _dt.timedelta(days=int(self.data[i])))
            elif isinstance(self.dtype, T.TimestampType):
                import datetime as _dt

                out.append(_dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
                           + _dt.timedelta(microseconds=int(self.data[i])))
            else:
                out.append(int(self.data[i]))
        return out

    @staticmethod
    def from_numpy(arr: np.ndarray, dtype: T.DataType,
                   validity: Optional[np.ndarray] = None) -> "HostColumn":
        v = validity if validity is not None else np.ones(len(arr), np.bool_)
        return HostColumn(dtype, v, data=np.ascontiguousarray(arr))

    @staticmethod
    def from_strings(strs: List[Optional[str]]) -> "HostColumn":
        return HostColumn.from_pylist(strs, T.STRING)

    # -- pyarrow interchange (used by the IO layer) -------------------------
    @staticmethod
    def from_arrow(arr, dtype: T.DataType) -> "HostColumn":
        import pyarrow as pa

        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        n = len(arr)
        validity = np.asarray(arr.is_valid())
        if isinstance(dtype, T.StructType):
            kids = [HostColumn.from_arrow(arr.field(f.name), f.dataType)
                    for f in dtype.fields]
            return HostColumn(dtype, validity, children=kids)
        if isinstance(dtype, T.MapType):
            # pyarrow MapArray.to_pylist yields [(k, v), ...] pairs
            rows = arr.to_pylist()
            return HostColumn.from_pylist(
                [dict(v) if v is not None else None for v in rows], dtype)
        if isinstance(dtype, T.ArrayType):
            # list columns come through the python interchange (scan
            # formats with nested data: parquet lists, avro arrays)
            return HostColumn.from_pylist(arr.to_pylist(), dtype)
        if isinstance(dtype, T.StringType):
            arr = arr.cast(pa.large_binary()) if not pa.types.is_large_binary(arr.type) else arr
            buf = np.frombuffer(arr.buffers()[2] or b"", dtype=np.uint8)
            offs = np.frombuffer(arr.buffers()[1], dtype=np.int64)[arr.offset: arr.offset + n + 1]
            lengths = (offs[1:] - offs[:-1]).astype(np.int32)
            width = int(lengths.max()) if n and lengths.size else 1
            width = max(width, 1)
            from spark_rapids_tpu.native import ragged_to_padded

            chars = ragged_to_padded(buf, offs, width)
            return HostColumn(dtype, validity, chars=chars, lengths=lengths)
        sdt = T.storage_dtype(dtype)
        if isinstance(dtype, T.DecimalType):
            # arrow decimal128 storage is 16-byte little-endian (lo, hi)
            arr2 = arr.cast(pa.decimal128(38, dtype.scale)) \
                if arr.type.scale != dtype.scale else arr
            buf = arr2.buffers()[1]
            raw = np.frombuffer(buf, dtype=np.int64)
            lo = raw[0::2][arr2.offset: arr2.offset + n]
            if dtype.is_128:
                hi = raw[1::2][arr2.offset: arr2.offset + n]
                limbs = np.zeros((n, 2), np.int64)
                limbs[:, 0] = np.where(validity, hi, 0)
                limbs[:, 1] = np.where(validity, lo, 0)
                return HostColumn(dtype, validity, data=limbs)
            # precision<=18: the signed low word IS the unscaled value
            np_arr = np.where(validity, lo, 0)
        else:
            if isinstance(dtype, T.TimestampType) and pa.types.is_timestamp(
                    arr.type) and arr.type.unit != "us":
                arr = arr.cast(pa.timestamp("us", tz=arr.type.tz))
            fill = False if pa.types.is_boolean(arr.type) else 0
            np_arr = np.asarray(arr.fill_null(fill)).astype(sdt, copy=False)
        return HostColumn(dtype, validity, data=np_arr)

    def to_arrow(self):
        import pyarrow as pa

        mask = ~self.validity
        if isinstance(self.dtype, T.MapType):
            # dict inference would require string keys; build the MapArray
            # as [(k, v), ...] item lists instead
            rows = self.to_pylist()
            items = [list(d.items()) if d is not None else None
                     for d in rows]
            return pa.array(items, type=pa.map_(
                self.children[0].to_arrow().type.value_type,
                self.children[1].to_arrow().type.value_type))
        if self.is_array:
            return pa.array(self.to_pylist())
        if self.is_struct:
            kid_arrays = [c.to_arrow() for c in self.children]
            fields = [pa.field(f.name, a.type) for f, a in
                      zip(self.dtype.fields, kid_arrays)]
            return pa.StructArray.from_arrays(
                kid_arrays, fields=fields,
                mask=pa.array(mask) if mask.any() else None)
        if self.is_string:
            return pa.array(self.to_pylist(), type=pa.string())
        if isinstance(self.dtype, T.DecimalType):
            from decimal import Decimal

            if self.dtype.is_128:
                from spark_rapids_tpu.expr.decimal128 import to_py

                vals = [Decimal(to_py(int(self.data[i, 0]),
                                      int(self.data[i, 1])))
                        .scaleb(-self.dtype.scale)
                        if self.validity[i] else None
                        for i in range(self.num_rows)]
            else:
                vals = [Decimal(int(self.data[i])).scaleb(-self.dtype.scale)
                        if self.validity[i] else None
                        for i in range(self.num_rows)]
            return pa.array(vals, type=pa.decimal128(
                self.dtype.precision, self.dtype.scale))
        if isinstance(self.dtype, T.DateType):
            return pa.array(np.ma.masked_array(self.data, mask)).cast(pa.date32())
        if isinstance(self.dtype, T.TimestampType):
            return pa.array(np.ma.masked_array(self.data, mask)).cast(
                pa.timestamp("us", tz="UTC"))
        return pa.array(np.ma.masked_array(self.data, mask))
