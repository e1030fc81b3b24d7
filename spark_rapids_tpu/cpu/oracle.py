"""The CPU oracle — an independent CPU implementation of plans + expressions.

Role (SURVEY.md §4 "key insight"): the reference's correctness net runs every
query twice — with the plugin on (GPU) and off (CPU Spark) — and asserts
equal results.  Standalone, we have no CPU Spark, so this module *is* the
"CPU Spark": a second, deliberately different implementation —

  * decimals: arbitrary-precision Python ints (vs device int64 unscaled)
  * strings: Python str objects (vs device padded byte matrices)
  * dates/timestamps: Python datetime arithmetic in the handlers
    (vs device civil-calendar bit math)
  * group-by/join: dict-based hashing (vs device lax.sort + segments)

so that agreement between the two paths is meaningful evidence.  It is also
the *fallback executor*: plan nodes tagged willNotWorkOnTpu run here, exactly
as untagged nodes stay on CPU Spark in the reference.
"""
from __future__ import annotations

import dataclasses
import datetime as pydt
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import HostColumn
from spark_rapids_tpu.expr import base as E
from spark_rapids_tpu.expr import arithmetic as A
from spark_rapids_tpu.expr import cast as C
from spark_rapids_tpu.expr import conditional as CO
from spark_rapids_tpu.expr import datetime as DT
from spark_rapids_tpu.expr import mathfuncs as M
from spark_rapids_tpu.expr import predicates as P
from spark_rapids_tpu.expr import strings as S
from spark_rapids_tpu.plan import nodes as PN


@dataclasses.dataclass
class CpuCol:
    """values: object ndarray for string/decimal; typed ndarray otherwise.
    validity: bool ndarray."""

    dtype: T.DataType
    values: np.ndarray
    validity: np.ndarray

    @property
    def n(self):
        return len(self.validity)

    @staticmethod
    def from_objs(objs, dt: T.DataType) -> "CpuCol":
        """Build from python objects in STORAGE representation (None = null)."""
        n = len(objs)
        validity = np.array([o is not None for o in objs], np.bool_)
        if isinstance(dt, (T.StringType, T.DecimalType, T.ArrayType,
                           T.StructType, T.MapType)):
            vals = np.empty(n, object)
            for i, o in enumerate(objs):
                vals[i] = o
            return CpuCol(dt, vals, validity)
        data = np.zeros(n, T.storage_dtype(dt))
        for i, o in enumerate(objs):
            if o is not None:
                data[i] = o
        return CpuCol(dt, data, validity)

    @staticmethod
    def from_host(h: HostColumn) -> "CpuCol":
        if isinstance(h.dtype, T.MapType):
            kcol = CpuCol.from_host(h.children[0])
            vcol = CpuCol.from_host(h.children[1])
            vals = np.empty(h.num_rows, object)
            for i in range(h.num_rows):
                vals[i] = (dict(zip(kcol.row(i), vcol.row(i)))
                           if h.validity[i] else None)
            return CpuCol(h.dtype, vals, h.validity.copy())
        if h.is_struct:
            kids = [CpuCol.from_host(c) for c in h.children]
            vals = np.empty(h.num_rows, object)
            for i in range(h.num_rows):
                vals[i] = (tuple(k.row(i) for k in kids)
                           if h.validity[i] else None)
            return CpuCol(h.dtype, vals, h.validity.copy())
        if h.is_string_array:
            lists = h.to_pylist()
            vals = np.empty(h.num_rows, object)
            for i, v in enumerate(lists):
                vals[i] = v
            return CpuCol(h.dtype, vals, h.validity.copy())
        if h.is_array:
            elem_t = h.dtype.elementType
            vals = []
            for i in range(h.num_rows):
                if not h.validity[i]:
                    vals.append(None)
                    continue
                ln = int(h.lengths[i])
                row = CpuCol.from_host(HostColumn(
                    elem_t, h.elem_valid[i, :ln], data=h.data[i, :ln]))
                vals.append([row.row(j) for j in range(ln)])
            out = np.empty(h.num_rows, object)
            for i, v in enumerate(vals):
                out[i] = v
            return CpuCol(h.dtype, out, h.validity.copy())
        if h.is_string:
            vals = np.array(
                [bytes(h.chars[i, : h.lengths[i]]).decode("utf-8", "replace")
                 if h.validity[i] else None
                 for i in range(h.num_rows)], dtype=object)
            return CpuCol(h.dtype, vals, h.validity.copy())
        if isinstance(h.dtype, T.DecimalType):
            if h.dtype.is_128:
                from spark_rapids_tpu.expr.decimal128 import to_py

                vals = np.array(
                    [to_py(int(h.data[i, 0]), int(h.data[i, 1]))
                     for i in range(h.num_rows)], dtype=object)
            else:
                # tolist() gives PYTHON ints (np.int64 elements would wrap
                # on >64-bit products); np.array over the list is C-speed
                vals = np.empty(h.num_rows, object)
                vals[:] = h.data.tolist()
            return CpuCol(h.dtype, vals, h.validity.copy())
        return CpuCol(h.dtype, h.data.copy(), h.validity.copy())

    def to_host(self) -> HostColumn:
        n = self.n
        if isinstance(self.dtype, T.MapType):
            keys = [list(self.values[i].keys())
                    if self.validity[i] and self.values[i] is not None
                    else None for i in range(n)]
            vals = [list(self.values[i].values())
                    if self.validity[i] and self.values[i] is not None
                    else None for i in range(n)]
            kcol = CpuCol.from_objs(
                keys, T.ArrayType(self.dtype.keyType, containsNull=False))
            vcol = CpuCol.from_objs(vals, T.ArrayType(self.dtype.valueType))
            return HostColumn(self.dtype, self.validity.copy(),
                              children=[kcol.to_host(), vcol.to_host()])
        if isinstance(self.dtype, T.StructType):
            kids = []
            for k, f in enumerate(self.dtype.fields):
                fv = [self.values[i][k]
                      if self.validity[i] and self.values[i] is not None
                      else None for i in range(n)]
                kids.append(CpuCol.from_objs(fv, f.dataType).to_host())
            return HostColumn(self.dtype, self.validity.copy(), children=kids)
        if isinstance(self.dtype, T.ArrayType) and isinstance(
                self.dtype.elementType, T.StringType):
            rows = [list(self.values[i]) if self.validity[i]
                    and self.values[i] is not None else None
                    for i in range(n)]
            h = HostColumn.from_pylist(rows, self.dtype)
            h.validity = self.validity.copy()
            return h
        if isinstance(self.dtype, T.ArrayType):
            elem_t = self.dtype.elementType
            width = max((len(v) for v in self.values if v is not None),
                        default=1) or 1
            data = np.zeros((n, width), T.storage_dtype(elem_t))
            ev = np.zeros((n, width), np.bool_)
            lengths = np.zeros(n, np.int32)
            for i in range(n):
                v = self.values[i]
                if not self.validity[i] or v is None:
                    continue
                lengths[i] = len(v)
                eh = HostColumn.from_pylist(list(v), elem_t)
                data[i, :len(v)] = eh.data
                ev[i, :len(v)] = eh.validity
            return HostColumn(self.dtype, self.validity.copy(), data=data,
                              lengths=lengths, elem_valid=ev)
        if isinstance(self.dtype, T.StringType):
            strs = [self.values[i] if self.validity[i] else None
                    for i in range(n)]
            h = HostColumn.from_pylist(strs, T.STRING)
            h.validity = self.validity.copy()
            return h
        if isinstance(self.dtype, T.DecimalType):
            if self.dtype.is_128:
                from spark_rapids_tpu.expr.decimal128 import limbs_of

                data = np.zeros((n, 2), np.int64)
                for i in range(n):
                    if self.validity[i]:
                        data[i, 0], data[i, 1] = limbs_of(int(self.values[i]))
                return HostColumn(self.dtype, self.validity.copy(), data=data)
            data = np.zeros(n, np.int64)
            for i in range(n):
                if self.validity[i]:
                    v = int(self.values[i])
                    # clamp into int64 (oracle may exceed; device would null)
                    data[i] = max(min(v, 2 ** 63 - 1), -(2 ** 63))
            return HostColumn(self.dtype, self.validity.copy(), data=data)
        return HostColumn(self.dtype, self.validity.copy(),
                          data=np.asarray(self.values))

    def row(self, i):
        return self.values[i] if self.validity[i] else None

    def to_pylist(self):
        """Lossless python values (decimals keep arbitrary precision —
        HostColumn's int64 storage would clamp precision>18)."""
        import datetime as _dt
        from decimal import Decimal as _Dec

        out = []
        for i in range(self.n):
            if not self.validity[i]:
                out.append(None)
            elif isinstance(self.dtype, T.ArrayType):
                v = self.values[i]
                ev = np.array([e is not None for e in v], np.bool_)
                vals = np.empty(len(v), object)
                for j, e in enumerate(v):
                    vals[j] = e
                out.append(CpuCol(self.dtype.elementType, vals,
                                  ev).to_pylist())
            elif isinstance(self.dtype, T.StructType):
                v = self.values[i]
                out.append(tuple(
                    CpuCol.from_objs([v[k]], f.dataType).to_pylist()[0]
                    for k, f in enumerate(self.dtype.fields)))
            elif isinstance(self.dtype, T.MapType):
                d = self.values[i]
                ks = CpuCol.from_objs(list(d.keys()),
                                      self.dtype.keyType).to_pylist()
                vs = CpuCol.from_objs(list(d.values()),
                                      self.dtype.valueType).to_pylist()
                out.append(dict(zip(ks, vs)))
            elif isinstance(self.dtype, T.DecimalType):
                out.append(_Dec(int(self.values[i])).scaleb(-self.dtype.scale))
            elif isinstance(self.dtype, T.DateType):
                out.append(_dt.date(1970, 1, 1)
                           + _dt.timedelta(days=int(self.values[i])))
            elif isinstance(self.dtype, T.TimestampType):
                out.append(_dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
                           + _dt.timedelta(microseconds=int(self.values[i])))
            elif isinstance(self.dtype, T.BooleanType):
                out.append(bool(self.values[i]))
            elif isinstance(self.dtype, (T.FloatType, T.DoubleType)):
                out.append(float(self.values[i]))
            elif isinstance(self.dtype, T.StringType):
                out.append(self.values[i])
            else:
                out.append(int(self.values[i]))
        return out


CpuBatch = List[CpuCol]  # plus schema carried by plan


# ===========================================================================
# Expression interpreter
# ===========================================================================

def eval_expr(e: E.Expression, cols: CpuBatch, n: int, ansi: bool = False) -> CpuCol:
    h = _HANDLERS.get(type(e).__name__)
    if h is None:
        raise NotImplementedError(f"oracle: {type(e).__name__}")
    return h(e, cols, n, ansi)


def _kids(e, cols, n, ansi):
    return [eval_expr(c, cols, n, ansi) for c in e.children]


def _null_prop_validity(kids: List[CpuCol]) -> np.ndarray:
    v = kids[0].validity.copy()
    for k in kids[1:]:
        v &= k.validity
    return v


def _h_bound(e: E.BoundReference, cols, n, ansi):
    return cols[e.ordinal]


def _h_literal(e: E.Literal, cols, n, ansi):
    dt = e._dataType
    if e.value is None:
        if isinstance(dt, (T.StringType, T.DecimalType)):
            return CpuCol(dt, np.array([None] * n, dtype=object),
                          np.zeros(n, np.bool_))
        sdt = T.storage_dtype(dt) if not isinstance(dt, T.NullType) else np.int32
        return CpuCol(dt, np.zeros(n, sdt), np.zeros(n, np.bool_))
    if isinstance(dt, T.StringType):
        return CpuCol(dt, np.array([e.value] * n, dtype=object),
                      np.ones(n, np.bool_))
    if isinstance(dt, T.DecimalType):
        return CpuCol(dt, np.array([e.storage_value()] * n, dtype=object),
                      np.ones(n, np.bool_))
    return CpuCol(dt, np.full(n, e.storage_value(), T.storage_dtype(dt)),
                  np.ones(n, np.bool_))


def _h_alias(e, cols, n, ansi):
    return eval_expr(e.children[0], cols, n, ansi)


# -- arithmetic -------------------------------------------------------------

_JMIN = {T.ByteType: -(2**7), T.ShortType: -(2**15), T.IntegerType: -(2**31),
         T.LongType: -(2**63)}
_JRANGE = {T.ByteType: 2**8, T.ShortType: 2**16, T.IntegerType: 2**32,
           T.LongType: 2**64}


def _java_wrap(vals, dt) -> np.ndarray:
    """Wrap arbitrary python ints into the Java type (independent of numpy
    overflow behavior)."""
    lo, rng = _JMIN[type(dt)], _JRANGE[type(dt)]
    out = np.zeros(len(vals), T.storage_dtype(dt))
    for i, v in enumerate(vals):
        out[i] = ((int(v) - lo) % rng) + lo
    return out


def _dec_check(vals, validity, dt: T.DecimalType, ansi, op):
    bound = 10 ** dt.precision
    safe = np.where(validity, vals, 0)
    in_bounds = np.asarray(safe < bound, np.bool_) & np.asarray(
        safe > -bound, np.bool_)
    bad = validity & ~in_bounds
    if bad.any():
        if ansi:
            raise E.SparkArithmeticException(f"decimal {op} overflow (ANSI)")
        return validity & in_bounds
    return validity.copy() if hasattr(validity, "copy") else validity


def _h_binarith(e: A.BinaryArithmetic, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    validity = l.validity & r.validity
    dt = e.dataType
    name = type(e).__name__
    if isinstance(dt, T.DecimalType):
        lt, rt = e.left.dataType, e.right.dataType
        if name in ("Add", "Subtract", "Multiply"):
            # vectorized object-int arithmetic (the hot TPC-H shapes)
            a = np.where(validity, l.values, 0)
            b = np.where(validity, r.values, 0)
            if name in ("Add", "Subtract"):
                a = a * (10 ** (dt.scale - lt.scale))
                b = b * (10 ** (dt.scale - rt.scale))
                out = a + b if name == "Add" else a - b
            else:
                out = a * b
            validity = _dec_check(out, validity, dt, ansi, name.lower())
            return CpuCol(dt, out, validity)
        out = np.zeros(n, dtype=object)
        for i in range(n):
            if not validity[i]:
                out[i] = 0
                continue
            a, b = int(l.values[i]), int(r.values[i])
            if name == "Divide":
                if b == 0:
                    if ansi:
                        raise E.SparkArithmeticException("division by zero (ANSI)")
                    validity[i] = False
                    out[i] = 0
                else:
                    from decimal import Decimal, ROUND_HALF_UP, localcontext

                    with localcontext() as lctx:
                        lctx.prec = 78
                        q = (Decimal(a).scaleb(-lt.scale)
                             / Decimal(b).scaleb(-rt.scale))
                        out[i] = int(q.scaleb(dt.scale).quantize(
                            Decimal(1), rounding=ROUND_HALF_UP))
            elif name in ("Remainder", "Pmod"):
                if b == 0:
                    if ansi:
                        raise E.SparkArithmeticException("division by zero (ANSI)")
                    validity[i] = False
                    out[i] = 0
                else:
                    sa = a * 10 ** (dt.scale - lt.scale)
                    sb = b * 10 ** (dt.scale - rt.scale)
                    m = abs(sa) % abs(sb)
                    out[i] = m * (1 if sa >= 0 else -1) if name == "Remainder" \
                        else (sa % abs(sb))
            else:
                raise NotImplementedError(name)
        validity = _dec_check(out, validity, dt, ansi, name.lower())
        return CpuCol(dt, out, validity)
    if dt.is_integral:
        out_py = []
        la, ra = l.values, r.values
        for i in range(n):
            if not validity[i]:
                out_py.append(0)
                continue
            a, b = int(la[i]), int(ra[i])
            if name == "Add":
                v = a + b
            elif name == "Subtract":
                v = a - b
            elif name == "Multiply":
                v = a * b
            elif name == "Remainder":
                if b == 0:
                    if ansi:
                        raise E.SparkArithmeticException("division by zero (ANSI)")
                    validity[i] = False
                    v = 0
                else:
                    v = int(math.fmod(a, b))
            elif name == "Pmod":
                if b == 0:
                    if ansi:
                        raise E.SparkArithmeticException("division by zero (ANSI)")
                    validity[i] = False
                    v = 0
                else:
                    # Spark: r = a % n (truncated); r < 0 -> (r + n) % n
                    v = int(math.fmod(a, b))
                    if v < 0:
                        v = int(math.fmod(v + b, b))
            elif name == "IntegralDivide":
                if b == 0:
                    if ansi:
                        raise E.SparkArithmeticException("division by zero (ANSI)")
                    validity[i] = False
                    v = 0
                else:
                    v = int(a / b) if abs(a) < 2**52 and abs(b) < 2**52 else \
                        abs(a) // abs(b) * (1 if (a < 0) == (b < 0) else -1)
            else:
                raise NotImplementedError(name)
            lo, rng = _JMIN[type(dt)], _JRANGE[type(dt)]
            wrapped = ((v - lo) % rng) + lo
            if ansi and wrapped != v:
                raise E.SparkArithmeticException(f"{name} overflow (ANSI)")
            out_py.append(wrapped)
        return CpuCol(dt, np.array(out_py, T.storage_dtype(dt)), validity)
    # floating point
    la = l.values.astype(np.float64)
    ra = r.values.astype(np.float64)
    with np.errstate(all="ignore"):
        if name == "Add":
            out = la + ra
        elif name == "Subtract":
            out = la - ra
        elif name == "Multiply":
            out = la * ra
        elif name == "Divide":
            zero = ra == 0.0
            if ansi and bool((zero & validity).any()):
                raise E.SparkArithmeticException("division by zero (ANSI)")
            validity = validity & ~zero
            out = np.where(zero, np.nan, la / np.where(zero, 1.0, ra))
        elif name in ("Remainder", "Pmod"):
            zero = ra == 0.0
            validity = validity & ~zero
            out = np.fmod(la, np.where(zero, 1.0, ra))
            if name == "Pmod":
                safe = np.where(zero, 1.0, ra)
                out = np.where(out < 0, np.fmod(out + safe, safe), out)
        else:
            raise NotImplementedError(name)
    return CpuCol(e.dataType, out.astype(T.storage_dtype(e.dataType)), validity)


def _h_unaryminus(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    dt = e.dataType
    if isinstance(dt, T.DecimalType):
        return CpuCol(dt, np.array([-int(v) for v in c.values], object),
                      c.validity.copy())
    if dt.is_integral:
        return CpuCol(dt, _java_wrap([-int(v) for v in c.values], dt),
                      c.validity.copy())
    return CpuCol(dt, -c.values, c.validity.copy())


def _h_abs(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    dt = e.dataType
    if isinstance(dt, T.DecimalType):
        return CpuCol(dt, np.array([abs(int(v)) for v in c.values], object),
                      c.validity.copy())
    if dt.is_integral:
        return CpuCol(dt, _java_wrap([abs(int(v)) for v in c.values], dt),
                      c.validity.copy())
    return CpuCol(dt, np.abs(c.values), c.validity.copy())


# -- predicates -------------------------------------------------------------

def _cmp_rows(l: CpuCol, r: CpuCol, dt: T.DataType):
    """elementwise python compare -> int array (-1,0,1)."""
    if isinstance(dt, T.DecimalType):
        # vectorized object-int compare (nulls neutralized; validity masks
        # the result downstream)
        a = np.where(l.validity, l.values, 0)
        b = np.where(r.validity, r.values, 0)
        return np.asarray(a > b, np.int32) - np.asarray(a < b, np.int32)
    out = np.zeros(l.n, np.int32)
    for i in range(l.n):
        a, b = l.values[i], r.values[i]
        if isinstance(dt, T.StringType):
            ab, bb = a.encode() if a is not None else b"", \
                b.encode() if b is not None else b""
            out[i] = (ab > bb) - (ab < bb)
        else:
            out[i] = (a > b) - (a < b)
    return out


def _h_comparison(e: P.BinaryComparison, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    validity = l.validity & r.validity
    name = type(e).__name__
    ct = e.left.dataType
    if isinstance(ct, (T.StringType, T.DecimalType)):
        cmpv = _cmp_rows(l, r, ct)
        data = {"EqualTo": cmpv == 0, "LessThan": cmpv < 0,
                "LessThanOrEqual": cmpv <= 0, "GreaterThan": cmpv > 0,
                "GreaterThanOrEqual": cmpv >= 0}[name]
    else:
        with np.errstate(invalid="ignore"):
            data = {"EqualTo": l.values == r.values,
                    "LessThan": l.values < r.values,
                    "LessThanOrEqual": l.values <= r.values,
                    "GreaterThan": l.values > r.values,
                    "GreaterThanOrEqual": l.values >= r.values}[name]
    return CpuCol(T.BOOLEAN, np.asarray(data, np.bool_), validity)


def _h_nullsafe_eq(e, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    ct = e.left.dataType
    if isinstance(ct, (T.StringType, T.DecimalType)):
        eq = _cmp_rows(l, r, ct) == 0
    else:
        eq = l.values == r.values
    data = (l.validity & r.validity & eq) | (~l.validity & ~r.validity)
    return CpuCol(T.BOOLEAN, data, np.ones(n, np.bool_))


def _h_and(e, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    lt = l.validity & l.values.astype(bool)
    lf = l.validity & ~l.values.astype(bool)
    rt = r.validity & r.values.astype(bool)
    rf = r.validity & ~r.values.astype(bool)
    data = lt & rt
    validity = (l.validity & r.validity) | lf | rf
    return CpuCol(T.BOOLEAN, data, validity)


def _h_or(e, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    lt = l.validity & l.values.astype(bool)
    rt = r.validity & r.values.astype(bool)
    data = lt | rt
    validity = (l.validity & r.validity) | lt | rt
    return CpuCol(T.BOOLEAN, data, validity)


def _h_not(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    return CpuCol(T.BOOLEAN, ~c.values.astype(bool), c.validity.copy())


def _h_isnull(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    return CpuCol(T.BOOLEAN, ~c.validity, np.ones(n, np.bool_))


def _h_isnotnull(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    return CpuCol(T.BOOLEAN, c.validity.copy(), np.ones(n, np.bool_))


def _h_isnan(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    data = np.zeros(n, np.bool_)
    m = c.validity
    data[m] = np.isnan(c.values[m].astype(np.float64))
    return CpuCol(T.BOOLEAN, data, np.ones(n, np.bool_))


def _h_in(e: P.In, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    v, cands = kids[0], kids[1:]
    data = np.zeros(n, np.bool_)
    any_null_cand = any(not bool(c.validity.all()) for c in cands)
    for c in cands:
        if not c.validity.any():
            continue
        if isinstance(e.children[0].dataType, (T.StringType, T.DecimalType)):
            eq = np.array([v.values[i] == c.values[i] for i in range(n)])
        else:
            eq = v.values == c.values
        data |= eq & c.validity
    validity = v.validity.copy()
    if any_null_cand:
        validity &= data
    return CpuCol(T.BOOLEAN, data, validity)


# -- conditionals -----------------------------------------------------------

def _select(pred_data, pred_valid, a: CpuCol, b: CpuCol, dt) -> CpuCol:
    take_a = pred_data.astype(bool) & pred_valid
    if a.values.dtype == object or b.values.dtype == object:
        vals = np.array([a.values[i] if take_a[i] else b.values[i]
                         for i in range(len(take_a))], dtype=object)
    else:
        vals = np.where(take_a, a.values, b.values)
    validity = np.where(take_a, a.validity, b.validity)
    return CpuCol(dt, vals, validity.astype(np.bool_))


def _h_if(e, cols, n, ansi):
    p, a, b = _kids(e, cols, n, ansi)
    return _select(p.values, p.validity, a, b, e.dataType)


def _h_casewhen(e: CO.CaseWhen, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    nb = (len(e.children) - (1 if e.has_else else 0)) // 2
    if e.has_else:
        acc = kids[-1]
    else:
        acc = _h_literal(E.Literal(None, e.dataType), cols, n, ansi)
    for i in reversed(range(nb)):
        cond, val = kids[2 * i], kids[2 * i + 1]
        acc = _select(cond.values, cond.validity, val, acc, e.dataType)
    return acc


def _h_coalesce(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    acc = kids[-1]
    for c in reversed(kids[:-1]):
        acc = _select(c.validity, np.ones(n, np.bool_), c, acc, e.dataType)
    return acc


def _h_nanvl(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    is_nan = np.zeros(n, np.bool_)
    m = a.validity
    is_nan[m] = np.isnan(a.values[m].astype(np.float64))
    return _select(~is_nan, np.ones(n, np.bool_), a, b, e.dataType)


def _h_greatest(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    mx = type(e).__name__ == "Greatest"
    out_vals = []
    out_valid = np.zeros(n, np.bool_)

    def rank(v):
        # NaN strictly greatest; strings by bytes
        if isinstance(v, str):
            return (0, v.encode())
        if isinstance(v, float) and math.isnan(v):
            return (1, 0.0)
        return (0, float(v))

    for i in range(n):
        vals = [k.values[i] for k in kids if k.validity[i]]
        if not vals:
            out_vals.append(0 if kids[0].values.dtype != object else None)
            continue
        out_valid[i] = True
        out_vals.append((max if mx else min)(vals, key=rank))
    dtype = object if kids[0].values.dtype == object else kids[0].values.dtype
    return CpuCol(e.dataType, np.array(out_vals, dtype=dtype), out_valid)


# -- cast -------------------------------------------------------------------

def _h_cast(e: C.Cast, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    src, dst = e.child.dataType, e.to
    ansi = ansi or e.ansi_override
    if src == dst:
        return c
    if isinstance(src, T.DecimalType) and isinstance(dst, T.DecimalType):
        # vectorized integer rescale (comparison coercion makes this hot)
        vals = np.where(c.validity, c.values, 0)
        diff = dst.scale - src.scale
        widens = (dst.precision - dst.scale >= src.precision - src.scale
                  and diff >= 0)
        if diff == 0:
            if widens:  # pure widening: values cannot overflow
                return CpuCol(dst, vals, c.validity.copy())
            out = vals
        elif diff > 0:
            out = vals * (10 ** diff)
        else:
            den = 10 ** (-diff)
            q = vals // den               # floor
            rem = vals - q * den
            neg = np.asarray(vals < 0, np.bool_)
            q = q + np.asarray(neg & np.asarray(rem != 0, np.bool_),
                               np.int64)  # -> trunc toward zero
            rem2 = np.abs(vals - q * den)
            q = q + np.where(np.asarray(2 * rem2 >= den, np.bool_)
                             & np.asarray(rem2 != 0, np.bool_),
                             np.where(neg, -1, 1), 0)  # HALF_UP
            out = q
        validity = _dec_check(out, c.validity, dst, ansi, "cast")
        return CpuCol(dst, out, validity)
    out_vals: list = []
    out_valid = c.validity.copy()
    for i in range(n):
        if not c.validity[i]:
            out_vals.append(None)
            continue
        try:
            out_vals.append(_cast_one(c.values[i], src, dst, ansi))
        except _CastNull:
            if ansi:
                raise E.SparkArithmeticException(
                    f"invalid cast {src}->{dst} (ANSI)")
            out_vals.append(None)
            out_valid[i] = False
    if isinstance(dst, (T.StringType, T.DecimalType)):
        vals = np.array([v if v is not None else None for v in out_vals],
                        dtype=object)
    else:
        sdt = T.storage_dtype(dst)
        vals = np.array([v if v is not None else 0 for v in out_vals],
                        dtype=sdt)
    return CpuCol(dst, vals, out_valid)


class _CastNull(Exception):
    pass


_TS_TIME_RE = None


def _civil_days_py(y, m, d):
    """Hinnant days-from-civil (python ints; years beyond 9999 fine)."""
    yy = y - (1 if m <= 2 else 0)
    era = (yy if yy >= 0 else yy - 399) // 400
    yoe = yy - era * 400
    mp = m + (-3 if m > 2 else 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _civil_valid_py(y, m, d):
    if not (1 <= m <= 12 and d >= 1 and 1 <= y <= 9999):
        return False
    if m == 12:
        ml = _civil_days_py(y + 1, 1, 1) - _civil_days_py(y, 12, 1)
    else:
        ml = _civil_days_py(y, m + 1, 1) - _civil_days_py(y, m, 1)
    return d <= ml


def _parse_civil_py(s):
    """Oracle twin of cast._parse_civil_string: returns (days, tail) or
    None.  Grammar: [y]yyyy[-[m]m[-[d]d<tail>]]."""
    import re as _re

    m = _re.match(r"^(\d{4,6})(?:-(\d{1,2})(?:-(\d{1,2})(.*))?)?$", s,
                  _re.S)
    if not m:
        return None
    y = int(m.group(1))
    mo = int(m.group(2)) if m.group(2) else 1
    d = int(m.group(3)) if m.group(3) else 1
    tail = m.group(4) if m.group(4) is not None else ""
    if not _civil_valid_py(y, mo, d):
        return None
    return _civil_days_py(y, mo, d), tail, m.group(3) is not None


def _str_to_date_py(sv):
    r = _parse_civil_py(str(sv).strip())
    if r is None:
        return None
    days, tail, had_day = r
    if tail and not (had_day and tail[0] in " T"):
        return None
    return days


_TS_TAIL_RE = None


def _str_to_ts_py(sv):
    """Oracle twin of cast._string_to_timestamp (same documented subset)."""
    import re as _re

    global _TS_TAIL_RE
    if _TS_TAIL_RE is None:
        _TS_TAIL_RE = _re.compile(
            r"^[ T](\d{1,2})(?::(\d{1,2})(?::(\d{1,2})"
            r"(?:\.(\d{1,9}))?)?)?"
            r"(Z|z|[+-](?:\d{4}|\d{1,2}(?::\d{2})?))?$")
    r = _parse_civil_py(str(sv).strip())
    if r is None:
        return None
    days, tail, _ = r
    micros = days * 86_400_000_000
    if not tail:
        return micros
    m = _TS_TAIL_RE.match(tail)
    if not m:
        return None
    h = int(m.group(1))
    mi = int(m.group(2)) if m.group(2) else 0
    s = int(m.group(3)) if m.group(3) else 0
    if h > 23 or mi > 59 or s > 59:
        return None
    frac = m.group(4) or ""
    frac_us = (int(frac) * 10 ** (6 - len(frac)) if len(frac) <= 6
               else int(frac) // 10 ** (len(frac) - 6)) if frac else 0
    off = 0
    tz = m.group(5)
    if tz and tz not in ("Z", "z"):
        sign = 1 if tz[0] == "+" else -1
        body = tz[1:]
        if ":" in body:
            hh, mm = body.split(":")
        elif len(body) == 4:
            hh, mm = body[:2], body[2:]
        else:
            hh, mm = body, "0"
        hh, mm = int(hh), int(mm)
        if hh > 18 or mm > 59 or hh * 60 + mm > 18 * 60:
            return None
        off = sign * (hh * 3600 + mm * 60)
    return (micros + h * 3_600_000_000 + mi * 60_000_000 + s * 1_000_000
            + frac_us - off * 1_000_000)


def _cast_one(v, src: T.DataType, dst: T.DataType, ansi: bool):
    import decimal as pydec

    def is_int(t):
        return t.is_integral

    if isinstance(dst, T.BooleanType):
        if isinstance(src, T.StringType):
            s = str(v).strip().lower()
            if s in ("true", "t", "yes", "y", "1"):
                return True
            if s in ("false", "f", "no", "n", "0"):
                return False
            raise _CastNull
        return v != 0
    if isinstance(dst, T.StringType):
        if isinstance(src, T.BooleanType):
            return "true" if v else "false"
        if isinstance(src, T.DecimalType):
            d = pydec.Decimal(int(v)).scaleb(-src.scale)
            return f"{d:.{src.scale}f}" if src.scale > 0 else str(int(v))
        if isinstance(src, T.DateType):
            return (pydt.date(1970, 1, 1) + pydt.timedelta(days=int(v))).isoformat()
        if isinstance(src, T.TimestampType):
            ts = pydt.datetime(1970, 1, 1) + pydt.timedelta(microseconds=int(v))
            base = ts.strftime("%Y-%m-%d %H:%M:%S")
            if ts.microsecond:
                frac = f"{ts.microsecond:06d}".rstrip("0")
                return f"{base}.{frac}"
            return base
        if isinstance(src, (T.FloatType, T.DoubleType)):
            from spark_rapids_tpu.expr.cast import java_fp_to_string

            return java_fp_to_string(float(v), isinstance(src, T.FloatType))
        return str(int(v))
    if is_int(dst):
        if isinstance(src, T.StringType):
            s = str(v).strip()
            if not s or not s.lstrip("+-").isdigit() or len(s.lstrip("+-")) > 19:
                raise _CastNull
            val = int(s)
        elif isinstance(src, (T.FloatType, T.DoubleType)):
            f = float(v)
            if math.isnan(f):
                val = 0
            elif f >= 2 ** 63:      # Java (long) saturates
                val = 2 ** 63 - 1
            elif f <= -(2 ** 63):
                val = -(2 ** 63)
            else:
                val = int(f)
        elif isinstance(src, T.DecimalType):
            val = int(pydec.Decimal(int(v)).scaleb(-src.scale)
                      .to_integral_value(rounding=pydec.ROUND_DOWN))
        elif isinstance(src, T.TimestampType):
            val = int(v) // 1_000_000 if int(v) >= 0 or int(v) % 1_000_000 == 0 \
                else int(v) // 1_000_000
        else:
            val = int(v)
        lo, rng = _JMIN[type(dst)], _JRANGE[type(dst)]
        wrapped = ((val - lo) % rng) + lo
        if isinstance(src, T.StringType) and wrapped != val:
            raise _CastNull
        if isinstance(src, T.DecimalType) and wrapped != val:
            raise _CastNull
        return wrapped
    if isinstance(dst, (T.FloatType, T.DoubleType)):
        if isinstance(src, T.StringType):
            from spark_rapids_tpu.expr.cast import spark_string_to_double

            f = spark_string_to_double(str(v))
            if f is None:
                raise _CastNull
            return f
        if isinstance(src, T.DecimalType):
            return float(pydec.Decimal(int(v)).scaleb(-src.scale))
        return float(v)
    if isinstance(dst, T.DecimalType):
        if isinstance(src, T.DecimalType):
            d = pydec.Decimal(int(v)).scaleb(-src.scale)
        elif isinstance(src, (T.FloatType, T.DoubleType)):
            f = float(v)
            if math.isnan(f) or math.isinf(f):
                raise _CastNull
            d = pydec.Decimal(f)
        else:
            d = pydec.Decimal(int(v))
        scaled = int(d.scaleb(dst.scale).quantize(
            pydec.Decimal(1), rounding=pydec.ROUND_HALF_UP))
        if abs(scaled) >= 10 ** dst.precision:
            raise _CastNull
        return scaled
    if isinstance(dst, T.DateType):
        if isinstance(src, T.StringType):
            days = _str_to_date_py(v)
            if days is None:
                raise _CastNull
            return days
        if isinstance(src, T.TimestampType):
            return int(v) // 86_400_000_000
        raise _CastNull
    if isinstance(dst, T.TimestampType):
        if isinstance(src, T.DateType):
            return int(v) * 86_400_000_000
        if isinstance(src, T.StringType):
            micros = _str_to_ts_py(v)
            if micros is None:
                raise _CastNull
            return micros
        if is_int(src):
            return int(v) * 1_000_000
        raise _CastNull
    raise NotImplementedError(f"oracle cast {src}->{dst}")


# -- math -------------------------------------------------------------------

def _h_unary_math(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    x = c.values.astype(np.float64)
    name = type(e).__name__
    validity = c.validity.copy()
    with np.errstate(all="ignore"):
        if name == "Sqrt":
            out = np.sqrt(np.where(x < 0, np.nan, x))
        elif name == "Exp":
            out = np.exp(x)
        elif name == "Log":
            bad = x <= 0
            validity &= ~bad
            out = np.log(np.where(bad, 1.0, x))
        elif name == "Log10":
            bad = x <= 0
            validity &= ~bad
            out = np.log10(np.where(bad, 1.0, x))
        elif name in ("Sin", "Cos", "Tan", "Asin", "Acos", "Atan",
                      "Sinh", "Cosh", "Tanh", "Asinh", "Acosh", "Atanh",
                      "Cbrt", "Expm1"):
            out = getattr(np, {"Sin": "sin", "Cos": "cos", "Tan": "tan",
                               "Asin": "arcsin", "Acos": "arccos",
                               "Atan": "arctan", "Sinh": "sinh",
                               "Cosh": "cosh", "Tanh": "tanh",
                               "Asinh": "arcsinh", "Acosh": "arccosh",
                               "Atanh": "arctanh", "Cbrt": "cbrt",
                               "Expm1": "expm1"}[name])(x)
        elif name == "Log2":
            bad = x <= 0
            validity &= ~bad
            out = np.log2(np.where(bad, 1.0, x))
        elif name == "Log1p":
            bad = x <= -1.0
            validity &= ~bad
            out = np.log1p(np.where(bad, 0.0, x))
        elif name == "Rint":
            out = np.round(x)  # numpy round is half-to-even == Math.rint
        elif name == "Cot":
            out = 1.0 / np.tan(x)
        elif name == "Csc":
            out = 1.0 / np.sin(x)
        elif name == "Sec":
            out = 1.0 / np.cos(x)
        elif name == "ToDegrees":
            out = np.degrees(x)
        elif name == "ToRadians":
            out = np.radians(x)
        elif name == "Signum":
            out = np.sign(x)
        else:
            raise NotImplementedError(name)
    return CpuCol(T.DOUBLE, out, validity)


def _h_binary_math(e, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    a = l.values.astype(np.float64)
    b = r.values.astype(np.float64)
    name = type(e).__name__
    validity = l.validity & r.validity
    with np.errstate(all="ignore"):
        if name == "Atan2":
            out = np.arctan2(a, b)
        elif name == "Hypot":
            out = np.hypot(a, b)
        elif name == "Logarithm":
            bad = (b <= 0) | (a <= 0) | (a == 1.0)
            validity = validity & ~bad
            out = np.log(np.where(b <= 0, 1.0, b)) / np.log(
                np.where((a <= 0) | (a == 1.0), 2.0, a))
        else:
            raise NotImplementedError(name)
    return CpuCol(T.DOUBLE, out, validity)


def _h_bitwise(e, cols, n, ansi):
    name = type(e).__name__
    if name == "BitwiseNot":
        (c,) = _kids(e, cols, n, ansi)
        return CpuCol(e.dataType, ~c.values, c.validity.copy())
    l, r = _kids(e, cols, n, ansi)
    validity = l.validity & r.validity
    if name in ("BitwiseAnd", "BitwiseOr", "BitwiseXor"):
        fn = {"BitwiseAnd": np.bitwise_and, "BitwiseOr": np.bitwise_or,
              "BitwiseXor": np.bitwise_xor}[name]
        return CpuCol(e.dataType, fn(l.values, r.values), validity)
    # shifts: Java masks the amount to the value width
    width_mask = 63 if isinstance(e.dataType, T.LongType) else 31
    amt = (r.values.astype(np.int64) & width_mask).astype(l.values.dtype)
    if name == "ShiftLeft":
        out = l.values << amt
    elif name == "ShiftRight":
        out = l.values >> amt
    else:  # ShiftRightUnsigned
        udt = np.uint64 if l.values.dtype == np.int64 else np.uint32
        out = (l.values.view(udt) >> amt.view(udt)).view(l.values.dtype)
    return CpuCol(e.dataType, out, validity)


def _h_pow(e, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    with np.errstate(all="ignore"):
        out = np.power(l.values.astype(np.float64), r.values.astype(np.float64))
    return CpuCol(T.DOUBLE, out, l.validity & r.validity)


def _h_floorceil(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    ct = e.child.dataType
    is_ceil = type(e).__name__ == "Ceil"
    if ct.is_integral:
        return c
    if isinstance(ct, T.DecimalType):
        import decimal as pydec

        r = pydec.ROUND_CEILING if is_ceil else pydec.ROUND_FLOOR
        vals = np.array([int(pydec.Decimal(int(v)).scaleb(-ct.scale)
                             .to_integral_value(rounding=r))
                         for v in c.values], dtype=object)
        return CpuCol(e.dataType, vals, c.validity.copy())
    f = np.ceil if is_ceil else np.floor
    return CpuCol(T.LONG, f(c.values.astype(np.float64)).astype(np.int64),
                  c.validity.copy())


def _h_round(e, cols, n, ansi):
    c, s = _kids(e, cols, n, ansi)
    ct = e.children[0].dataType
    if isinstance(ct, T.DecimalType):
        import decimal as pydec

        dt: T.DecimalType = e.dataType
        vals = np.array(
            [int(pydec.Decimal(int(v)).scaleb(-ct.scale).scaleb(dt.scale)
                 .quantize(pydec.Decimal(1), rounding=pydec.ROUND_HALF_UP))
             for v in c.values], dtype=object)
        return CpuCol(dt, vals, c.validity.copy())
    if ct.is_integral:
        return c
    out = np.zeros(n, np.float64)
    for i in range(n):
        if c.validity[i]:
            import decimal as pydec

            d = pydec.Decimal(repr(float(c.values[i]))).quantize(
                pydec.Decimal(1).scaleb(-int(s.values[i])),
                rounding=pydec.ROUND_HALF_UP)
            out[i] = float(d)
    return CpuCol(e.dataType, out, c.validity & s.validity)


# -- strings ----------------------------------------------------------------

def _str_rows(c: CpuCol):
    return c.values


def _h_length(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.array([len(v) if v is not None else 0 for v in c.values],
                   np.int32)
    return CpuCol(T.INT, out, c.validity.copy())


def _h_upperlower(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    up = type(e).__name__ == "Upper"
    # ASCII-only to match device (documented incompat for non-ASCII)
    def tx(s):
        return "".join(
            chr(ord(ch) - 32) if up and "a" <= ch <= "z" else
            chr(ord(ch) + 32) if not up and "A" <= ch <= "Z" else ch
            for ch in s)

    out = np.array([tx(v) if v is not None else None for v in c.values],
                   object)
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_substring(e, cols, n, ansi):
    c, p, ln = _kids(e, cols, n, ansi)
    out = []
    validity = c.validity & p.validity & ln.validity
    for i in range(n):
        if not validity[i]:
            out.append(None)
            continue
        s = c.values[i]
        pos, want = int(p.values[i]), int(ln.values[i])
        b = s.encode()
        # Spark substringSQL: window computed on unclamped start
        if pos > 0:
            start = pos - 1
        elif pos < 0:
            start = len(b) + pos
        else:
            start = 0
        end = start + max(want, 0)
        seg = b[max(start, 0): max(end, 0)]
        out.append(seg.decode("utf-8", "replace"))
    return CpuCol(T.STRING, np.array(out, object), validity)


def _h_concat(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    validity = _null_prop_validity(kids)
    out = []
    for i in range(n):
        out.append("".join(k.values[i] for k in kids) if validity[i] else None)
    return CpuCol(T.STRING, np.array(out, object), validity)


def _h_startswith(e, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    validity = l.validity & r.validity
    name = type(e).__name__
    out = np.zeros(n, np.bool_)
    for i in range(n):
        if validity[i]:
            s, t = l.values[i], r.values[i]
            out[i] = (s.startswith(t) if name == "StartsWith"
                      else s.endswith(t) if name == "EndsWith"
                      else t in s)
    return CpuCol(T.BOOLEAN, out, validity)


def _h_trim(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.array([v.strip(" ") if v is not None else None
                    for v in c.values], object)
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_like(e: S.Like, cols, n, ansi):
    import re

    from spark_rapids_tpu.regex.transpiler import like_to_regex

    l, _ = _kids(e, cols, n, ansi)
    rx = re.compile(like_to_regex(e.right.value))
    out = np.array([bool(rx.fullmatch(v)) if v is not None else False
                    for v in l.values], np.bool_)
    return CpuCol(T.BOOLEAN, out, l.validity.copy())


def _java_regex_to_python(pat: str) -> str:
    """Adjust Java-vs-Python differences for the supported subset:
    Java `.` excludes \\r too; Java `$` also matches before a final \\r /
    \\r\\n.  Walks the pattern skipping escapes and char classes."""
    out = []
    i = 0
    in_class = False
    while i < len(pat):
        c = pat[i]
        if c == "\\" and i + 1 < len(pat):
            out.append(pat[i:i + 2])
            i += 2
            continue
        if in_class:
            if c == "]":
                in_class = False
            out.append(c)
        elif c == "[":
            in_class = True
            out.append(c)
        elif c == ".":
            out.append(r"[^\n\r]")
        elif c == "$":
            out.append(r"(?=(?:\r\n|\n|\r)?\Z)")
        else:
            out.append(c)
        i += 1
    return "".join(out)


def _h_rlike(e, cols, n, ansi):
    import re

    l, _ = _kids(e, cols, n, ansi)
    rx = re.compile(_java_regex_to_python(e.right.value))
    out = np.array([bool(rx.search(v)) if v is not None else False
                    for v in l.values], np.bool_)
    return CpuCol(T.BOOLEAN, out, l.validity.copy())


# -- datetime ---------------------------------------------------------------

def _date_of(c: CpuCol, dtype):
    if isinstance(dtype, T.TimestampType):
        return [pydt.date(1970, 1, 1)
                + pydt.timedelta(days=int(v) // 86_400_000_000)
                for v in c.values]
    return [pydt.date(1970, 1, 1) + pydt.timedelta(days=int(v))
            for v in c.values]


def _h_datefield(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    dates = _date_of(c, e.child.dataType)
    name = type(e).__name__
    out = np.zeros(n, np.int32)
    for i in range(n):
        if not c.validity[i]:
            continue
        d = dates[i]
        if name == "Year":
            out[i] = d.year
        elif name == "Month":
            out[i] = d.month
        elif name == "DayOfMonth":
            out[i] = d.day
        elif name == "DayOfWeek":
            out[i] = d.isoweekday() % 7 + 1
        elif name == "DayOfYear":
            out[i] = d.timetuple().tm_yday
        elif name == "Quarter":
            out[i] = (d.month - 1) // 3 + 1
        else:
            raise NotImplementedError(name)
    return CpuCol(T.INT, out, c.validity.copy())


def _h_lastday(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    import calendar

    dates = _date_of(c, e.child.dataType)
    out = np.zeros(n, np.int32)
    for i in range(n):
        if c.validity[i]:
            d = dates[i]
            last = d.replace(day=calendar.monthrange(d.year, d.month)[1])
            out[i] = (last - pydt.date(1970, 1, 1)).days
    return CpuCol(T.DATE, out, c.validity.copy())


def _h_timefield(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    name = type(e).__name__
    out = np.zeros(n, np.int32)
    for i in range(n):
        if c.validity[i]:
            ts = (pydt.datetime(1970, 1, 1)
                  + pydt.timedelta(microseconds=int(c.values[i])))
            out[i] = {"Hour": ts.hour, "Minute": ts.minute,
                      "Second": ts.second}[name]
    return CpuCol(T.INT, out, c.validity.copy())


def _h_dateadd(e, cols, n, ansi):
    d, k = _kids(e, cols, n, ansi)
    sign = -1 if type(e).__name__ == "DateSub" else 1
    out = (d.values.astype(np.int64)
           + sign * k.values.astype(np.int64)).astype(np.int32)
    return CpuCol(T.DATE, out, d.validity & k.validity)


def _h_datediff(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    return CpuCol(T.INT, (a.values.astype(np.int64)
                          - b.values.astype(np.int64)).astype(np.int32),
                  a.validity & b.validity)


def _h_unixts(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    if isinstance(e.child.dataType, T.DateType):
        out = c.values.astype(np.int64) * 86_400
    else:
        out = np.array([int(v) // 1_000_000 for v in c.values], np.int64)
    return CpuCol(T.LONG, out, c.validity.copy())


def _h_weekofyear(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    dates = _date_of(c, e.child.dataType)
    out = np.zeros(n, np.int32)
    for i in range(n):
        if c.validity[i]:
            out[i] = dates[i].isocalendar()[1]
    return CpuCol(T.INT, out, c.validity.copy())


def _h_addmonths(e, cols, n, ansi):
    import calendar

    d, k = _kids(e, cols, n, ansi)
    dates = _date_of(d, e.children[0].dataType)
    out = np.zeros(n, np.int32)
    validity = d.validity & k.validity
    for i in range(n):
        if not validity[i]:
            continue
        dt = dates[i]
        total = dt.year * 12 + dt.month - 1 + int(k.values[i])
        y, m = total // 12, total % 12 + 1
        day = min(dt.day, calendar.monthrange(y, m)[1])
        out[i] = (pydt.date(y, m, day) - pydt.date(1970, 1, 1)).days
    return CpuCol(T.DATE, out, validity)


def _h_monthsbetween(e, cols, n, ansi):
    import calendar

    a, b = _kids(e, cols, n, ansi)
    validity = a.validity & b.validity
    out = np.zeros(n, np.float64)

    def parts(col_, dt):
        if isinstance(dt, T.TimestampType):
            tss = [pydt.datetime(1970, 1, 1)
                   + pydt.timedelta(microseconds=int(v)) for v in col_.values]
        else:
            tss = [pydt.datetime(1970, 1, 1)
                   + pydt.timedelta(days=int(v)) for v in col_.values]
        return tss

    ta = parts(a, e.children[0].dataType)
    tb = parts(b, e.children[1].dataType)
    for i in range(n):
        if not validity[i]:
            continue
        x, y = ta[i], tb[i]
        months = (x.year - y.year) * 12 + (x.month - y.month)
        x_end = x.day == calendar.monthrange(x.year, x.month)[1]
        y_end = y.day == calendar.monthrange(y.year, y.month)[1]
        secs_x = x.hour * 3600 + x.minute * 60 + x.second + x.microsecond / 1e6
        secs_y = y.hour * 3600 + y.minute * 60 + y.second + y.microsecond / 1e6
        # Spark: equal day-of-month (or both month ends) -> whole months,
        # time of day ignored
        if (x_end and y_end) or x.day == y.day:
            v = float(months)
        else:
            v = months + ((x.day - y.day) * 86400.0 + secs_x - secs_y) \
                / (31.0 * 86400.0)
        if getattr(e, "round_off", True):
            v = float(np.round(v * 1e8) / 1e8)
        out[i] = v
    return CpuCol(T.DOUBLE, out, validity)


def _h_truncdate(e, cols, n, ansi):
    c = eval_expr(e.children[0], cols, n, ansi)
    from spark_rapids_tpu.expr.datetime import TruncDate as _TD

    fmt = e.children[1]
    unit = _TD._FMTS.get(str(fmt.value).lower()) \
        if getattr(fmt, "value", None) is not None else None
    dates = _date_of(c, e.children[0].dataType)
    out = np.zeros(n, np.int32)
    validity = c.validity.copy()
    for i in range(n):
        if not c.validity[i]:
            continue
        d = dates[i]
        if unit == "year":
            t = d.replace(month=1, day=1)
        elif unit == "quarter":
            t = d.replace(month=(d.month - 1) // 3 * 3 + 1, day=1)
        elif unit == "month":
            t = d.replace(day=1)
        elif unit == "week":
            t = d - pydt.timedelta(days=d.weekday())
        else:
            validity[i] = False
            continue
        out[i] = (t - pydt.date(1970, 1, 1)).days
    return CpuCol(T.DATE, out, validity)


def _h_nextday(e, cols, n, ansi):
    c = eval_expr(e.children[0], cols, n, ansi)
    from spark_rapids_tpu.expr.datetime import NextDay as _ND

    lit_ = e.children[1]
    target = _ND._DOW.get(str(lit_.value).strip().lower()) \
        if getattr(lit_, "value", None) is not None else None
    dates = _date_of(c, e.children[0].dataType)
    out = np.zeros(n, np.int32)
    validity = c.validity.copy()
    for i in range(n):
        if not c.validity[i]:
            continue
        if target is None:
            validity[i] = False
            continue
        d = dates[i]
        dow = d.isoweekday() % 7     # Sunday=0
        delta = (target - dow) % 7 or 7
        out[i] = (d - pydt.date(1970, 1, 1)).days + delta
    return CpuCol(T.DATE, out, validity)


def _py_civil_from_days(z: int):
    """Howard Hinnant civil-from-days (pure ints: no datetime range cap)."""
    z += 719468
    era = (z if z >= 0 else z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + 3 if mp < 10 else mp - 9
    return y + (1 if m <= 2 else 0), m, d


_DOW_ABBR = ["Sun", "Mon", "Tue", "Wed", "Thu", "Fri", "Sat"]
_DOW_FULL = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
             "Friday", "Saturday"]
_MON_ABBR = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
             "Oct", "Nov", "Dec"]
_MON_FULL = ["January", "February", "March", "April", "May", "June", "July",
             "August", "September", "October", "November", "December"]
_ORACLE_FMT_TOKENS = ("yyyy", "MMMM", "MMM", "MM", "dd", "DD", "HH", "mm",
                      "ss", "EEEE", "EEE", "a")


def _oracle_format_micros(micros: int, fmt: str) -> str:
    """Render with pure integer civil math (Java patterns, UTC)."""
    days, rem = divmod(micros, 86_400_000_000)
    y, mo, d = _py_civil_from_days(days)
    h = rem // 3_600_000_000
    mi = (rem // 60_000_000) % 60
    s = (rem // 1_000_000) % 60
    dow = (days + 4) % 7
    out = []
    i = 0
    while i < len(fmt):
        for t in _ORACLE_FMT_TOKENS:
            if fmt.startswith(t, i):
                if t == "yyyy":
                    out.append(f"{y:04d}")
                elif t == "MM":
                    out.append(f"{mo:02d}")
                elif t == "MMM":
                    out.append(_MON_ABBR[mo - 1])
                elif t == "MMMM":
                    out.append(_MON_FULL[mo - 1])
                elif t == "dd":
                    out.append(f"{d:02d}")
                elif t == "DD":
                    out.append(f"{_day_of_year(y, mo, d):03d}")
                elif t == "HH":
                    out.append(f"{h:02d}")
                elif t == "mm":
                    out.append(f"{mi:02d}")
                elif t == "ss":
                    out.append(f"{s:02d}")
                elif t == "EEE":
                    out.append(_DOW_ABBR[dow])
                elif t == "EEEE":
                    out.append(_DOW_FULL[dow])
                elif t == "a":
                    out.append("AM" if h < 12 else "PM")
                i += len(t)
                break
        else:
            ch = fmt[i]
            if ch.isalpha():
                raise NotImplementedError(f"oracle time format letter {ch!r}")
            out.append(ch)
            i += 1
    return "".join(out)


_MDAYS = [31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31]


def _day_of_year(y: int, m: int, d: int) -> int:
    leap = (y % 4 == 0 and y % 100 != 0) or y % 400 == 0
    return sum(_MDAYS[: m - 1]) + (1 if leap and m > 2 else 0) + d


def _h_format_time(e, cols, n, ansi):
    c = eval_expr(e.children[0], cols, n, ansi)
    fmt = str(e.children[1].value)
    name = type(e).__name__
    out = np.empty(n, object)
    for i in range(n):
        if not c.validity[i]:
            out[i] = None
            continue
        if name == "FromUnixTime":
            # Java sec * MICROS_PER_SECOND wraps silently (long multiply)
            micros = int(c.values[i]) * 1_000_000
            micros = (micros + 2 ** 63) % 2 ** 64 - 2 ** 63
        elif isinstance(e.children[0].dataType, T.DateType):
            micros = int(c.values[i]) * 86_400_000_000
        else:
            micros = int(c.values[i])
        out[i] = _oracle_format_micros(micros, fmt)
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_size(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.array([len(v) if c.validity[i] and v is not None else -1
                    for i, v in enumerate(c.values)], np.int32)
    return CpuCol(T.INT, out, np.ones(n, np.bool_))


def _arr_index(e, cols, n, ansi, one_based):
    a, k = _kids(e, cols, n, ansi)
    et = e.dataType
    out_vals = []
    validity = a.validity & k.validity
    for i in range(n):
        if not validity[i]:
            out_vals.append(None)
            continue
        v = a.values[i]
        idx = int(k.values[i])
        if one_based:
            if idx == 0:
                out_vals.append(None)
                validity[i] = False
                continue
            idx = idx - 1 if idx > 0 else len(v) + idx
        if not (0 <= idx < len(v)) or v[idx] is None:
            out_vals.append(None)
            validity[i] = False
        else:
            out_vals.append(v[idx])
    if isinstance(et, T.StringType):
        arr = np.empty(n, object)
        for i, x in enumerate(out_vals):
            arr[i] = x
        return CpuCol(et, arr, validity)
    arr = np.array([x if x is not None else 0 for x in out_vals],
                   T.storage_dtype(et))
    return CpuCol(et, arr, validity)


def _h_get_array_item(e, cols, n, ansi):
    return _arr_index(e, cols, n, ansi, one_based=False)


def _h_element_at(e, cols, n, ansi):
    if isinstance(e.children[0]._dataType, T.MapType):
        return _h_get_map_value(e, cols, n, ansi)
    return _arr_index(e, cols, n, ansi, one_based=True)


def _h_array_contains(e, cols, n, ansi):
    a, v = _kids(e, cols, n, ansi)
    out = np.zeros(n, np.bool_)
    validity = a.validity & v.validity
    for i in range(n):
        if not validity[i]:
            continue
        arr = a.values[i]
        found = any(x is not None and x == v.values[i] for x in arr)
        out[i] = found
        if not found and any(x is None for x in arr):
            validity[i] = False
    return CpuCol(T.BOOLEAN, out, validity)


def _h_create_array(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        vals[i] = [k.row(i) for k in kids]
    return CpuCol(e.dataType, vals, np.ones(n, np.bool_))


def _h_array_minmax(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    mx = type(e).__name__ == "ArrayMax"
    et = e.dataType
    out_vals = []
    validity = c.validity.copy()
    for i in range(n):
        if not c.validity[i]:
            out_vals.append(None)
            continue
        xs = [x for x in c.values[i] if x is not None]
        if not xs:
            out_vals.append(None)
            validity[i] = False
        else:
            out_vals.append(max(xs) if mx else min(xs))
    arr = np.array([x if x is not None else 0 for x in out_vals],
                   T.storage_dtype(et))
    return CpuCol(et, arr, validity)


def _h_udf(e, cols, n, ansi):
    """Row-based UDF evaluation — the CPU truth (reference: the original
    Scala UDF body that RapidsUDF accelerates)."""
    kids = _kids(e, cols, n, ansi)
    out_vals = []
    validity = np.ones(n, np.bool_)
    from spark_rapids_tpu.udf_compiler import F, _wants_namespace

    wants_f = _wants_namespace(e.fn)
    if getattr(e, "vectorized", False):
        # pandas-style: whole columns in storage representation (mirrors
        # UserDefinedExpression._eval_python's vectorized branch)
        ins = []
        for k in kids:
            if k.values.dtype == object:
                ins.append(np.array(k.to_pylist(), dtype=object))
            else:
                ins.append(k.values)
        res = np.asarray(e.fn(*ins))
        mask = np.ones(n, np.bool_)
        for k in kids:
            mask &= k.validity
        out_vals = [res[i].item() if mask[i] else None for i in range(n)]
        validity = mask.copy()
        for i in range(n):
            if out_vals[i] is None:
                validity[i] = False
        dt = e.dataType
        return _udf_results_to_col(out_vals, validity, dt, n)
    # python UDFs receive CONVERTED python values (dates as datetime.date,
    # decimals as Decimal, plain python ints — NOT numpy storage scalars),
    # exactly like pyspark and the device arrow-eval path
    pylists = [k.to_pylist() for k in kids]
    for i in range(n):
        args = [p[i] for p in pylists]
        v = e.fn(*args, F) if wants_f else e.fn(*args)
        v = _clamp_udf_result(v, e.dataType)
        if v is None:
            validity[i] = False
        out_vals.append(v)
    dt = e.dataType
    return _udf_results_to_col(out_vals, validity, dt, n)


_INT_BOUNDS = {T.ByteType: 2**7, T.ShortType: 2**15, T.IntegerType: 2**31,
               T.LongType: 2**63}


def _clamp_udf_result(v, dt):
    """Results outside the declared type's range become NULL (pyspark's
    serializer behavior)."""
    bound = _INT_BOUNDS.get(type(dt))
    if bound is not None and v is not None:
        if not isinstance(v, int) or not (-bound <= v < bound):
            return None
    return v


def _udf_results_to_col(out_vals, validity, dt, n):
    out_vals = [_clamp_udf_result(v, dt) for v in out_vals]
    for i, v in enumerate(out_vals):
        if v is None:
            validity[i] = False
    if isinstance(dt, (T.StringType, T.DecimalType)):
        arr = np.array([v if v is not None else None for v in out_vals],
                       object)
    else:
        arr = np.array([v if v is not None else 0 for v in out_vals],
                       T.storage_dtype(dt))
    return CpuCol(dt, arr, validity)


def _java_replacement_to_python(r: str) -> str:
    """Java replacement -> python re template: $n -> \\n (group ref),
    \\$ -> literal $, literal backslashes doubled."""
    out = []
    i = 0
    while i < len(r):
        ch = r[i]
        if ch == "\\" and i + 1 < len(r):
            nxt = r[i + 1]
            out.append("$" if nxt == "$" else "\\\\" + nxt)
            i += 2
        elif ch == "$" and i + 1 < len(r) and r[i + 1].isdigit():
            out.append("\\" + r[i + 1])
            i += 2
        elif ch == "\\":
            out.append("\\\\")
            i += 1
        else:
            out.append(ch)
            i += 1
    return "".join(out)


def _h_regexp_replace(e, cols, n, ansi):
    import re as _re

    c = eval_expr(e.children[0], cols, n, ansi)
    pat = _re.compile(_java_regex_to_python(str(e.children[1].value)))
    repl = _java_replacement_to_python(str(e.children[2].value))
    out = np.array([pat.sub(repl, v) if v is not None else None
                    for v in c.values], object)
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_regexp_extract(e, cols, n, ansi):
    import re as _re

    c = eval_expr(e.children[0], cols, n, ansi)
    pat = _re.compile(_java_regex_to_python(str(e.children[1].value)))
    idx = int(e.children[2].value)
    out = []
    for v in c.values:
        if v is None:
            out.append(None)
            continue
        m = pat.search(v)
        out.append((m.group(idx) or "") if m else "")
    return CpuCol(T.STRING, np.array(out, object), c.validity.copy())


def _h_octetbit(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    mult = 8 if type(e).__name__ == "BitLength" else 1
    out = np.array([len(v.encode("utf-8")) * mult if v is not None else 0
                    for v in c.values], np.int32)
    return CpuCol(T.INT, out, c.validity.copy())


def _h_leftright(e, cols, n, ansi):
    s, k = _kids(e, cols, n, ansi)
    left = type(e).__name__ == "StringLeft"
    validity = s.validity & k.validity
    out = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            out[i] = None
            continue
        v = s.values[i]
        kk = int(k.values[i])
        if kk <= 0:
            out[i] = ""
        else:
            out[i] = v[:kk] if left else v[-kk:] if kk <= len(v) else v
    return CpuCol(T.STRING, out, validity)


def _h_substring_index(e, cols, n, ansi):
    s, d, k = _kids(e, cols, n, ansi)
    validity = s.validity & d.validity & k.validity
    out = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            out[i] = None
            continue
        v, delim, cnt = s.values[i], d.values[i], int(k.values[i])
        if cnt == 0 or not delim:
            out[i] = ""
            continue
        if cnt > 0:
            pos = 0
            found = 0
            while found < cnt:
                j = v.find(delim, pos)
                if j < 0:
                    break
                found += 1
                pos = j + len(delim)
            out[i] = v if found < cnt else v[: pos - len(delim)]
        else:
            pos = len(v)
            found = 0
            while found < -cnt:
                j = v.rfind(delim, 0, pos)
                if j < 0:
                    break
                found += 1
                pos = j
            out[i] = v if found < -cnt else v[pos + len(delim):]
    return CpuCol(T.STRING, out, validity)


# -- string breadth ---------------------------------------------------------

def _h_reverse(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.array([v[::-1] if v is not None else None for v in c.values],
                   object)
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_initcap(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)

    def tx(s):
        if s is None:
            return None
        out = []
        prev_space = True
        for ch in s:
            if prev_space and "a" <= ch <= "z":
                out.append(chr(ord(ch) - 32))
            elif not prev_space and "A" <= ch <= "Z":
                out.append(chr(ord(ch) + 32))
            else:
                out.append(ch)
            prev_space = ch == " "
        return "".join(out)

    return CpuCol(T.STRING, np.array([tx(v) for v in c.values], object),
                  c.validity.copy())


def _h_ascii(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.array([(ord(v[0]) if v else 0)
                    if v is not None else 0 for v in c.values], np.int32)
    return CpuCol(T.INT, out, c.validity.copy())


def _h_chr(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)

    def tx(v):
        if v is None:
            return None
        lv = int(v)
        if lv < 0:
            return ""
        return chr(lv % 256)

    return CpuCol(T.STRING, np.array([tx(v) for v in c.values], object),
                  c.validity.copy())


def _h_replace(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    c, se, re_ = kids
    validity = _null_prop_validity(kids)
    out = []
    for i in range(n):
        if not validity[i]:
            out.append(None)
            continue
        s, search, rep = c.values[i], se.values[i], re_.values[i]
        out.append(s if search == "" else s.replace(search, rep))
    return CpuCol(T.STRING, np.array(out, object), validity)


def _h_translate(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    c, f, t = kids
    validity = _null_prop_validity(kids)
    out = []
    for i in range(n):
        if not validity[i]:
            out.append(None)
            continue
        frm, to = f.values[i], t.values[i]
        table = {}
        for j, ch in enumerate(frm):
            if ch not in table:
                table[ch] = to[j] if j < len(to) else None
        out.append("".join(table.get(ch, ch) for ch in c.values[i]
                           if table.get(ch, ch) is not None))
    return CpuCol(T.STRING, np.array(out, object), validity)


def _h_instr(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    s, sub = kids
    validity = _null_prop_validity(kids)
    out = np.array([(s.values[i].find(sub.values[i]) + 1)
                    if validity[i] else 0 for i in range(n)], np.int32)
    return CpuCol(T.INT, out, validity)


def _h_locate(e, cols, n, ansi):
    sub, s, st = _kids(e, cols, n, ansi)
    validity = s.validity & sub.validity
    out = np.zeros(n, np.int32)
    for i in range(n):
        if not validity[i]:
            continue
        if not st.validity[i] or int(st.values[i]) < 1:
            out[i] = 0  # Spark: null start or start < 1 -> 0, stays valid
            continue
        frm = int(st.values[i]) - 1
        if sub.values[i] == "":
            out[i] = 1  # UTF8String.indexOf("") is 0 regardless of start
        else:
            out[i] = s.values[i].find(sub.values[i], frm) + 1
    return CpuCol(T.INT, out, validity)


def _pad_str(s, target, pad, left):
    if target <= 0:
        return ""
    if len(s) >= target:
        return s[:target]
    need = target - len(s)
    fill = (pad * (need // len(pad) + 1))[:need] if pad else ""
    return (fill + s) if left else (s + fill)


def _h_pad(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    c, ln, p = kids
    validity = _null_prop_validity(kids)
    left = type(e).__name__ == "StringLPad"
    out = [(_pad_str(c.values[i], int(ln.values[i]), p.values[i], left)
            if validity[i] else None) for i in range(n)]
    return CpuCol(T.STRING, np.array(out, object), validity)


def _h_repeat(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    c, r = kids
    validity = _null_prop_validity(kids)
    out = [(c.values[i] * max(int(r.values[i]), 0)
            if validity[i] else None) for i in range(n)]
    return CpuCol(T.STRING, np.array(out, object), validity)


def _h_concat_ws(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    sep = kids[0]
    out = []
    for i in range(n):
        if not sep.validity[i]:  # Spark: null separator -> NULL result
            out.append(None)
            continue
        pieces = [c.values[i] for c in kids[1:] if c.validity[i]]
        out.append(sep.values[i].join(pieces))
    return CpuCol(T.STRING, np.array(out, object), sep.validity.copy())


# -- hash functions (exact ports of Spark Murmur3_x86_32 / XXH64) -----------

_M32 = 0xFFFFFFFF
_M64 = 0xFFFFFFFFFFFFFFFF


def _mm3_mix_k1(k1):
    k1 = (k1 * 0xCC9E2D51) & _M32
    k1 = ((k1 << 15) | (k1 >> 17)) & _M32
    return (k1 * 0x1B873593) & _M32


def _mm3_mix_h1(h1, k1):
    h1 ^= k1
    h1 = ((h1 << 13) | (h1 >> 19)) & _M32
    return (h1 * 5 + 0xE6546B64) & _M32


def _mm3_fmix(h1, length):
    h1 ^= length
    h1 ^= h1 >> 16
    h1 = (h1 * 0x85EBCA6B) & _M32
    h1 ^= h1 >> 13
    h1 = (h1 * 0xC2B2AE35) & _M32
    return h1 ^ (h1 >> 16)


def _mm3_update(kind, x, seed):
    if kind == "int":
        return _mm3_fmix(_mm3_mix_h1(seed, _mm3_mix_k1(x & _M32)), 4)
    if kind == "long":
        x &= _M64
        h = _mm3_mix_h1(seed, _mm3_mix_k1(x & _M32))
        h = _mm3_mix_h1(h, _mm3_mix_k1(x >> 32))
        return _mm3_fmix(h, 8)
    bs = x
    h = seed
    aligned = (len(bs) // 4) * 4
    for i in range(0, aligned, 4):
        block = bs[i] | bs[i + 1] << 8 | bs[i + 2] << 16 | bs[i + 3] << 24
        h = _mm3_mix_h1(h, _mm3_mix_k1(block))
    for i in range(aligned, len(bs)):
        b = bs[i]
        sb = b if b < 128 else b | 0xFFFFFF00
        h = _mm3_mix_h1(h, _mm3_mix_k1(sb))
    return _mm3_fmix(h, len(bs))


_XP1 = 0x9E3779B185EBCA87
_XP2 = 0xC2B2AE3D27D4EB4F
_XP3 = 0x165667B19E3779F9
_XP4 = 0x85EBCA77C2B2AE63
_XP5 = 0x27D4EB2F165667C5


def _xrotl(x, r):
    return ((x << r) | (x >> (64 - r))) & _M64


def _xfmix(h):
    h ^= h >> 33
    h = (h * _XP2) & _M64
    h ^= h >> 29
    h = (h * _XP3) & _M64
    return h ^ (h >> 32)


def _xxh_update(kind, x, seed):
    if kind == "int":
        h = (seed + _XP5 + 4) & _M64
        h ^= ((x & _M32) * _XP1) & _M64
        h = (_xrotl(h, 23) * _XP2 + _XP3) & _M64
        return _xfmix(h)
    if kind == "long":
        x &= _M64
        h = (seed + _XP5 + 8) & _M64
        h ^= (_xrotl((x * _XP2) & _M64, 31) * _XP1) & _M64
        h = (_xrotl(h, 27) * _XP1 + _XP4) & _M64
        return _xfmix(h)
    bs = x
    n = len(bs)
    if n >= 32:
        v1 = (seed + _XP1 + _XP2) & _M64
        v2 = (seed + _XP2) & _M64
        v3 = seed & _M64
        v4 = (seed - _XP1) & _M64
        o = 0
        while o <= n - 32:
            vs = []
            for j, v in enumerate((v1, v2, v3, v4)):
                k = int.from_bytes(bs[o + 8 * j:o + 8 * j + 8], "little")
                vs.append((_xrotl((v + k * _XP2) & _M64, 31) * _XP1) & _M64)
            v1, v2, v3, v4 = vs
            o += 32
        h = (_xrotl(v1, 1) + _xrotl(v2, 7) + _xrotl(v3, 12)
             + _xrotl(v4, 18)) & _M64
        for v in (v1, v2, v3, v4):
            h = ((h ^ (_xrotl((v * _XP2) & _M64, 31) * _XP1 & _M64))
                 * _XP1 + _XP4) & _M64
    else:
        h = (seed + _XP5) & _M64
        o = 0
    h = (h + n) & _M64
    while o <= n - 8:
        k = int.from_bytes(bs[o:o + 8], "little")
        h = (_xrotl(h ^ ((_xrotl((k * _XP2) & _M64, 31) * _XP1) & _M64), 27)
             * _XP1 + _XP4) & _M64
        o += 8
    if o <= n - 4:
        k = int.from_bytes(bs[o:o + 4], "little")
        h = (_xrotl(h ^ ((k * _XP1) & _M64), 23) * _XP2 + _XP3) & _M64
        o += 4
    while o < n:
        h = (_xrotl(h ^ ((bs[o] * _XP5) & _M64), 11) * _XP1) & _M64
        o += 1
    return _xfmix(h)


def _hash_input(dt: T.DataType, v):
    """-> (kind, value) matching Spark HashExpression's per-type encoding."""
    if isinstance(dt, T.StringType):
        return "bytes", v.encode("utf-8")
    if isinstance(dt, T.FloatType):
        f = np.float32(v)
        if f == 0.0:
            f = np.float32(0.0)
        bits = (0x7FC00000 if np.isnan(f)
                else int(f.view(np.int32)))
        return "int", bits
    if isinstance(dt, T.DoubleType):
        d = np.float64(v)
        if d == 0.0:
            d = np.float64(0.0)
        bits = (0x7FF8000000000000 if np.isnan(d)
                else int(d.view(np.int64)))
        return "long", bits
    if isinstance(dt, (T.LongType, T.TimestampType, T.DecimalType)):
        return "long", int(v)
    if isinstance(dt, T.BooleanType):
        return "int", 1 if v else 0
    return "int", int(v)  # byte/short/int/date


def _h_hashexpr(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    xx = type(e).__name__ == "XxHash64"
    out = np.zeros(n, np.int64 if xx else np.int32)
    for i in range(n):
        h = e.seed & (_M64 if xx else _M32)
        for c in kids:
            if not c.validity[i]:
                continue
            kind, x = _hash_input(c.dtype, c.values[i])
            h = _xxh_update(kind, x, h) if xx else _mm3_update(kind, x, h)
        if xx:
            out[i] = h - (1 << 64) if h >= (1 << 63) else h
        else:
            out[i] = h - (1 << 32) if h >= (1 << 31) else h
    return CpuCol(e.dataType, out, np.ones(n, np.bool_))


def _h_utc_shift(e, cols, n, ansi):
    """from/to_utc_timestamp via python zoneinfo — independent of the
    device path's raw TZif tables."""
    import datetime as pydt
    from zoneinfo import ZoneInfo

    ts, tzc = _kids(e, cols, n, ansi)
    to_utc = type(e).__name__ == "ToUTCTimestamp"
    validity = ts.validity & tzc.validity
    out = np.zeros(n, np.int64)
    zi_cache = {}
    for i in range(n):
        if not validity[i]:
            continue
        tz = tzc.values[i]
        zi = zi_cache.get(tz)
        if zi is None:
            zi = zi_cache[tz] = ZoneInfo(tz)
        us = int(ts.values[i])
        if to_utc:
            wall = (pydt.datetime(1970, 1, 1)
                    + pydt.timedelta(microseconds=us))
            off = wall.replace(tzinfo=zi, fold=0).utcoffset()
        else:
            inst = pydt.datetime.fromtimestamp(us // 1_000_000,
                                               tz=pydt.timezone.utc)
            # astimezone: offset AT THE INSTANT (tzinfo.utcoffset(dt)
            # alone would treat dt's fields as wall time)
            off = inst.astimezone(zi).utcoffset()
        shift = int(off.total_seconds()) * 1_000_000
        out[i] = us - shift if to_utc else us + shift
    return CpuCol(T.TIMESTAMP, out, validity)


# -- misc breadth: digests, encodings, url, soundex, ids ---------------------

def _str_map_handler(fn):
    def h(e, cols, n, ansi):
        kids = _kids(e, cols, n, ansi)
        s = kids[0]
        out = np.empty(n, object)
        validity = _null_prop_validity(kids)
        for i in range(n):
            if validity[i]:
                out[i] = fn(e, s.values[i], [k.values[i] for k in kids[1:]])
                if out[i] is None:
                    validity[i] = False
        return CpuCol.from_objs(list(out), T.STRING)

    return h


def _o_md5(e, s, _):
    import hashlib

    return hashlib.md5(s.encode()).hexdigest()


def _o_sha1(e, s, _):
    import hashlib

    return hashlib.sha1(s.encode()).hexdigest()


def _o_sha2(e, s, extra):
    import hashlib

    algo = {0: "sha256", 224: "sha224", 256: "sha256", 384: "sha384",
            512: "sha512"}.get(int(extra[0]) if extra[0] is not None
                               else -1)
    if algo is None:
        return None
    return getattr(hashlib, algo)(s.encode()).hexdigest()


def _h_crc32(e, cols, n, ansi):
    import zlib

    (s,) = _kids(e, cols, n, ansi)
    out = np.zeros(n, np.int64)
    for i in range(n):
        if s.validity[i]:
            out[i] = zlib.crc32(s.values[i].encode())
    return CpuCol(T.LONG, out, s.validity.copy())


def _o_base64(e, s, _):
    import base64 as b64

    return b64.b64encode(s.encode()).decode()


def _o_unbase64(e, s, _):
    import base64 as b64

    try:
        return b64.b64decode(s.encode(), validate=False).decode(
            "utf-8", "replace")
    except Exception:
        return None


def _o_encode(e, s, extra):
    try:
        return s.encode(str(extra[0]).lower()).decode("utf-8", "replace")
    except (UnicodeError, LookupError, TypeError):
        return None


def _o_decode(e, s, extra):
    try:
        return s.encode("utf-8").decode(str(extra[0]).lower())
    except (UnicodeError, LookupError, TypeError):
        return None


def _h_hex(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    for i in range(n):
        if not c.validity[i]:
            continue
        if isinstance(c.dtype, T.StringType):
            out[i] = c.values[i].encode().hex().upper()
        else:
            out[i] = format(int(c.values[i]) & 0xFFFFFFFFFFFFFFFF, "X")
    return CpuCol.from_objs(list(out), T.STRING)


def _o_unhex(e, s, _):
    if len(s) % 2:
        s = "0" + s
    try:
        return bytes.fromhex(s).decode("utf-8", "replace")
    except ValueError:
        return None


def _h_bin(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    for i in range(n):
        if c.validity[i]:
            out[i] = format(int(c.values[i]) & 0xFFFFFFFFFFFFFFFF, "b")
    return CpuCol.from_objs(list(out), T.STRING)


def _o_conv(e, s, extra):
    from spark_rapids_tpu.expr.misc import _conv_str

    if extra[0] is None or extra[1] is None:
        return None
    return _conv_str(s, int(extra[0]), int(extra[1]))


def _h_format_number(e, cols, n, ansi):
    import decimal as pydec

    c, d = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    validity = c.validity & d.validity
    for i in range(n):
        if not validity[i]:
            continue
        dd = int(d.values[i])
        if dd < 0:
            validity[i] = False
            continue
        if isinstance(c.dtype, T.DecimalType):
            v = pydec.Decimal(int(c.values[i])).scaleb(-c.dtype.scale)
        elif isinstance(c.dtype, (T.FloatType, T.DoubleType)):
            fv = float(c.values[i])
            if math.isnan(fv) or math.isinf(fv):
                # Java DecimalFormat renders the NaN / infinity glyphs
                out[i] = ("NaN" if math.isnan(fv)
                          else ("∞" if fv > 0 else "-∞"))
                continue
            v = pydec.Decimal(repr(fv))
        else:
            v = pydec.Decimal(int(c.values[i]))
        with pydec.localcontext() as lctx:
            lctx.prec = 400  # 1e308 doubles need headroom to quantize
            q = v.quantize(pydec.Decimal(1).scaleb(-dd),
                           rounding=pydec.ROUND_HALF_EVEN)
        out[i] = f"{q:,.{dd}f}"
    col = CpuCol.from_objs(list(out), T.STRING)
    col.validity &= validity
    return col


def _o_parse_url(e, s, extra):
    from spark_rapids_tpu.expr.misc import _URL_PARTS, _parse_url_part

    part = extra[0] if extra else None
    key = extra[1] if len(extra) > 1 else None
    if part not in _URL_PARTS:
        return None
    return _parse_url_part(s, part, key)


def _o_soundex(e, s, _):
    from spark_rapids_tpu.expr.misc import _soundex_str

    return _soundex_str(s)


def _h_levenshtein(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    validity = a.validity & b.validity
    out = np.zeros(n, np.int32)
    for i in range(n):
        if not validity[i]:
            continue
        x, y = a.values[i].encode(), b.values[i].encode()
        prev = list(range(len(y) + 1))
        for ii, cx in enumerate(x, 1):
            cur = [ii]
            for jj, cy in enumerate(y, 1):
                cur.append(min(prev[jj] + 1, cur[-1] + 1,
                               prev[jj - 1] + (cx != cy)))
            prev = cur
        out[i] = prev[-1]
    return CpuCol(T.INT, out, validity)


def _h_mono_id(e, cols, n, ansi):
    return CpuCol(T.LONG, np.arange(n, dtype=np.int64),
                  np.ones(n, np.bool_))


def _h_partition_id(e, cols, n, ansi):
    return CpuCol(T.INT, np.zeros(n, np.int32), np.ones(n, np.bool_))


def _h_rand(e, cols, n, ansi):
    # same splitmix64 spec as the device path (a PRNG stream is a spec,
    # not semantics to cross-check; NOT Spark's XORShiftRandom)
    from spark_rapids_tpu.expr.misc import Rand as _DevRand

    z = _DevRand._u64_for_rows(e.seed, 0, n)
    vals = (z >> np.uint64(11)).astype(np.float64) / float(1 << 53)
    return CpuCol(T.DOUBLE, vals, np.ones(n, np.bool_))


def _h_raise_error(e, cols, n, ansi):
    (m,) = _kids(e, cols, n, ansi)
    for i in range(n):
        if m.validity[i]:
            raise RuntimeError(f"raise_error: {m.values[i]}")
    return CpuCol(T.NULL, np.zeros(n, np.int32), np.zeros(n, np.bool_))


def _h_bloom_might_contain(e, cols, n, ansi):
    bloom, v = _kids(e, cols, n, ansi)
    import math as _math

    k = max(1, round(e.num_bits / e.num_items * _math.log(2)))
    out = np.zeros(n, np.bool_)
    validity = bloom.validity & v.validity
    for i in range(n):
        if not validity[i]:
            continue
        words = bloom.values[i]
        h1 = _wrap64(_oracle_xxh64(v.dtype, v.values[i], 42))
        h2 = _wrap64(_oracle_xxh64(v.dtype, v.values[i], 77))
        hit = True
        for j in range(k):
            bit = _wrap64(h1 + j * h2) % e.num_bits
            if not (int(words[bit // 64]) >> (bit % 64)) & 1:
                hit = False
                break
        out[i] = hit
    return CpuCol(T.BOOLEAN, out, validity)


def _h_string_split(e, cols, n, ansi):
    import re as _re

    kids = _kids(e, cols, n, ansi)
    s = kids[0]
    pat = e._pattern
    limit = e._limit
    try:
        rx = _re.compile(_java_regex_to_python(pat)) if pat else None
    except _re.error:
        rx = None
    vals = np.empty(n, object)
    validity = s.validity.copy()
    from spark_rapids_tpu.expr.strings import _java_split

    for i in range(n):
        if not validity[i] or rx is None:
            validity[i] = False
            continue
        vals[i] = _java_split(rx, s.values[i], limit)
    return CpuCol(e.dataType, vals, validity)


def _h_array_join(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    arr, delim = kids[0], kids[1]
    rep = kids[2] if len(kids) > 2 else None
    validity = arr.validity & delim.validity
    out = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        r = rep.row(i) if rep is not None else None
        parts = [e2 if e2 is not None else r for e2 in arr.values[i]]
        out[i] = delim.values[i].join(p for p in parts if p is not None)
    return CpuCol.from_objs(list(out), T.STRING)


# -- collection breadth ------------------------------------------------------

def _nan_eq(a, b):
    """SQL set-op equality incl. NaN == NaN."""
    import math

    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) and math.isnan(b):
            return True
    return a == b


def _null_aware_eq(a, b):
    if a is None or b is None:
        return a is None and b is None
    return _nan_eq(a, b)


def _h_array_position(e, cols, n, ansi):
    a, v = _kids(e, cols, n, ansi)
    validity = a.validity & v.validity
    out = np.zeros(n, np.int64)
    for i in range(n):
        if not validity[i]:
            continue
        for j, x in enumerate(a.values[i]):
            if x is not None and _nan_eq(x, v.values[i]):
                out[i] = j + 1
                break
    return CpuCol(T.LONG, out, validity)


def _h_array_remove(e, cols, n, ansi):
    a, v = _kids(e, cols, n, ansi)
    validity = a.validity & v.validity
    vals = np.empty(n, object)
    for i in range(n):
        if validity[i]:
            vals[i] = [x for x in a.values[i]
                       if x is None or not _nan_eq(x, v.values[i])]
    return CpuCol(e.dataType, vals, validity)


def _distinct_list(xs):
    out = []
    for x in xs:
        if not any(_null_aware_eq(x, y) for y in out):
            out.append(x)
    return out


def _h_array_distinct(e, cols, n, ansi):
    (a,) = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if a.validity[i]:
            vals[i] = _distinct_list(a.values[i])
    return CpuCol(e.dataType, vals, a.validity.copy())


def _h_arrays_overlap(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    validity = a.validity & b.validity
    out = np.zeros(n, np.bool_)
    for i in range(n):
        if not validity[i]:
            continue
        xs, ys = a.values[i], b.values[i]
        hit = any(x is not None and any(
            y is not None and _nan_eq(x, y) for y in ys) for x in xs)
        out[i] = hit
        if (not hit and xs and ys
                and (any(x is None for x in xs)
                     or any(y is None for y in ys))):
            validity[i] = False
    return CpuCol(T.BOOLEAN, out, validity)


def _h_array_union(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    validity = a.validity & b.validity
    vals = np.empty(n, object)
    for i in range(n):
        if validity[i]:
            vals[i] = _distinct_list(list(a.values[i]) + list(b.values[i]))
    return CpuCol(e.dataType, vals, validity)


def _h_array_intersect(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    validity = a.validity & b.validity
    vals = np.empty(n, object)
    for i in range(n):
        if validity[i]:
            vals[i] = [x for x in _distinct_list(a.values[i])
                       if any(_null_aware_eq(x, y) for y in b.values[i])]
    return CpuCol(e.dataType, vals, validity)


def _h_array_except(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    validity = a.validity & b.validity
    vals = np.empty(n, object)
    for i in range(n):
        if validity[i]:
            vals[i] = [x for x in _distinct_list(a.values[i])
                       if not any(_null_aware_eq(x, y)
                                  for y in b.values[i])]
    return CpuCol(e.dataType, vals, validity)


def _h_slice(e, cols, n, ansi):
    a, st, ln = _kids(e, cols, n, ansi)
    validity = a.validity & st.validity & ln.validity
    vals = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        s, k = int(st.values[i]), int(ln.values[i])
        if s == 0:
            raise RuntimeError(
                "Unexpected value for start in function slice: SQL array "
                "indices start at 1.")
        if k < 0:
            raise RuntimeError(
                "Unexpected value for length in function slice: length "
                "must be greater than or equal to 0.")
        xs = a.values[i]
        start0 = s - 1 if s > 0 else len(xs) + s
        vals[i] = [] if start0 < 0 else xs[start0:start0 + k]
    return CpuCol(e.dataType, vals, validity)


def _h_sort_array(e, cols, n, ansi):
    import math

    a, _ = _kids(e, cols, n, ansi)
    asc = True
    if isinstance(e.children[1], E.Literal):
        asc = bool(e.children[1].value)
    vals = np.empty(n, object)

    def key(x):
        if isinstance(x, float) and math.isnan(x):
            return (1, 0.0)  # NaN greatest (Spark)
        return (0, x)

    for i in range(n):
        if a.validity[i]:
            xs = a.values[i]
            nulls = [x for x in xs if x is None]
            rest = sorted((x for x in xs if x is not None), key=key,
                          reverse=not asc)
            vals[i] = (nulls + rest) if asc else (rest + nulls)
    return CpuCol(e.dataType, vals, a.validity.copy())


def _h_array_repeat(e, cols, n, ansi):
    v, k = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    validity = k.validity.copy()
    for i in range(n):
        if validity[i]:
            count = max(int(k.values[i]), 0)
            vals[i] = [v.row(i)] * count
    return CpuCol(e.dataType, vals, validity)


def _h_sequence(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    validity = _null_prop_validity(kids)
    vals = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        start, stop = int(kids[0].values[i]), int(kids[1].values[i])
        if len(kids) > 2:
            step = int(kids[2].values[i])
        else:
            step = 1 if stop >= start else -1
        if step == 0 or (stop > start and step < 0) or \
                (stop < start and step > 0):
            raise RuntimeError("Illegal sequence boundaries")
        count = (stop - start) // step + 1
        vals[i] = [start + j * step for j in range(count)]
    return CpuCol(e.dataType, vals, validity)


def _h_create_map(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        d = {}
        for k in range(0, len(kids), 2):
            key = kids[k].row(i)
            if key is None:
                raise RuntimeError("Cannot use null as map key")
            if any(_nan_eq(key, existing) for existing in d):
                raise RuntimeError("Duplicate map key was found")
            d[key] = kids[k + 1].row(i)
        vals[i] = d
    return CpuCol(e.dataType, vals, np.ones(n, np.bool_))


def _h_map_keys(e, cols, n, ansi):
    (m,) = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if m.validity[i]:
            vals[i] = list(m.values[i].keys())
    return CpuCol(e.dataType, vals, m.validity.copy())


def _h_map_values(e, cols, n, ansi):
    (m,) = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if m.validity[i]:
            vals[i] = list(m.values[i].values())
    return CpuCol(e.dataType, vals, m.validity.copy())


def _h_get_map_value(e, cols, n, ansi):
    m, k = _kids(e, cols, n, ansi)
    validity = m.validity & k.validity
    objs = []
    for i in range(n):
        if not validity[i]:
            objs.append(None)
            continue
        hit = None
        for key, val in m.values[i].items():
            if _nan_eq(key, k.values[i]):
                hit = val
                break
        objs.append(hit)
    return CpuCol.from_objs(objs, e.dataType)


# -- higher-order functions ---------------------------------------------------

def _hof_flatten(e, cols, n, ansi):
    """Evaluate the lambda body over a flattened (row, element) batch."""
    a = eval_expr(e.children[0], cols, n, ansi)
    idx, elems = [], []
    for i in range(n):
        if a.validity[i] and a.values[i] is not None:
            for x in a.values[i]:
                idx.append(i)
                elems.append(x)
    m = len(idx)
    et = e.children[0]._dataType.elementType
    outer = [CpuCol(c.dtype, c.values[idx], c.validity[idx]) for c in cols]
    elem_col = CpuCol.from_objs(elems, et)
    # null elements stay null values (validity False) but rows exist
    res = eval_expr(e.body, outer + [elem_col], m, ansi)
    per_row = [[] for _ in range(n)]
    for k, i in enumerate(idx):
        per_row[i].append(res.row(k))
    return a, per_row


def _h_array_transform(e, cols, n, ansi):
    a, per_row = _hof_flatten(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if a.validity[i]:
            vals[i] = per_row[i]
    return CpuCol(e.dataType, vals, a.validity.copy())


def _h_array_filter(e, cols, n, ansi):
    a, per_row = _hof_flatten(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if a.validity[i]:
            vals[i] = [x for x, keep in zip(a.values[i], per_row[i])
                       if keep is not None and bool(keep)]
    return CpuCol(e.dataType, vals, a.validity.copy())


def _h_array_exists(e, cols, n, ansi):
    a, per_row = _hof_flatten(e, cols, n, ansi)
    out = np.zeros(n, np.bool_)
    validity = a.validity.copy()
    for i in range(n):
        if not a.validity[i]:
            continue
        preds = per_row[i]
        any_true = any(bool(p) for p in preds if p is not None)
        any_null = any(p is None for p in preds)
        out[i] = any_true
        if not any_true and any_null:
            validity[i] = False
    return CpuCol(T.BOOLEAN, out, validity)


def _h_array_forall(e, cols, n, ansi):
    a, per_row = _hof_flatten(e, cols, n, ansi)
    out = np.zeros(n, np.bool_)
    validity = a.validity.copy()
    for i in range(n):
        if not a.validity[i]:
            continue
        preds = per_row[i]
        any_false = any(not bool(p) for p in preds if p is not None)
        any_null = any(p is None for p in preds)
        out[i] = not any_false
        if not any_false and any_null:
            validity[i] = False
    return CpuCol(T.BOOLEAN, out, validity)


def _h_array_aggregate(e, cols, n, ansi):
    a = eval_expr(e.children[0], cols, n, ansi)
    acc = eval_expr(e.children[1], cols, n, ansi)
    maxw = max((len(v) for v in a.values
                if v is not None), default=0)
    for j in range(maxw):
        elems = [a.values[i][j]
                 if (a.validity[i] and a.values[i] is not None
                     and j < len(a.values[i])) else None
                 for i in range(n)]
        elem_col = CpuCol.from_objs(elems, e.children[0]._dataType.elementType)
        merged = eval_expr(e.merge, cols + [acc, elem_col], n, ansi)
        take = np.array([a.validity[i] and a.values[i] is not None
                         and j < len(a.values[i]) for i in range(n)])
        new_vals = acc.values.copy()
        new_valid = acc.validity.copy()
        for i in range(n):
            if take[i]:
                new_vals[i] = merged.values[i]
                new_valid[i] = merged.validity[i]
        acc = CpuCol(merged.dtype, new_vals, new_valid)
    if e.finish is not None:
        acc = eval_expr(e.finish, cols + [acc], n, ansi)
    return CpuCol(acc.dtype, acc.values, acc.validity & a.validity)


# -- JSON + struct expressions ----------------------------------------------
# Independent of the device path: json-module based (the device engine is a
# byte-level state machine in jsonpath.py / native C++), so differential
# tests exercise two implementations.

class _RawNum(str):
    """Number token with its raw source text preserved."""


_JSON_MISSING = object()


def _oracle_parse_json_path(path):
    import re

    if not isinstance(path, str) or not path.startswith("$"):
        return None
    token = re.compile(r"\.([^.\[]+)|\[\s*'([^']*)'\s*\]|\[(\d+)\]")
    out, i = [], 1
    while i < len(path):
        m = token.match(path, i)
        if not m:
            return None
        if m.group(1) is not None:
            if m.group(1) == "*":
                raise NotImplementedError("oracle: wildcard JSON path")
            out.append(m.group(1))
        elif m.group(2) is not None:
            out.append(m.group(2))
        else:
            out.append(int(m.group(3)))
        i = m.end()
    return out


def _oracle_json_loads(s: str):
    import json as _json

    def _reject(_):
        raise ValueError("non-standard constant")

    return _json.loads(s, parse_int=_RawNum, parse_float=_RawNum,
                       parse_constant=_reject)


def _oracle_json_ser(v) -> str:
    import json as _json

    if isinstance(v, _RawNum):
        return str(v)
    if v is None:
        return "null"
    if v is True:
        return "true"
    if v is False:
        return "false"
    if isinstance(v, str):
        return _json.dumps(v, ensure_ascii=False)
    if isinstance(v, list):
        return "[" + ",".join(_oracle_json_ser(x) for x in v) + "]"
    if isinstance(v, dict):
        return "{" + ",".join(
            _json.dumps(k, ensure_ascii=False) + ":" + _oracle_json_ser(x)
            for k, x in v.items()) + "}"
    return _json.dumps(v)


def _oracle_get_json_object(doc, path):
    if doc is None or path is None:
        return None
    steps = _oracle_parse_json_path(path)
    if steps is None:
        return None
    try:
        cur = _oracle_json_loads(doc)
    except ValueError:
        return None
    for s in steps:
        if isinstance(s, str):
            if not isinstance(cur, dict) or s not in cur:
                return None
            cur = cur[s]
        else:
            if not isinstance(cur, list) or s >= len(cur):
                return None
            cur = cur[s]
    if cur is None:
        return None
    if isinstance(cur, _RawNum):
        return str(cur)
    if cur is True:
        return "true"
    if cur is False:
        return "false"
    if isinstance(cur, str):
        return cur
    return _oracle_json_ser(cur)


def _h_get_json_object(e, cols, n, ansi):
    s, p = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    for i in range(n):
        try:
            out[i] = _oracle_get_json_object(s.row(i), p.row(i))
        except NotImplementedError:
            out[i] = None
        except RecursionError:
            out[i] = None
    return CpuCol.from_objs(list(out), T.STRING)


def _h_json_tuple(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    s = kids[0]
    vals = []
    for i in range(n):
        row = []
        doc = s.row(i)
        for k in kids[1:]:
            key = k.row(i)
            if doc is None or key is None:
                row.append(None)
                continue
            try:
                parsed = _oracle_json_loads(doc)
            except ValueError:
                row.append(None)
                continue
            v = parsed.get(key, _JSON_MISSING) if isinstance(
                parsed, dict) else _JSON_MISSING
            if v is _JSON_MISSING or v is None:
                row.append(None)
            elif isinstance(v, _RawNum):
                row.append(str(v))
            elif v is True:
                row.append("true")
            elif v is False:
                row.append("false")
            elif isinstance(v, str):
                row.append(v)
            else:
                row.append(_oracle_json_ser(v))
        vals.append(tuple(row))
    return CpuCol.from_objs(vals, e.dataType)


def _h_json_to_structs(e, cols, n, ansi):
    import json as _json

    s = _kids(e, cols, n, ansi)[0]
    fields = e.schema.fields
    vals = []
    for i in range(n):
        doc = s.row(i)
        if doc is None:
            vals.append(None)
            continue
        try:
            parsed = _json.loads(doc)
        except ValueError:
            parsed = None
        row = []
        if not isinstance(parsed, dict):
            row = [None] * len(fields)
        else:
            for f in fields:
                v = parsed.get(f.name)
                ok, sv = _oracle_convert_json_field(v, f.dataType)
                if not ok:
                    row = [None] * len(fields)
                    break
                row.append(sv)
        vals.append(tuple(row))
    return CpuCol.from_objs(vals, e.schema)


def _oracle_convert_json_field(v, dt):
    # from_json field conversion is DELIBERATELY shared with the device
    # path (expr/jsonexprs.convert_json_field): both sides parse with the
    # stdlib json module, so a separate copy would only invite silent
    # divergence, not independent verification.  The pinned expectations in
    # test_spark_semantics.py are the guard against a shared
    # misunderstanding of Spark's PERMISSIVE rules.
    from spark_rapids_tpu.expr.jsonexprs import convert_json_field

    ok, sv = convert_json_field(v, dt)
    if ok and sv is not None and isinstance(dt, T.FloatType):
        sv = np.float32(sv)
    return ok, sv


def _h_structs_to_json(e, cols, n, ansi):
    import json as _json

    s = _kids(e, cols, n, ansi)[0]
    fields = e.children[0].dataType.fields
    out = []
    for i in range(n):
        v = s.row(i)
        if v is None:
            out.append(None)
            continue
        parts = []
        for k, f in enumerate(fields):
            fv = v[k]
            if fv is None:
                continue
            key = _json.dumps(f.name, ensure_ascii=False)
            if isinstance(f.dataType, T.StringType):
                parts.append(f"{key}:{_json.dumps(fv, ensure_ascii=False)}")
            elif isinstance(f.dataType, T.BooleanType):
                parts.append(f"{key}:{'true' if fv else 'false'}")
            elif isinstance(f.dataType, (T.FloatType, T.DoubleType)):
                parts.append(f"{key}:{_json.dumps(float(fv))}")
            else:
                parts.append(f"{key}:{int(fv)}")
        out.append("{" + ",".join(parts) + "}")
    return CpuCol.from_objs(out, T.STRING)


def _h_get_struct_field(e, cols, n, ansi):
    s = _kids(e, cols, n, ansi)[0]
    k = e._field_ordinal
    ft = e.dataType
    objs = [s.values[i][k]
            if s.validity[i] and s.values[i] is not None else None
            for i in range(n)]
    return CpuCol.from_objs(objs, ft)


def _h_create_named_struct(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    vals = [tuple(k.row(i) for k in kids) for i in range(n)]
    out = CpuCol.from_objs(vals, e.dataType)
    out.validity[:] = True
    return out


def _map_hof_flatten(e, cols, n, ansi):
    """Evaluate a (k, v) lambda body over a flattened map-entry batch."""
    m = eval_expr(e.children[0], cols, n, ansi)
    idx, ks, vs = [], [], []
    for i in range(n):
        if m.validity[i] and m.values[i] is not None:
            for k, v in m.values[i].items():
                idx.append(i)
                ks.append(k)
                vs.append(v)
    cnt = len(idx)
    mt = e.children[0]._dataType
    outer = [CpuCol(c.dtype, c.values[idx], c.validity[idx]) for c in cols]
    kcol = CpuCol.from_objs(ks, mt.keyType)
    vcol = CpuCol.from_objs(vs, mt.valueType)
    res = eval_expr(e.body, outer + [kcol, vcol], cnt, ansi)
    per_row = [[] for _ in range(n)]
    for k, i in enumerate(idx):
        per_row[i].append(res.row(k))
    return m, per_row


def _h_transform_keys(e, cols, n, ansi):
    m, per_row = _map_hof_flatten(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if not m.validity[i]:
            continue
        d = {}
        for nk, v in zip(per_row[i], m.values[i].values()):
            if nk is None:
                raise RuntimeError("Cannot use null as map key")
            if any(_nan_eq(nk, ex) for ex in d):
                raise RuntimeError("Duplicate map key was found")
            d[nk] = v
        vals[i] = d
    return CpuCol(e.dataType, vals, m.validity.copy())


def _h_transform_values(e, cols, n, ansi):
    m, per_row = _map_hof_flatten(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if m.validity[i]:
            vals[i] = dict(zip(m.values[i].keys(), per_row[i]))
    return CpuCol(e.dataType, vals, m.validity.copy())


def _h_map_filter(e, cols, n, ansi):
    m, per_row = _map_hof_flatten(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if m.validity[i]:
            vals[i] = {k: v for (k, v), keep
                       in zip(m.values[i].items(), per_row[i])
                       if keep is not None and bool(keep)}
    return CpuCol(e.dataType, vals, m.validity.copy())


def _h_zip_with(e, cols, n, ansi):
    a = eval_expr(e.children[0], cols, n, ansi)
    b = eval_expr(e.children[1], cols, n, ansi)
    idx, xs, ys = [], [], []
    for i in range(n):
        if a.validity[i] and b.validity[i]:
            la = a.values[i] or []
            lb = b.values[i] or []
            for j in range(max(len(la), len(lb))):
                idx.append(i)
                xs.append(la[j] if j < len(la) else None)
                ys.append(lb[j] if j < len(lb) else None)
    cnt = len(idx)
    outer = [CpuCol(c.dtype, c.values[idx], c.validity[idx]) for c in cols]
    xcol = CpuCol.from_objs(xs, e.children[0]._dataType.elementType)
    ycol = CpuCol.from_objs(ys, e.children[1]._dataType.elementType)
    res = eval_expr(e.body, outer + [xcol, ycol], cnt, ansi)
    per_row = [[] for _ in range(n)]
    for k, i in enumerate(idx):
        per_row[i].append(res.row(k))
    vals = np.empty(n, object)
    validity = a.validity & b.validity
    for i in range(n):
        if validity[i]:
            vals[i] = per_row[i]
    return CpuCol(e.dataType, vals, validity)


def _h_map_from_arrays(e, cols, n, ansi):
    ka, va = _kids(e, cols, n, ansi)
    validity = ka.validity & va.validity
    vals = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        ks = ka.values[i] or []
        vs = va.values[i] or []
        if len(ks) != len(vs):
            raise RuntimeError(
                "key and value arrays must have the same length")
        d = {}
        for k, v in zip(ks, vs):
            if k is None:
                raise RuntimeError("Cannot use null as map key")
            if any(_nan_eq(k, ex) for ex in d):
                raise RuntimeError("Duplicate map key was found")
            d[k] = v
        vals[i] = d
    return CpuCol(e.dataType, vals, validity)


def _h_map_concat(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    validity = _null_prop_validity(kids)
    vals = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        d = {}
        for m in kids:
            for k, v in (m.values[i] or {}).items():
                if any(_nan_eq(k, ex) for ex in d):
                    raise RuntimeError("Duplicate map key was found")
                d[k] = v
        vals[i] = d
    return CpuCol(e.dataType, vals, validity)


def _h_map_contains_key(e, cols, n, ansi):
    m, key = _kids(e, cols, n, ansi)
    validity = m.validity & key.validity
    out = np.zeros(n, np.bool_)
    for i in range(n):
        if validity[i]:
            out[i] = any(_nan_eq(key.row(i), k)
                         for k in (m.values[i] or {}))
    return CpuCol(T.BOOLEAN, out, validity)


def _h_array_compact(e, cols, n, ansi):
    (a,) = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if a.validity[i]:
            vals[i] = [x for x in (a.values[i] or []) if x is not None]
    return CpuCol(e.dataType, vals, a.validity.copy())


def _h_array_append(e, cols, n, ansi):
    a, x = _kids(e, cols, n, ansi)
    prepend = type(e).__name__ == "ArrayPrepend"
    vals = np.empty(n, object)
    for i in range(n):
        if a.validity[i]:
            base = list(a.values[i] or [])
            vals[i] = ([x.row(i)] + base if prepend
                       else base + [x.row(i)])
    return CpuCol(e.dataType, vals, a.validity.copy())


def _h_make_date(e, cols, n, ansi):
    y, m, d = _kids(e, cols, n, ansi)
    validity = y.validity & m.validity & d.validity
    out = np.zeros(n, np.int32)
    for i in range(n):
        if not validity[i]:
            continue
        try:
            yy, mm, dd = int(y.values[i]), int(m.values[i]), int(d.values[i])
            if not (1 <= yy <= 9999):
                raise ValueError
            out[i] = (pydt.date(yy, mm, dd) - pydt.date(1970, 1, 1)).days
        except (ValueError, OverflowError):
            if ansi:
                raise RuntimeError("invalid date in make_date (ANSI)")
            validity[i] = False
    return CpuCol(T.DATE, out, validity)


def _h_make_timestamp(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    validity = _null_prop_validity(kids)
    y, m, d, h, mi, s = kids
    st = e.children[5].dataType
    out = np.zeros(n, np.int64)
    for i in range(n):
        if not validity[i]:
            continue
        try:
            yy, mm, dd = int(y.values[i]), int(m.values[i]), int(d.values[i])
            hh, mmin = int(h.values[i]), int(mi.values[i])
            if isinstance(st, T.DecimalType):
                micros_in_sec = int(s.values[i]) * (10 ** (6 - st.scale))
            elif isinstance(st, (T.FloatType, T.DoubleType)):
                micros_in_sec = int(round(float(s.values[i]) * 1e6))
            else:
                micros_in_sec = int(s.values[i]) * 1_000_000
            if not (1 <= yy <= 9999 and 0 <= hh <= 23 and 0 <= mmin <= 59
                    and 0 <= micros_in_sec <= 60_000_000):
                raise ValueError
            days = (pydt.date(yy, mm, dd) - pydt.date(1970, 1, 1)).days
            out[i] = (days * 86_400_000_000 + hh * 3_600_000_000
                      + mmin * 60_000_000 + micros_in_sec)
        except (ValueError, OverflowError):
            if ansi:
                raise RuntimeError("invalid timestamp in make_timestamp (ANSI)")
            validity[i] = False
    return CpuCol(T.TIMESTAMP, out, validity)


def _h_current(e, cols, n, ansi):
    if type(e).__name__ == "CurrentDate":
        return CpuCol(T.DATE,
                      np.full(n, e.captured_micros // 86_400_000_000,
                              np.int32), np.ones(n, np.bool_))
    return CpuCol(T.TIMESTAMP, np.full(n, e.captured_micros, np.int64),
                  np.ones(n, np.bool_))


def _h_timestamp_units(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    name = type(e).__name__
    validity = c.validity.copy()
    st = e.child.dataType
    out = np.zeros(n, np.int64)
    for i in range(n):
        if not validity[i]:
            continue
        v = c.values[i]
        if name == "TimestampSeconds":
            if isinstance(st, (T.FloatType, T.DoubleType)):
                f = float(v) * 1e6
                if not (math.isfinite(f) and abs(f) < 2.0 ** 63):
                    validity[i] = False
                    continue
                out[i] = int(round(f))
            elif not -9223372036854 <= int(v) <= 9223372036854:
                if ansi:
                    raise RuntimeError("timestamp_seconds overflow (ANSI)")
                validity[i] = False
            else:
                out[i] = int(v) * 1_000_000
        elif name == "TimestampMillis":
            if not -9223372036854775 <= int(v) <= 9223372036854775:
                validity[i] = False
            else:
                out[i] = int(v) * 1_000
        else:
            out[i] = int(v)
    return CpuCol(T.TIMESTAMP, out, validity)


def _h_unix_units(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    name = type(e).__name__
    div = {"UnixSeconds": 1_000_000, "UnixMillis": 1_000,
           "UnixMicros": 1}[name]
    out = np.array([int(v) // div for v in
                    np.where(c.validity, c.values, 0)], np.int64)
    return CpuCol(T.LONG, out, c.validity.copy())


def _h_unix_date(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    name = type(e).__name__
    dt = T.INT if name == "UnixDate" else T.DATE
    return CpuCol(dt, c.values.astype(np.int32), c.validity.copy())


def _h_weekday(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    days = (c.values.astype(np.int64) if isinstance(e.child.dataType,
                                                    T.DateType)
            else c.values.astype(np.int64) // 86_400_000_000)
    return CpuCol(T.INT, ((days + 3) % 7).astype(np.int32),
                  c.validity.copy())


def _h_to_date_ts(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    want_date = type(e).__name__ == "ToDate"
    ct = e.child.dataType
    validity = c.validity.copy()
    out = np.zeros(n, np.int32 if want_date else np.int64)
    for i in range(n):
        if not validity[i]:
            continue
        v = c.values[i]
        if isinstance(ct, T.DateType):
            out[i] = int(v) if want_date else int(v) * 86_400_000_000
        elif isinstance(ct, T.TimestampType):
            out[i] = int(v) // 86_400_000_000 if want_date else int(v)
        else:
            r = _str_to_date_py(v) if want_date else _str_to_ts_py(v)
            if r is None:
                validity[i] = False
            else:
                out[i] = r
    return CpuCol(T.DATE if want_date else T.TIMESTAMP, out, validity)


def _h_regexp_extract_all(e, cols, n, ansi):
    import re as _re

    c = eval_expr(e.children[0], cols, n, ansi)
    pat = _re.compile(_java_regex_to_python(str(e.children[1].value)))
    out = np.empty(n, object)
    for i in range(n):
        v = c.values[i]
        if v is not None and c.validity[i]:
            out[i] = [m for m in pat.findall(v) if m != ""]
    return CpuCol(e.dataType, out, c.validity.copy())


def _h_overlay(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    s, r, p, ln = kids
    validity = _null_prop_validity(kids)
    out = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        sv, rv = str(s.values[i]), str(r.values[i])
        pos0 = int(p.values[i]) - 1
        replen = int(ln.values[i])
        if replen < 0:
            replen = len(rv)
        pre = sv[:max(pos0, 0)][:len(sv)]
        tail = sv[min(max(pos0 + replen, 0), len(sv)):]
        out[i] = pre + rv + tail
    return CpuCol(T.STRING, out, validity)


def _h_find_in_set(e, cols, n, ansi):
    s, lst = _kids(e, cols, n, ansi)
    validity = s.validity & lst.validity
    out = np.zeros(n, np.int32)
    for i in range(n):
        if not validity[i]:
            continue
        sv = str(s.values[i])
        if "," in sv:
            out[i] = 0
            continue
        parts = str(lst.values[i]).split(",")
        out[i] = parts.index(sv) + 1 if sv in parts else 0
    return CpuCol(T.INT, out, validity)


def _h_elt(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    idx = kids[0]
    out = np.empty(n, object)
    validity = np.zeros(n, np.bool_)
    for i in range(n):
        if not idx.validity[i]:
            continue
        k = int(idx.values[i])
        if 1 <= k <= len(kids) - 1 and kids[k].validity[i]:
            out[i] = kids[k].values[i]
            validity[i] = True
    return CpuCol(T.STRING, out, validity)


def _h_space(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    for i in range(n):
        if c.validity[i]:
            out[i] = " " * max(int(c.values[i]), 0)
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_ltrim_rtrim(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    left = type(e).__name__ == "StringTrimLeft"
    out = np.empty(n, object)
    for i in range(n):
        if c.validity[i]:
            v = str(c.values[i])
            out[i] = v.lstrip(" ") if left else v.rstrip(" ")
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_bround(e, cols, n, ansi):
    c, s = _kids(e, cols, n, ansi)
    ct = e.children[0].dataType
    if ct.is_integral:
        return c
    out = np.zeros(n, np.float64)
    validity = c.validity & s.validity
    for i in range(n):
        if validity[i]:
            sc = 10.0 ** int(s.values[i])
            out[i] = np.round(float(c.values[i]) * sc) / sc
    return CpuCol(e.dataType, out, validity)


def _h_width_bucket(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    validity = _null_prop_validity(kids)
    out = np.zeros(n, np.int64)
    for i in range(n):
        if not validity[i]:
            continue
        v, lo, hi = (float(kids[j].values[i]) for j in range(3))
        nb = int(kids[3].values[i])
        if nb <= 0 or not all(math.isfinite(x) for x in (v, lo, hi)) \
                or lo == hi:
            validity[i] = False
            continue
        if lo < hi:
            if v < lo:
                out[i] = 0
            elif v >= hi:
                out[i] = nb + 1
            else:
                out[i] = int((v - lo) / ((hi - lo) / nb)) + 1
        else:
            if v > lo:
                out[i] = 0
            elif v <= hi:
                out[i] = nb + 1
            else:
                out[i] = int((lo - v) / ((lo - hi) / nb)) + 1
        out[i] = min(max(out[i], 0), nb + 1)
    return CpuCol(T.LONG, out, validity)


def _h_factorial(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.zeros(n, np.int64)
    validity = c.validity.copy()
    for i in range(n):
        if validity[i]:
            v = int(c.values[i])
            if 0 <= v <= 20:
                out[i] = math.factorial(v)
            else:
                validity[i] = False
    return CpuCol(T.LONG, out, validity)


def _h_bit_count(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    ct = e.child.dataType
    out = np.zeros(n, np.int32)
    for i in range(n):
        if not c.validity[i]:
            continue
        if isinstance(ct, T.BooleanType):
            out[i] = 1 if c.values[i] else 0
        else:
            # Java widens (sign-extends) before Long.bitCount
            out[i] = bin(int(c.values[i]) & ((1 << 64) - 1)).count("1")
    return CpuCol(T.INT, out, c.validity.copy())


def _h_nvl2(e, cols, n, ansi):
    a, b, c = _kids(e, cols, n, ansi)
    vals = np.where(a.validity, b.values, c.values)
    validity = np.where(a.validity, b.validity, c.validity)
    return CpuCol(e.dataType, vals, validity.astype(np.bool_))


def _h_nullif(e, cols, n, ansi):
    a, b = _kids(e, cols, n, ansi)
    validity = a.validity.copy()
    for i in range(n):
        if a.validity[i] and b.validity[i] \
                and _nan_eq(a.values[i], b.values[i]):
            validity[i] = False
    return CpuCol(e.dataType, a.values.copy(), validity)


def _h_trunc_timestamp(e, cols, n, ansi):
    from spark_rapids_tpu.expr.datetime import TruncTimestamp as _TT

    fmt_c, c = _kids(e, cols, n, ansi)
    unit = str(e.children[0].value).lower() \
        if getattr(e.children[0], "value", None) is not None else ""
    out = np.zeros(n, np.int64)
    validity = c.validity.copy()
    US_DAY = 86_400_000_000
    for i in range(n):
        if not validity[i]:
            continue
        micros = int(c.values[i])
        if unit in _TT._TIME:
            q = _TT._TIME[unit]
            out[i] = (micros // q) * q
        elif unit in _TT._DAY_FMTS:
            days = micros // US_DAY
            dt0 = pydt.date(1970, 1, 1) + pydt.timedelta(days=days)
            u = _TT._DAY_FMTS[unit]
            if u == "year":
                d2 = dt0.replace(month=1, day=1)
            elif u == "quarter":
                d2 = dt0.replace(month=(dt0.month - 1) // 3 * 3 + 1, day=1)
            elif u == "month":
                d2 = dt0.replace(day=1)
            else:
                d2 = dt0 - pydt.timedelta(days=dt0.weekday())
            out[i] = (d2 - pydt.date(1970, 1, 1)).days * US_DAY
        else:
            validity[i] = False
    return CpuCol(T.TIMESTAMP, out, validity)


def _h_timestamp_add(e, cols, n, ansi):
    from spark_rapids_tpu.expr.datetime import TimestampAdd as _TA

    k, c = _kids(e, cols, n, ansi)
    validity = k.validity & c.validity
    out = np.zeros(n, np.int64)
    US_DAY = 86_400_000_000
    for i in range(n):
        if not validity[i]:
            continue
        micros = int(c.values[i])
        kk = int(k.values[i])
        if e.unit in _TA._FIXED:
            out[i] = micros + kk * _TA._FIXED[e.unit]
            continue
        mult = {"month": 1, "quarter": 3, "year": 12}.get(e.unit)
        if mult is None:
            validity[i] = False
            continue
        days = micros // US_DAY
        tod = micros - days * US_DAY
        d0 = pydt.date(1970, 1, 1) + pydt.timedelta(days=days)
        tot = d0.year * 12 + (d0.month - 1) + kk * mult
        ny, nm = tot // 12, tot % 12 + 1
        import calendar

        nd = min(d0.day, calendar.monthrange(ny, nm)[1])
        out[i] = ((pydt.date(ny, nm, nd) - pydt.date(1970, 1, 1)).days
                  * US_DAY + tod)
    return CpuCol(T.TIMESTAMP, out, validity)


def _h_timestamp_diff(e, cols, n, ansi):
    from spark_rapids_tpu.expr.datetime import TimestampAdd as _TA

    a, b = _kids(e, cols, n, ansi)
    validity = a.validity & b.validity
    out = np.zeros(n, np.int64)
    US_DAY = 86_400_000_000
    for i in range(n):
        if not validity[i]:
            continue
        s, t = int(a.values[i]), int(b.values[i])
        fixed = _TA._FIXED.get(e.unit)
        if fixed is not None:
            d = t - s
            out[i] = d // fixed if d >= 0 else -((-d) // fixed)
            continue
        mult = {"month": 1, "quarter": 3, "year": 12}.get(e.unit)
        if mult is None:
            validity[i] = False
            continue
        sd, ed = s // US_DAY, t // US_DAY
        d1 = pydt.date(1970, 1, 1) + pydt.timedelta(days=sd)
        d2 = pydt.date(1970, 1, 1) + pydt.timedelta(days=ed)
        months = (d2.year * 12 + d2.month) - (d1.year * 12 + d1.month)
        stod, etod = s - sd * US_DAY, t - ed * US_DAY
        fwd = t >= s
        short = ((d2.day < d1.day or (d2.day == d1.day and etod < stod))
                 if fwd else
                 (d2.day > d1.day or (d2.day == d1.day and etod > stod)))
        months += (-1 if short and fwd else (1 if short and not fwd else 0))
        out[i] = months // mult if months >= 0 else -((-months) // mult)
    return CpuCol(T.LONG, out, validity)


def _h_convert_timezone(e, cols, n, ansi):
    from spark_rapids_tpu.tzdb import zone_tables

    (c,) = _kids(e, cols, n, ansi)
    tsrc = zone_tables(e.source_tz)
    ttgt = zone_tables(e.target_tz)
    out = np.zeros(n, np.int64)
    for i in range(n):
        if not c.validity[i]:
            continue
        micros = int(c.values[i])
        secs = micros // 1_000_000
        j = np.searchsorted(tsrc["wall_starts"], secs, side="right") - 1
        off1 = int(tsrc["offsets"][max(min(j, len(tsrc["offsets"]) - 1), 0)])
        utc = micros - off1 * 1_000_000
        us = utc // 1_000_000
        j2 = np.searchsorted(ttgt["utc_instants"], us, side="right") - 1
        off2 = int(ttgt["offsets"][max(min(j2, len(ttgt["offsets"]) - 1), 0)])
        out[i] = utc + off2 * 1_000_000
    return CpuCol(T.TIMESTAMP, out, c.validity.copy())


def _h_month_day_name(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    days = _date_of(c, e.child.dataType)
    months = ("Jan", "Feb", "Mar", "Apr", "May", "Jun",
              "Jul", "Aug", "Sep", "Oct", "Nov", "Dec")
    dows = ("Mon", "Tue", "Wed", "Thu", "Fri", "Sat", "Sun")
    out = np.empty(n, object)
    for i in range(n):
        if not c.validity[i]:
            continue
        if type(e).__name__ == "MonthName":
            out[i] = months[days[i].month - 1]
        else:
            out[i] = dows[days[i].weekday()]
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_date_part(e, cols, n, ansi):
    if e._inner is None:
        return CpuCol(T.INT, np.zeros(n, np.int32), np.zeros(n, np.bool_))
    return eval_expr(e._inner, cols, n, ansi)


def _h_url_codec(e, cols, n, ansi):
    from urllib.parse import quote_plus, unquote_plus
    import re as _re

    (c,) = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    validity = c.validity.copy()
    enc = type(e).__name__ == "UrlEncode"
    for i in range(n):
        if not validity[i]:
            continue
        s = str(c.values[i])
        if enc:
            out[i] = quote_plus(s)
        else:
            if _re.search(r"%(?![0-9A-Fa-f]{2})", s):
                validity[i] = False
                continue
            out[i] = unquote_plus(s)
    return CpuCol(T.STRING, out, validity)


def _h_json_array_length(e, cols, n, ansi):
    import json as _json

    (c,) = _kids(e, cols, n, ansi)
    out = np.zeros(n, np.int32)
    validity = np.zeros(n, np.bool_)
    for i in range(n):
        if not c.validity[i]:
            continue
        try:
            v = _json.loads(str(c.values[i]))
        except ValueError:
            continue
        if isinstance(v, list):
            out[i] = len(v)
            validity[i] = True
    return CpuCol(T.INT, out, validity)


def _h_json_object_keys(e, cols, n, ansi):
    import json as _json

    (c,) = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    validity = np.zeros(n, np.bool_)
    for i in range(n):
        if not c.validity[i]:
            continue
        try:
            v = _json.loads(str(c.values[i]))
        except ValueError:
            continue
        if isinstance(v, dict):
            out[i] = [str(k)[:e.KEY_WIDTH] for k in list(v)[:e.MAX_KEYS]]
            validity[i] = True
    return CpuCol(e.dataType, out, validity)


def _h_format_string(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    fmt = str(e.children[0].value)
    pyfmt = fmt.replace("%%", "\x00")
    out = np.empty(n, object)
    validity = np.zeros(n, np.bool_)
    for i in range(n):
        row = []
        null = False
        for k, ce in zip(kids[1:], e.children[1:]):
            if not k.validity[i]:
                null = True
                break
            v = k.values[i]
            if isinstance(ce.dataType, (T.FloatType, T.DoubleType)):
                row.append(float(v))
            elif isinstance(ce.dataType, T.StringType):
                row.append(str(v))
            else:
                row.append(int(v))
        if null:
            continue
        try:
            out[i] = (pyfmt % tuple(row)).replace("\x00", "%")
            validity[i] = True
        except (TypeError, ValueError):
            continue
    return CpuCol(T.STRING, out, validity)


def _h_uuid(e, cols, n, ansi):
    base = np.uint64((e.seed * 0x9E3779B97F4A7C15 + 0xA5A5A5A5)
                     & 0xFFFFFFFFFFFFFFFF)
    out = np.empty(n, object)
    with np.errstate(over="ignore"):
        for i in range(n):
            def mix(z):
                z = np.uint64(z + np.uint64(0x9E3779B97F4A7C15))
                z = np.uint64((z ^ (z >> np.uint64(30)))
                              * np.uint64(0xBF58476D1CE4E5B9))
                z = np.uint64((z ^ (z >> np.uint64(27)))
                              * np.uint64(0x94D049BB133111EB))
                return np.uint64(z ^ (z >> np.uint64(31)))

            hi = int(mix(base + np.uint64(i * 2)))
            lo = int(mix(base + np.uint64(i * 2 + 1)))
            hi = (hi & 0xFFFFFFFFFFFF0FFF) | 0x4000
            lo = (lo & 0x3FFFFFFFFFFFFFFF) | (1 << 63)
            s = f"{hi:016x}{lo:016x}"
            out[i] = f"{s[:8]}-{s[8:12]}-{s[12:16]}-{s[16:20]}-{s[20:]}"
    return CpuCol(T.STRING, out, np.ones(n, np.bool_))


def _h_pi_e(e, cols, n, ansi):
    v = math.pi if type(e).__name__ == "Pi" else math.e
    return CpuCol(T.DOUBLE, np.full(n, v, np.float64),
                  np.ones(n, np.bool_))


def _h_mask(e, cols, n, ansi):
    (c,) = [eval_expr(e.children[0], cols, n, ansi)]

    def rep_of(i):
        v = getattr(e.children[i], "value", None)
        return None if v is None else str(v)[0]

    up, lo, dg, ot = rep_of(1), rep_of(2), rep_of(3), rep_of(4)
    out = np.empty(n, object)
    for i in range(n):
        if not c.validity[i]:
            continue
        res = []
        for ch in str(c.values[i]):
            if "A" <= ch <= "Z":
                res.append(up if up is not None else ch)
            elif "a" <= ch <= "z":
                res.append(lo if lo is not None else ch)
            elif "0" <= ch <= "9":
                res.append(dg if dg is not None else ch)
            else:
                res.append(ot if ot is not None else ch)
        out[i] = "".join(res)
    return CpuCol(T.STRING, out, c.validity.copy())


def _h_ilike(e, cols, n, ansi):
    import re

    from spark_rapids_tpu.regex.transpiler import like_to_regex

    l, _ = _kids(e, cols, n, ansi)
    rx = re.compile(like_to_regex(str(e.right.value).lower()))
    out = np.array(
        [bool(rx.fullmatch("".join(
            chr(ord(ch) + 32) if "A" <= ch <= "Z" else ch for ch in v)))
         if v is not None else False for v in l.values], np.bool_)
    return CpuCol(T.BOOLEAN, out, l.validity.copy())


def _h_regexp_span(e, cols, n, ansi):
    import re as _re

    c = eval_expr(e.children[0], cols, n, ansi)
    pat = _re.compile(_java_regex_to_python(str(e.children[1].value)))
    name = type(e).__name__
    def nonempty_matches(v):
        # full matches (not group contents), skipping zero-length hits —
        # the device greedy span scan's non-overlapping leftmost contract
        return [m for m in pat.finditer(v) if m.group(0) != ""]

    if name == "RegExpCount":
        out = np.array([len(nonempty_matches(v)) if v is not None else 0
                        for v in c.values], np.int32)
        return CpuCol(T.INT, out, c.validity.copy())
    if name == "RegExpInStr":
        out = np.zeros(n, np.int32)
        for i, v in enumerate(c.values):
            if v is None or not c.validity[i]:
                continue
            ms = nonempty_matches(v)
            out[i] = (ms[0].start() + 1) if ms else 0
        return CpuCol(T.INT, out, c.validity.copy())
    out = np.empty(n, object)
    validity = c.validity.copy()
    for i, v in enumerate(c.values):
        if v is None or not validity[i]:
            validity[i] = False
            continue
        ms = nonempty_matches(v)
        if ms:
            out[i] = ms[0].group(0)
        else:
            validity[i] = False
    return CpuCol(T.STRING, out, validity)


def _h_split_part(e, cols, n, ansi):
    s, d, k = _kids(e, cols, n, ansi)
    delim = str(e.children[1].value)
    validity = s.validity & d.validity & k.validity
    out = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        parts = str(s.values[i]).split(delim)
        want = int(k.values[i])
        if want < 0:
            want = len(parts) + want + 1
        out[i] = parts[want - 1] if 1 <= want <= len(parts) else ""
    return CpuCol(T.STRING, out, validity)


def _h_get(e, cols, n, ansi):
    a, idx = _kids(e, cols, n, ansi)
    validity = a.validity & idx.validity
    out = np.empty(n, object)
    ok = np.zeros(n, np.bool_)
    for i in range(n):
        if not validity[i]:
            continue
        arr = a.values[i] or []
        j = int(idx.values[i])
        if 0 <= j < len(arr) and arr[j] is not None:
            out[i] = arr[j]
            ok[i] = True
    return CpuCol.from_objs(
        [out[i] if ok[i] else None for i in range(n)], e.dataType)


def _h_array_size(e, cols, n, ansi):
    (a,) = _kids(e, cols, n, ansi)
    out = np.array([len(a.values[i]) if a.validity[i]
                    and a.values[i] is not None else 0
                    for i in range(n)], np.int32)
    return CpuCol(T.INT, out, a.validity.copy())




def _h_hive_hash(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)

    def one(c, i):
        if not c.validity[i]:
            return 0
        v = c.values[i]
        dt = c.dtype
        if isinstance(dt, T.BooleanType):
            return 1 if v else 0
        if isinstance(dt, T.LongType):
            u = int(v) & _M64
            return _to_i32((u ^ (u >> 32)) & _M32)
        if isinstance(dt, T.FloatType):
            import struct

            f = np.float32(v)
            bits = struct.unpack("<i", struct.pack("<f", float(f)))[0]
            if math.isnan(float(f)):
                bits = 0x7FC00000
            return _to_i32(bits & _M32)
        if isinstance(dt, T.DoubleType):
            import struct

            bits = struct.unpack("<q", struct.pack("<d", float(v)))[0]
            if math.isnan(float(v)):
                bits = 0x7FF8000000000000
            u = bits & _M64
            return _to_i32((u ^ (u >> 32)) & _M32)
        if isinstance(dt, T.StringType):
            h = 0
            for b in str(v).encode("utf-8"):
                sb = b - 256 if b >= 128 else b   # Java signed bytes
                h = (h * 31 + sb) & _M32
            return _to_i32(h)
        return _to_i32(int(v) & _M32)

    out = np.zeros(n, np.int32)
    for i in range(n):
        h = 0
        for c in kids:
            h = (h * 31 + one(c, i)) & _M32
        out[i] = _to_i32(h)
    return CpuCol(T.INT, out, np.ones(n, np.bool_))


def _to_i32(u):
    return u - (1 << 32) if u >= (1 << 31) else u


def _h_array_insert(e, cols, n, ansi):
    arr, _p, item = _kids(e, cols, n, ansi)
    pos = int(e.pos_literal)
    vals = np.empty(n, object)
    validity = arr.validity.copy()
    for i in range(n):
        if not arr.validity[i]:
            continue
        a = list(arr.values[i])
        v = item.row(i)
        L = len(a)
        if pos > 0:
            idx = pos - 1
            if idx >= L:
                vals[i] = a + [None] * (idx - L) + [v]
            else:
                vals[i] = a[:idx] + [v] + a[idx:]
        else:
            # Spark 3.5 default: -1 appends (0-based position L + pos + 1)
            idx = L + pos + 1
            if idx < 0:
                vals[i] = [v] + [None] * (-idx) + a
            else:
                vals[i] = a[:idx] + [v] + a[idx:]
    return CpuCol(e.dataType, vals, validity)


def _h_flatten(e, cols, n, ansi):
    vals = np.empty(n, object)
    validity = np.ones(n, np.bool_)
    if getattr(e, "_absorbed", False):
        members = [eval_expr(m, cols, n, ansi) for m in e.children]
        for i in range(n):
            if any(not m.validity[i] for m in members):
                validity[i] = False
                continue
            out = []
            for m in members:
                out.extend(m.values[i])
            vals[i] = out
        return CpuCol(e.dataType, vals, validity)
    # general array<array> child (CPU-only shape): a null inner array
    # nulls the whole result, matching Spark flatten
    (c,) = _kids(e, cols, n, ansi)
    for i in range(n):
        if not c.validity[i]:
            validity[i] = False
            continue
        out = []
        bad = False
        for sub in c.values[i]:
            if sub is None:
                bad = True
                break
            out.extend(sub)
        if bad:
            validity[i] = False
        else:
            vals[i] = out
    return CpuCol(e.dataType, vals, validity)


def _h_str_to_map(e, cols, n, ansi):
    import re as _re

    kids = _kids(e, cols, n, ansi)
    rp = _re.compile(_java_regex_to_python(e._pair))
    rk = _re.compile(_java_regex_to_python(e._kv))
    vals = np.empty(n, object)
    validity = kids[0].validity.copy()
    for i in range(n):
        if not validity[i]:
            continue
        m = {}
        for entry in rp.split(str(kids[0].values[i])):
            parts = rk.split(entry, maxsplit=1)
            if parts[0] in m:
                raise RuntimeError("Duplicate map key was found")
            m[parts[0]] = parts[1] if len(parts) > 1 else None
        vals[i] = m
    return CpuCol(e.dataType, vals, validity)


def _h_schema_of_json(e, cols, n, ansi):
    s = e._folded()
    return CpuCol(T.STRING, np.array([s] * n, object),
                  np.ones(n, np.bool_))


def _h_xpath(e, cols, n, ansi):
    from spark_rapids_tpu.expr.xpath import xpath_eval

    kids = _kids(e, cols, n, ansi)
    path = e._path()
    vals = np.empty(n, object)
    validity = np.zeros(n, np.bool_)
    for i in range(n):
        v = kids[0].row(i)
        res = e._convert(xpath_eval(v, path)) if path is not None else None
        if res is not None:
            vals[i] = res
            validity[i] = True
    return CpuCol(e.dataType, vals, validity)




def _h_try_arith(e, cols, n, ansi):
    """try_add/subtract/multiply/divide: the ANSI op with per-row
    errors-as-null (twin of arithmetic._TryMixin)."""
    base = type(e).__name__[3:]
    l, r = _kids(e, cols, n, ansi)
    dt = e.dataType
    validity = (l.validity & r.validity).copy()
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        a = np.where(validity, l.values.astype(np.float64), 0.0)
        b = np.where(validity, r.values.astype(np.float64), 1.0)
        if base == "Divide":
            zero = b == 0.0
            validity &= ~zero
            out = a / np.where(zero, 1.0, b)
        elif base == "Add":
            out = a + b
        elif base == "Subtract":
            out = a - b
        else:
            out = a * b
        return CpuCol(dt, out.astype(T.storage_dtype(dt)), validity)
    if isinstance(dt, T.DecimalType):
        lt, rt = e.left.dataType, e.right.dataType
        out = np.zeros(n, object)
        for i in range(n):
            if not validity[i]:
                out[i] = 0
                continue
            a, b = int(l.values[i]), int(r.values[i])
            if base in ("Add", "Subtract"):
                sa = a * 10 ** (dt.scale - lt.scale)
                sb = b * 10 ** (dt.scale - rt.scale)
                v = sa + sb if base == "Add" else sa - sb
            elif base == "Multiply":
                v = a * b
            else:
                if b == 0:
                    validity[i] = False
                    out[i] = 0
                    continue
                from decimal import ROUND_HALF_UP, Decimal, localcontext

                with localcontext() as lc:
                    lc.prec = 78
                    q = (Decimal(a).scaleb(-lt.scale)
                         / Decimal(b).scaleb(-rt.scale))
                    v = int(q.scaleb(dt.scale).quantize(
                        Decimal(1), rounding=ROUND_HALF_UP))
            if abs(v) >= 10 ** dt.precision:
                validity[i] = False
                v = 0
            out[i] = v
        return CpuCol(dt, out, validity)
    out = np.zeros(n, T.storage_dtype(dt))
    lo, rng = _JMIN[type(dt)], _JRANGE[type(dt)]
    for i in range(n):
        if not validity[i]:
            continue
        a, b = int(l.values[i]), int(r.values[i])
        v = a + b if base == "Add" else a - b if base == "Subtract" \
            else a * b
        wrapped = ((v - lo) % rng) + lo
        if wrapped != v:
            validity[i] = False
        else:
            out[i] = v
    return CpuCol(dt, out, validity)


def _h_bit_get(e, cols, n, ansi):
    l, r = _kids(e, cols, n, ansi)
    bits = {T.ByteType: 8, T.ShortType: 16, T.IntegerType: 32,
            T.LongType: 64}[type(e.left.dataType)]
    validity = l.validity & r.validity
    out = np.zeros(n, np.int8)
    for i in range(n):
        if not validity[i]:
            continue
        pos = int(r.values[i])
        if pos < 0 or pos >= bits:
            raise RuntimeError(
                f"Invalid bit position: must be in [0, {bits})")
        out[i] = (int(l.values[i]) >> pos) & 1
    return CpuCol(T.BYTE, out, validity)


def _h_assert_true(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    for i in range(n):
        if not (c.validity[i] and bool(c.values[i])):
            raise RuntimeError(
                f"'{e.child.sql_string()}' is not true!")
    return CpuCol(T.NullType(), np.zeros(n, np.int8),
                  np.zeros(n, np.bool_))


def _h_typeof(e, cols, n, ansi):
    s = e.child.dataType.simpleString
    return CpuCol(T.STRING, np.array([s] * n, object),
                  np.ones(n, np.bool_))





def _h_map_entries(e, cols, n, ansi):
    (m,) = _kids(e, cols, n, ansi)
    vals = np.empty(n, object)
    for i in range(n):
        if m.validity[i]:
            vals[i] = [tuple(kv) for kv in m.values[i].items()]
    return CpuCol(e.dataType, vals, m.validity.copy())


def _h_arrays_zip(e, cols, n, ansi):
    kids = _kids(e, cols, n, ansi)
    validity = _null_prop_validity(kids)
    vals = np.empty(n, object)
    for i in range(n):
        if not validity[i]:
            continue
        arrs = [k.values[i] for k in kids]
        ln = max((len(a) for a in arrs), default=0)
        vals[i] = [tuple(a[j] if j < len(a) else None for a in arrs)
                   for j in range(ln)]
    return CpuCol(e.dataType, vals, validity)


def _h_map_zip_with(e, cols, n, ansi):
    m1 = eval_expr(e.children[0], cols, n, ansi)
    m2 = eval_expr(e.children[1], cols, n, ansi)
    idx, ks, v1s, v2s = [], [], [], []
    validity = m1.validity & m2.validity
    for i in range(n):
        if not validity[i]:
            continue
        d1 = m1.values[i] or {}
        d2 = m2.values[i] or {}
        keys = list(d1.keys()) + [k for k in d2 if k not in d1]
        for k in keys:
            idx.append(i)
            ks.append(k)
            v1s.append(d1.get(k))
            v2s.append(d2.get(k))
    cnt = len(idx)
    outer = [CpuCol(c.dtype, c.values[idx], c.validity[idx]) for c in cols]
    m1t = e.children[0]._dataType
    m2t = e.children[1]._dataType
    kcol = CpuCol.from_objs(ks, m1t.keyType)
    c1 = CpuCol.from_objs(v1s, m1t.valueType)
    c2 = CpuCol.from_objs(v2s, m2t.valueType)
    res = eval_expr(e.body, outer + [kcol, c1, c2], cnt, ansi)
    per_row = [{} for _ in range(n)]
    for k, i in enumerate(idx):
        per_row[i][ks[k]] = res.row(k)
    vals = np.empty(n, object)
    for i in range(n):
        if validity[i]:
            vals[i] = per_row[i]
    return CpuCol(e.dataType, vals, validity)


_HANDLERS = {
    "BoundReference": _h_bound,
    "Literal": _h_literal,
    "Alias": _h_alias,
    "Add": _h_binarith, "Subtract": _h_binarith, "Multiply": _h_binarith,
    "Divide": _h_binarith, "IntegralDivide": _h_binarith,
    "Remainder": _h_binarith, "Pmod": _h_binarith,
    "UnaryMinus": _h_unaryminus, "Abs": _h_abs,
    "EqualTo": _h_comparison, "LessThan": _h_comparison,
    "LessThanOrEqual": _h_comparison, "GreaterThan": _h_comparison,
    "GreaterThanOrEqual": _h_comparison, "EqualNullSafe": _h_nullsafe_eq,
    "And": _h_and, "Or": _h_or, "Not": _h_not,
    "IsNull": _h_isnull, "IsNotNull": _h_isnotnull, "IsNaN": _h_isnan,
    "In": _h_in,
    "If": _h_if, "CaseWhen": _h_casewhen, "Coalesce": _h_coalesce,
    "Nvl": _h_coalesce, "NaNvl": _h_nanvl,
    "Greatest": _h_greatest, "Least": _h_greatest,
    "Cast": _h_cast,
    "Sqrt": _h_unary_math, "Exp": _h_unary_math, "Log": _h_unary_math,
    "Log10": _h_unary_math, "Sin": _h_unary_math, "Cos": _h_unary_math,
    "Tan": _h_unary_math, "Asin": _h_unary_math, "Acos": _h_unary_math,
    "Atan": _h_unary_math, "Signum": _h_unary_math,
    "Sinh": _h_unary_math, "Cosh": _h_unary_math, "Tanh": _h_unary_math,
    "Asinh": _h_unary_math, "Acosh": _h_unary_math, "Atanh": _h_unary_math,
    "Cbrt": _h_unary_math, "Log2": _h_unary_math, "Log1p": _h_unary_math,
    "Expm1": _h_unary_math, "Rint": _h_unary_math, "Cot": _h_unary_math,
    "Csc": _h_unary_math, "Sec": _h_unary_math,
    "ToDegrees": _h_unary_math, "ToRadians": _h_unary_math,
    "Atan2": _h_binary_math, "Hypot": _h_binary_math,
    "Logarithm": _h_binary_math,
    "BitwiseAnd": _h_bitwise, "BitwiseOr": _h_bitwise,
    "BitwiseXor": _h_bitwise, "BitwiseNot": _h_bitwise,
    "ShiftLeft": _h_bitwise, "ShiftRight": _h_bitwise,
    "ShiftRightUnsigned": _h_bitwise,
    "Pow": _h_pow, "Floor": _h_floorceil, "Ceil": _h_floorceil,
    "Round": _h_round,
    "Length": _h_length, "Upper": _h_upperlower, "Lower": _h_upperlower,
    "Substring": _h_substring, "Concat": _h_concat,
    "StartsWith": _h_startswith, "EndsWith": _h_startswith,
    "Contains": _h_startswith, "StringTrim": _h_trim, "Like": _h_like,
    "RLike": _h_rlike,
    "Year": _h_datefield, "Month": _h_datefield, "DayOfMonth": _h_datefield,
    "DayOfWeek": _h_datefield, "DayOfYear": _h_datefield,
    "Quarter": _h_datefield, "LastDay": _h_lastday,
    "WeekOfYear": _h_weekofyear, "AddMonths": _h_addmonths,
    "MonthsBetween": _h_monthsbetween, "TruncDate": _h_truncdate,
    "NextDay": _h_nextday, "FromUnixTime": _h_format_time,
    "DateFormat": _h_format_time,
    "Hour": _h_timefield, "Minute": _h_timefield, "Second": _h_timefield,
    "DateAdd": _h_dateadd, "DateSub": _h_dateadd, "DateDiff": _h_datediff,
    "UnixTimestamp": _h_unixts, "ToUnixTimestamp": _h_unixts,
    "MakeDate": _h_make_date, "MakeTimestamp": _h_make_timestamp,
    "CurrentDate": _h_current, "CurrentTimestamp": _h_current,
    "TimestampSeconds": _h_timestamp_units,
    "TimestampMillis": _h_timestamp_units,
    "TimestampMicros": _h_timestamp_units,
    "UnixSeconds": _h_unix_units, "UnixMillis": _h_unix_units,
    "UnixMicros": _h_unix_units,
    "UnixDate": _h_unix_date, "DateFromUnixDate": _h_unix_date,
    "WeekDay": _h_weekday,
    "ToDate": _h_to_date_ts, "ToTimestamp": _h_to_date_ts,
    "TruncTimestamp": _h_trunc_timestamp,
    "TimestampAdd": _h_timestamp_add, "TimestampDiff": _h_timestamp_diff,
    "ConvertTimezone": _h_convert_timezone,
    "MonthName": _h_month_day_name, "DayName": _h_month_day_name,
    "LocalTimestamp": _h_current, "DatePart": _h_date_part,
    "UrlEncode": _h_url_codec, "UrlDecode": _h_url_codec,
    "JsonArrayLength": _h_json_array_length,
    "JsonObjectKeys": _h_json_object_keys,
    "FormatString": _h_format_string, "Uuid": _h_uuid,
    "Pi": _h_pi_e, "EulerNumber": _h_pi_e,
    "Mask": _h_mask, "ILike": _h_ilike,
    "RegExpCount": _h_regexp_span, "RegExpInStr": _h_regexp_span,
    "RegExpSubStr": _h_regexp_span, "SplitPart": _h_split_part,
    "Get": _h_get, "ArraySize": _h_array_size,
    "Murmur3Hash": _h_hashexpr, "XxHash64": _h_hashexpr,
    "HiveHash": _h_hive_hash,
    "TryAdd": _h_try_arith, "TrySubtract": _h_try_arith,
    "TryMultiply": _h_try_arith, "TryDivide": _h_try_arith,
    "BitGet": _h_bit_get, "AssertTrue": _h_assert_true,
    "TypeOf": _h_typeof,
    "ArrayInsert": _h_array_insert,
    "Flatten": _h_flatten,
    "StrToMap": _h_str_to_map,
    "SchemaOfJson": _h_schema_of_json,
    "XPathList": _h_xpath, "XPathString": _h_xpath,
    "XPathBoolean": _h_xpath, "XPathShort": _h_xpath,
    "XPathInt": _h_xpath, "XPathLong": _h_xpath,
    "XPathFloat": _h_xpath, "XPathDouble": _h_xpath,
    "Reverse": _h_reverse, "InitCap": _h_initcap, "Ascii": _h_ascii,
    "Chr": _h_chr, "StringReplace": _h_replace,
    "StringTranslate": _h_translate, "StringInstr": _h_instr,
    "StringLocate": _h_locate, "StringLPad": _h_pad, "StringRPad": _h_pad,
    "StringRepeat": _h_repeat, "ConcatWs": _h_concat_ws,
    "OctetLength": _h_octetbit, "BitLength": _h_octetbit,
    "UserDefinedExpression": _h_udf,
    "Size": _h_size, "GetArrayItem": _h_get_array_item,
    "ElementAt": _h_element_at, "ArrayContains": _h_array_contains,
    "CreateArray": _h_create_array, "ArrayMin": _h_array_minmax,
    "ArrayMax": _h_array_minmax,
    "StringLeft": _h_leftright, "StringRight": _h_leftright,
    "SubstringIndex": _h_substring_index,
    "StringSplit": _h_string_split,
    "ArrayJoin": _h_array_join,
    "RegExpReplace": _h_regexp_replace,
    "RegExpExtract": _h_regexp_extract,
    "RegExpExtractAll": _h_regexp_extract_all,
    "Overlay": _h_overlay, "FindInSet": _h_find_in_set, "Elt": _h_elt,
    "StringSpace": _h_space,
    "StringTrimLeft": _h_ltrim_rtrim, "StringTrimRight": _h_ltrim_rtrim,
    "BRound": _h_bround, "WidthBucket": _h_width_bucket,
    "Factorial": _h_factorial, "BitwiseCount": _h_bit_count,
    "Nvl2": _h_nvl2, "NullIf": _h_nullif,
    "GetJsonObject": _h_get_json_object,
    "JsonTuple": _h_json_tuple,
    "JsonToStructs": _h_json_to_structs,
    "StructsToJson": _h_structs_to_json,
    "GetStructField": _h_get_struct_field,
    "CreateNamedStruct": _h_create_named_struct,
    "ArrayPosition": _h_array_position,
    "ArrayRemove": _h_array_remove,
    "ArrayDistinct": _h_array_distinct,
    "ArraysOverlap": _h_arrays_overlap,
    "ArrayUnion": _h_array_union,
    "ArrayIntersect": _h_array_intersect,
    "ArrayExcept": _h_array_except,
    "Slice": _h_slice,
    "SortArray": _h_sort_array,
    "ArrayRepeat": _h_array_repeat,
    "Sequence": _h_sequence,
    "CreateMap": _h_create_map,
    "MapKeys": _h_map_keys,
    "MapValues": _h_map_values,
    "GetMapValue": _h_get_map_value,
    "BloomFilterMightContain": _h_bloom_might_contain,
    "FromUTCTimestamp": _h_utc_shift,
    "ToUTCTimestamp": _h_utc_shift,
    "Md5": _str_map_handler(_o_md5),
    "Sha1": _str_map_handler(_o_sha1),
    "Sha2": _str_map_handler(_o_sha2),
    "Crc32": _h_crc32,
    "Base64": _str_map_handler(_o_base64),
    "UnBase64": _str_map_handler(_o_unbase64),
    "Encode": _str_map_handler(_o_encode),
    "Decode": _str_map_handler(_o_decode),
    "Hex": _h_hex,
    "Unhex": _str_map_handler(_o_unhex),
    "Bin": _h_bin,
    "Conv": _str_map_handler(_o_conv),
    "FormatNumber": _h_format_number,
    "ParseUrl": _str_map_handler(_o_parse_url),
    "Soundex": _str_map_handler(_o_soundex),
    "Levenshtein": _h_levenshtein,
    "MonotonicallyIncreasingID": _h_mono_id,
    "SparkPartitionID": _h_partition_id,
    "Rand": _h_rand,
    "RaiseError": _h_raise_error,
    "ArrayTransform": _h_array_transform,
    "TransformKeys": _h_transform_keys,
    "TransformValues": _h_transform_values,
    "MapFilter": _h_map_filter,
    "ZipWith": _h_zip_with,
    "MapZipWith": _h_map_zip_with,
    "MapEntries": _h_map_entries,
    "ArraysZip": _h_arrays_zip,
    "MapFromArrays": _h_map_from_arrays,
    "MapConcat": _h_map_concat,
    "MapContainsKey": _h_map_contains_key,
    "ArrayCompact": _h_array_compact,
    "ArrayAppend": _h_array_append,
    "ArrayPrepend": _h_array_append,
    "ArrayFilter": _h_array_filter,
    "ArrayExists": _h_array_exists,
    "ArrayForAll": _h_array_forall,
    "ArrayAggregate": _h_array_aggregate,
}


# ===========================================================================
# Plan executor
# ===========================================================================

def execute_cpu_plan(plan: PN.SparkPlan, ansi: bool = False) -> Tuple[CpuBatch, int]:
    """Execute a plan tree fully on CPU.  Returns (columns, num_rows)."""
    if hasattr(plan, "materialize_cpu"):
        # TpuMaterializedScan: columnar->row boundary under a CPU node
        return plan.materialize_cpu()
    name = type(plan).__name__
    if isinstance(plan, PN.LocalTableScan):
        cols = [CpuCol.from_host(h) for h in plan.host_columns]
        n = cols[0].n if cols else 0
        return cols, n
    if isinstance(plan, PN.FileSourceScan):
        return _cpu_file_scan(plan)
    if isinstance(plan, PN.CachedRelation):
        cached = plan.cache_slot.get("cpu")
        if cached is None:
            cached = execute_cpu_plan(plan.child, ansi)
            plan.cache_slot["cpu"] = cached
        return cached
    if isinstance(plan, PN.RangeNode):
        vals = np.arange(plan.start, plan.end, plan.step, dtype=np.int64)
        return [CpuCol(T.LONG, vals, np.ones(len(vals), np.bool_))], len(vals)
    if isinstance(plan, PN.Generate):
        return _cpu_generate(plan, ansi)
    if isinstance(plan, PN.Expand):
        cols, n = execute_cpu_plan(plan.child, ansi)
        pieces = [[eval_expr(e, cols, n, ansi) for e in ps]
                  for ps in plan.projections]
        merged = []
        for ci in range(len(plan.projections[0])):
            vals = np.concatenate([p[ci].values for p in pieces])
            valid = np.concatenate([p[ci].validity for p in pieces])
            merged.append(CpuCol(pieces[0][ci].dtype, vals, valid))
        return merged, n * len(plan.projections)
    if isinstance(plan, PN.BroadcastNestedLoopJoin):
        return _cpu_bnlj(plan, ansi)
    if isinstance(plan, PN.Sample):
        from spark_rapids_tpu.expr.misc import Rand as _DevRand

        cols, n = execute_cpu_plan(plan.children[0], ansi)
        z = _DevRand._u64_for_rows(plan.seed, 0, n)
        u = (z >> np.uint64(11)).astype(np.float64) / float(1 << 53)
        keep = u < plan.fraction
        idx = np.nonzero(keep)[0]
        return [CpuCol(c.dtype, c.values[idx], c.validity[idx])
                for c in cols], len(idx)
    if isinstance(plan, PN.Project):
        cols, n = execute_cpu_plan(plan.child, ansi)
        return [eval_expr(e, cols, n, ansi) for e in plan.exprs], n
    if isinstance(plan, PN.Filter):
        cols, n = execute_cpu_plan(plan.child, ansi)
        pred = eval_expr(plan.condition, cols, n, ansi)
        keep = pred.values.astype(bool) & pred.validity
        out = [CpuCol(c.dtype, c.values[keep], c.validity[keep]) for c in cols]
        return out, int(keep.sum())
    if isinstance(plan, PN.HashAggregate):
        return _cpu_aggregate(plan, ansi)
    if isinstance(plan, (PN.SortMergeJoin, PN.ShuffledHashJoin,
                         PN.BroadcastHashJoin)):
        return _cpu_join(plan, ansi)
    if isinstance(plan, PN.Sort):
        return _cpu_sort(plan, ansi)
    if isinstance(plan, PN.Window):
        return _cpu_window(plan, ansi)
    if isinstance(plan, (PN.GlobalLimit, PN.LocalLimit)):
        cols, n = execute_cpu_plan(plan.children[0], ansi)
        k = min(plan.n, n)
        return [CpuCol(c.dtype, c.values[:k], c.validity[:k]) for c in cols], k
    if isinstance(plan, PN.Union):
        parts = [execute_cpu_plan(c, ansi) for c in plan.children]
        ncols = len(parts[0][0])
        out = []
        for ci in range(ncols):
            vals = np.concatenate([p[0][ci].values for p in parts])
            valid = np.concatenate([p[0][ci].validity for p in parts])
            out.append(CpuCol(parts[0][0][ci].dtype, vals, valid))
        return out, sum(p[1] for p in parts)
    if isinstance(plan, (PN.Exchange, PN.BroadcastExchange)):
        return execute_cpu_plan(plan.children[0], ansi)
    if isinstance(plan, PN.InsertIntoHadoopFsRelation):
        from spark_rapids_tpu.io.writer import cpu_write

        cpu_write(plan, ansi)
        return [], 0
    raise NotImplementedError(f"oracle plan node {name}")


def _cpu_file_scan(plan: PN.FileSourceScan):
    import pyarrow.parquet as pq
    import pyarrow.csv as pacsv

    import os

    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.io import faults as IOF

    # the oracle honors the SAME per-file tolerance confs as the TPU
    # scan (differential runs must read the same surviving file set);
    # skips here bump no counters and write no quarantine — only the
    # device scan's accounting is the product surface
    conf = get_conf()
    tol = IOF.scan_tolerance(conf)

    def read_one(p):
        if os.path.isdir(p):
            import pyarrow.dataset as ds

            return ds.dataset(
                p, format=plan.fmt, partitioning="hive",
                exclude_invalid_files=True).to_table(
                columns=[f.name for f in plan.output.fields])
        if plan.fmt == "parquet":
            from spark_rapids_tpu.io.scan import read_parquet_file

            return read_parquet_file(
                p, [f.name for f in plan.output.fields])
        if plan.fmt == "orc":
            import pyarrow.orc as paorc

            return paorc.ORCFile(p).read(
                columns=[f.name for f in plan.output.fields])
        if plan.fmt in ("csv", "json"):
            import pyarrow as pa

            from spark_rapids_tpu.io.text import (read_csv_spark,
                                                  read_json_spark)

            rd = read_csv_spark if plan.fmt == "csv" else read_json_spark
            tcols, _ = rd(p, plan.output, plan.options)
            return pa.table(
                {f.name: c.to_arrow()
                 for f, c in zip(plan.output.fields, tcols)})
        if plan.fmt == "avro":
            import pyarrow as pa

            from spark_rapids_tpu.io.avro import read_avro_columns

            acols, astruct = read_avro_columns(p, plan.output)
            return pa.table(
                {f.name: c.to_arrow()
                 for f, c in zip(astruct.fields, acols)})
        raise NotImplementedError(plan.fmt)

    tables = []
    for p in plan.paths:
        try:
            with IOF.file_context(p, plan.fmt, "cpu-oracle"):
                tables.append(read_one(p))
        except Exception as e:
            IOF.handle_scan_error(e, p, plan.fmt, "cpu-oracle", tol,
                                  conf, count_skips=False)
    import pyarrow as pa

    if not tables:
        cols = [CpuCol.from_host(HostColumn.from_pylist([], f.dataType))
                for f in plan.output.fields]
        return cols, 0
    tbl = pa.concat_tables(tables)
    cols = []
    for f in plan.output.fields:
        h = HostColumn.from_arrow(tbl.column(f.name), f.dataType)
        cols.append(CpuCol.from_host(h))
    return cols, tbl.num_rows


def _group_key(cols: List[CpuCol], i: int):
    out = []
    for c in cols:
        if not c.validity[i]:
            out.append(("\0NULL",))
        else:
            v = c.values[i]
            if isinstance(v, float) and math.isnan(v):
                out.append(("\0NAN",))
            else:
                out.append(v)
    return tuple(out)


def _cpu_aggregate(plan: PN.HashAggregate, ansi: bool):
    cols, n = execute_cpu_plan(plan.child, ansi)
    gcols = [eval_expr(g, cols, n, ansi) for g in plan.grouping]
    mode = plan.mode
    child_names = plan.child.output.field_names()
    if mode == PN.AggregateMode.FINAL:
        # inputs are partial buffers from the child by name
        acols = []
        for a in plan.aggregates:
            if a.func == "avg":
                acols.append((cols[child_names.index(a.result_name + "_sum")],
                              cols[child_names.index(a.result_name + "_count")]))
            elif a.func in PN.MOMENT_BUFFERS:
                acols.append(tuple(
                    cols[child_names.index(a.result_name + s)]
                    for s in PN.MOMENT_BUFFERS[a.func]))
            elif a.func == "approx_count_distinct":
                acols.append(cols[child_names.index(a.result_name + "_hll")])
            else:
                nm = a.result_name
                acols.append(cols[child_names.index(nm)])
    else:
        acols = []
        for a in plan.aggregates:
            if a.child is None:
                acols.append(None)
            elif a.child2 is not None:
                acols.append((eval_expr(a.child, cols, n, ansi),
                              eval_expr(a.child2, cols, n, ansi)))
            else:
                acols.append(eval_expr(a.child, cols, n, ansi))
    groups: Dict[tuple, int] = {}
    order: List[tuple] = []
    rows_per_group: List[List[int]] = []
    if gcols:
        for i in range(n):
            k = _group_key(gcols, i)
            gi = groups.get(k)
            if gi is None:
                gi = len(order)
                groups[k] = gi
                order.append(k)
                rows_per_group.append([])
            rows_per_group[gi].append(i)
        ng = len(order)
    else:
        ng = 1
        rows_per_group = [list(range(n))]
    out_cols: List[CpuCol] = []
    for ki, g in enumerate(plan.grouping):
        vals = []
        valid = np.ones(ng, np.bool_)
        for gi in range(ng):
            i = rows_per_group[gi][0]
            if gcols[ki].validity[i]:
                vals.append(gcols[ki].values[i])
            else:
                vals.append(None)
                valid[gi] = False
        dtype = (object if gcols[ki].values.dtype == object
                 else gcols[ki].values.dtype)
        arr = np.array([v if v is not None else
                        (None if dtype == object else 0) for v in vals],
                       dtype=dtype)
        out_cols.append(CpuCol(g.dataType, arr, valid))
    for a, ac, f in zip(plan.aggregates, acols,
                        plan.output.fields[len(plan.grouping):]
                        if mode != PN.AggregateMode.PARTIAL else
                        _partial_field_groups(plan)):
        if mode == PN.AggregateMode.PARTIAL:
            for c in _agg_partial(a, ac, rows_per_group, f):
                out_cols.append(c)
        elif mode == PN.AggregateMode.FINAL:
            out_cols.append(_agg_final(a, ac, rows_per_group))
        else:
            vals, valid = _agg_one(a, ac, rows_per_group, ansi)
            out_cols.append(CpuCol(a.result_type, vals, valid))
    return out_cols, ng


def _partial_field_groups(plan: PN.HashAggregate):
    """Yield the output field (or field pair for avg) per aggregate."""
    fields = plan.output.fields[len(plan.grouping):]
    i = 0
    for a in plan.aggregates:
        if a.func == "avg":
            yield (fields[i], fields[i + 1])
            i += 2
        elif a.func in PN.MOMENT_BUFFERS:
            k = len(PN.MOMENT_BUFFERS[a.func])
            yield tuple(fields[i:i + k])
            i += k
        else:
            yield (fields[i],)
            i += 1


# -- moment/covariance/HLL/bloom helpers (spec-mirrors of the device path;
# hashing goes through the oracle's OWN xxhash64) -----------------------------

_HLL_P = PN.HLL_DEFAULT_P


def _oracle_xxh64(dtype, value, seed: int) -> int:
    kind, x = _hash_input(dtype, value)
    h = _xxh_update(kind, x, seed & _M64)
    return h & _M64


def _scaled_floats(ac: CpuCol, idxs) -> List[float]:
    scale = (10.0 ** -ac.dtype.scale
             if isinstance(ac.dtype, T.DecimalType) else 1.0)
    return [float(ac.values[i]) * scale for i in idxs if ac.validity[i]]


def _moment_stats(xs: List[float]):
    n = len(xs)
    if n == 0:
        return 0.0, 0.0, 0.0, 0.0, 0.0
    m = sum(xs) / n
    m2 = sum((x - m) ** 2 for x in xs)
    m3 = sum((x - m) ** 3 for x in xs)
    m4 = sum((x - m) ** 4 for x in xs)
    return float(n), m, m2, m3, m4


def _cov_stats(pairs):
    n = len(pairs)
    if n == 0:
        return 0.0, 0.0, 0.0, 0.0, 0.0, 0.0
    xa = sum(x for x, _ in pairs) / n
    ya = sum(y for _, y in pairs) / n
    ck = sum((x - xa) * (y - ya) for x, y in pairs)
    xm2 = sum((x - xa) ** 2 for x, _ in pairs)
    ym2 = sum((y - ya) ** 2 for _, y in pairs)
    return float(n), xa, ya, ck, xm2, ym2


def _cov_pairs(ac, idxs):
    xc, yc = ac
    xs = _scaled_floats_map(xc)
    ys = _scaled_floats_map(yc)
    return [(xs(i), ys(i)) for i in idxs
            if xc.validity[i] and yc.validity[i]]


def _scaled_floats_map(c: CpuCol):
    scale = (10.0 ** -c.dtype.scale
             if isinstance(c.dtype, T.DecimalType) else 1.0)
    return lambda i: float(c.values[i]) * scale


def _finalize_moment(func: str, n, m2, m3, m4):
    """-> (value, valid); Spark nullOnDivideByZero semantics."""
    if n <= 0 or m2 == 0.0:
        return 0.0, False
    if func == "skewness":
        return math.sqrt(n) * m3 / (m2 ** 1.5), True
    return n * m4 / (m2 * m2) - 3.0, True


def _finalize_cov(func: str, n, ck, xm2, ym2):
    if n <= 0:
        return 0.0, False
    if func == "corr":
        denom = math.sqrt(xm2 * ym2)
        if denom == 0.0:
            return float("nan"), True
        return ck / denom, True
    if func == "covar_pop":
        return ck / n, True
    if n <= 1:
        return 0.0, False
    return ck / (n - 1.0), True


def _hll_regs(ac: CpuCol, idxs) -> List[int]:
    p = _HLL_P
    m = 1 << p
    regs = [0] * m
    for i in idxs:
        if not ac.validity[i]:
            continue
        h = _oracle_xxh64(ac.dtype, ac.values[i], 42)
        idx = h >> (64 - p)
        w = (h << p) & _M64
        clz = 64 - w.bit_length()
        rank = min(clz + 1, 65 - p)
        regs[idx] = max(regs[idx], rank)
    return regs


def _hll_estimate(regs: List[int]) -> int:
    m = len(regs)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    inv = sum(2.0 ** -r for r in regs)
    raw = alpha * m * m / inv
    zeros = regs.count(0)
    if raw <= 2.5 * m and zeros > 0:
        est = m * math.log(m / zeros)
    else:
        est = raw
    return int(round(est))


def _wrap64(x: int) -> int:
    return ((x + 2**63) % 2**64) - 2**63


def _bloom_words(ac: CpuCol, idxs, num_items: int, num_bits: int):
    words = [0] * (num_bits // 64)
    k = max(1, round(num_bits / num_items * math.log(2)))
    for i in idxs:
        if not ac.validity[i]:
            continue
        h1 = _wrap64(_oracle_xxh64(ac.dtype, ac.values[i], 42))
        h2 = _wrap64(_oracle_xxh64(ac.dtype, ac.values[i], 77))
        for j in range(k):
            bit = _wrap64(h1 + j * h2) % num_bits
            words[bit // 64] |= 1 << (bit % 64)
    return [_wrap64(w) for w in words]


def _percentile_sorted(ac: CpuCol, idxs):
    vals = [(ac.values[i]) for i in idxs if ac.validity[i]]
    return sorted(vals, key=lambda v: (isinstance(v, float)
                                       and math.isnan(v), v))


def _agg_partial(a: PN.AggregateExpression, ac: Optional[CpuCol],
                 rows_per_group, fields):
    ng = len(rows_per_group)
    if a.func == "avg":
        sum_f, cnt_f = fields
        sums, cnts = [], []
        valid = np.ones(ng, np.bool_)
        dec = isinstance(sum_f.dataType, T.DecimalType)
        for gi in range(ng):
            idxs = [i for i in rows_per_group[gi] if ac.validity[i]]
            cnts.append(len(idxs))
            if not idxs:
                sums.append(None)
                valid[gi] = False
            elif dec:
                sums.append(sum(int(ac.values[i]) for i in idxs))
            else:
                sums.append(float(np.sum(np.array(
                    [ac.values[i] for i in idxs], np.float64))))
        svals = (np.array([s if s is not None else 0 for s in sums],
                          dtype=object if dec else np.float64))
        yield CpuCol(sum_f.dataType, svals, valid)
        yield CpuCol(cnt_f.dataType, np.array(cnts, np.int64),
                     np.ones(ng, np.bool_))
        return
    if a.func in PN.MOMENT_BUFFERS:
        suffixes = PN.MOMENT_BUFFERS[a.func]
        bufs = [[] for _ in suffixes]
        mvalid = np.ones(ng, np.bool_)
        for gi in range(ng):
            if a.func in PN.COVARIANCE_FUNCS \
                    or a.func in PN.REGR_FUNCS:
                pair_ac = ((ac[1], ac[0])
                           if a.func in PN.REGR_FUNCS else ac)
                pairs = _cov_pairs(pair_ac, rows_per_group[gi])
                stats = _cov_stats(pairs)
                nvals = stats[0]
            else:
                xs = _scaled_floats(ac, rows_per_group[gi])
                n_, m, m2, m3, m4 = _moment_stats(xs)
                stats = {"_n": n_, "_avg": m, "_m2": m2, "_m3": m3,
                         "_m4": m4}
                stats = tuple(stats[s] for s in suffixes)
                nvals = n_
            if nvals == 0:
                mvalid[gi] = False
            for b, v in zip(bufs, stats):
                b.append(v)
        for si, (s, f) in enumerate(zip(suffixes, fields)):
            valid = np.ones(ng, np.bool_) if s == "_n" else mvalid
            yield CpuCol(f.dataType, np.array(bufs[si], np.float64),
                         valid.copy())
        return
    if a.func == "approx_count_distinct":
        (f,) = fields
        vals = np.empty(ng, object)
        for gi in range(ng):
            vals[gi] = _hll_regs(ac, rows_per_group[gi])
        yield CpuCol(f.dataType, vals, np.ones(ng, np.bool_))
        return
    # count/sum/min/max/first/last/count_if partials share the final shape
    vals, valid = _agg_one(a, ac, rows_per_group, False)
    (f,) = fields
    yield CpuCol(f.dataType, vals, valid)


def _agg_final(a: PN.AggregateExpression, ac, rows_per_group) -> CpuCol:
    """Merge partial buffers (collect_* never reaches FINAL — the planner
    builds it single-phase COMPLETE)."""
    ng = len(rows_per_group)
    if a.func == "avg":
        sc, cc = ac
        dec = isinstance(a.result_type, T.DecimalType)
        out, valid = [], np.ones(ng, np.bool_)
        for gi in range(ng):
            idxs = rows_per_group[gi]
            total_cnt = sum(int(cc.values[i]) for i in idxs if cc.validity[i])
            if total_cnt == 0:
                out.append(None)
                valid[gi] = False
                continue
            if dec:
                import decimal as pydec

                rt: T.DecimalType = a.result_type
                s = sum(int(sc.values[i]) for i in idxs if sc.validity[i])
                in_scale = rt.scale - 4
                with pydec.localcontext() as lctx:
                    lctx.prec = 78
                    q = pydec.Decimal(s).scaleb(-in_scale) / total_cnt
                    out.append(int(q.scaleb(rt.scale).quantize(
                        pydec.Decimal(1), rounding=pydec.ROUND_HALF_UP)))
            else:
                s = sum(float(sc.values[i]) for i in idxs if sc.validity[i])
                out.append(s / total_cnt)
        if dec:
            return CpuCol(a.result_type, np.array(out, object), valid)
        return CpuCol(a.result_type,
                      np.array([v if v is not None else 0 for v in out],
                               np.float64), valid)
    if a.func in PN.VARIANCE_FUNCS:
        cn, ca, cm = ac
        out = np.zeros(ng, np.float64)
        valid = np.ones(ng, np.bool_)
        for gi in range(ng):
            idxs = [i for i in rows_per_group[gi]
                    if cn.validity[i] and float(cn.values[i]) > 0]
            ntot = sum(float(cn.values[i]) for i in idxs)
            if ntot == 0:
                valid[gi] = False
                continue
            mean = sum(float(cn.values[i]) * float(ca.values[i])
                       for i in idxs) / ntot
            m2 = sum(float(cm.values[i])
                     + float(cn.values[i]) * (float(ca.values[i]) - mean) ** 2
                     for i in idxs)
            v, ok = _finalize_variance(a.func, ntot, m2)
            out[gi] = v
            valid[gi] = ok
        return CpuCol(a.result_type, out, valid)
    if a.func in PN.HIGHER_MOMENT_FUNCS:
        cn, ca, cm2, cm3 = ac[:4]
        cm4 = ac[4] if len(ac) > 4 else None
        out = np.zeros(ng, np.float64)
        valid = np.ones(ng, np.bool_)
        for gi in range(ng):
            idxs = [i for i in rows_per_group[gi]
                    if cn.validity[i] and float(cn.values[i]) > 0]
            ntot = sum(float(cn.values[i]) for i in idxs)
            if ntot == 0:
                valid[gi] = False
                continue
            mean = sum(float(cn.values[i]) * float(ca.values[i])
                       for i in idxs) / ntot
            m2 = m3 = m4 = 0.0
            for i in idxs:
                ni = float(cn.values[i])
                di = float(ca.values[i]) - mean
                m2i = float(cm2.values[i])
                m3i = float(cm3.values[i])
                m2 += m2i + ni * di * di
                m3 += m3i + 3.0 * m2i * di + ni * di ** 3
                if cm4 is not None:
                    m4 += (float(cm4.values[i]) + 4.0 * m3i * di
                           + 6.0 * m2i * di * di + ni * di ** 4)
            v, ok = _finalize_moment(a.func, ntot, m2, m3, m4)
            out[gi] = v
            valid[gi] = ok
        return CpuCol(a.result_type, out, valid)
    if a.func in PN.COVARIANCE_FUNCS or a.func in PN.REGR_FUNCS:
        cn, cx, cy, cc = ac[:4]
        is_regr = a.func in PN.REGR_FUNCS
        is_corr = a.func == "corr" or is_regr
        out = np.zeros(ng, np.float64)
        valid = np.ones(ng, np.bool_)
        for gi in range(ng):
            idxs = [i for i in rows_per_group[gi]
                    if cn.validity[i] and float(cn.values[i]) > 0]
            ntot = sum(float(cn.values[i]) for i in idxs)
            if ntot == 0:
                valid[gi] = False
                continue
            xavg = sum(float(cn.values[i]) * float(cx.values[i])
                       for i in idxs) / ntot
            yavg = sum(float(cn.values[i]) * float(cy.values[i])
                       for i in idxs) / ntot
            ck = xm2 = ym2 = 0.0
            for i in idxs:
                ni = float(cn.values[i])
                dxi = float(cx.values[i]) - xavg
                dyi = float(cy.values[i]) - yavg
                ck += float(cc.values[i]) + ni * dxi * dyi
                if is_corr:
                    xm2 += float(ac[4].values[i]) + ni * dxi * dxi
                    ym2 += float(ac[5].values[i]) + ni * dyi * dyi
            if is_regr:
                v, ok = _finalize_regr(a.func, ntot, xavg, yavg, ck,
                                       xm2, ym2)
            else:
                v, ok = _finalize_cov(a.func, ntot, ck, xm2, ym2)
            out[gi] = v
            valid[gi] = ok
        if a.func == "regr_count":
            return CpuCol(T.LONG, out.astype(np.int64),
                          np.ones(ng, np.bool_))
        return CpuCol(a.result_type, out, valid)
    if a.func == "approx_count_distinct":
        out = np.zeros(ng, np.int64)
        for gi in range(ng):
            m = 1 << _HLL_P
            merged = [0] * m
            for i in rows_per_group[gi]:
                if not ac.validity[i]:
                    continue
                regs = ac.values[i]
                for j in range(m):
                    if regs[j] > merged[j]:
                        merged[j] = regs[j]
            out[gi] = _hll_estimate(merged)
        return CpuCol(a.result_type, out, np.ones(ng, np.bool_))
    merge_func = {"count": "sum", "count_star": "sum", "sum": "sum",
                  "min": "min", "max": "max", "first": "first",
                  "last": "last", "count_if": "sum",
                  "bool_and": "min", "bool_or": "max",
                  "any_value": "first", "bit_and": "bit_and",
                  "bit_or": "bit_or", "bit_xor": "bit_xor"}[a.func]
    merged = PN.AggregateExpression(merge_func, None, a.result_name,
                                    a.result_type)
    vals, valid = _agg_one(merged, ac, rows_per_group, False)
    if a.func in ("count", "count_star", "count_if"):
        valid = np.ones(ng, np.bool_)
        vals = np.array([v if valid[i] else 0 for i, v in enumerate(vals)],
                        np.int64)
    return CpuCol(a.result_type, vals, valid)


def _finalize_variance(func: str, n: float, m2: float):
    """-> (value, is_valid).  Spark CentralMomentAgg semantics with the
    default nullOnDivideByZero (samp of a single row -> NULL)."""
    den = n if func.endswith("_pop") else n - 1.0
    if den <= 0:
        return 0.0, False
    v = m2 / den
    return (v if func.startswith("var") else math.sqrt(v)), True


def _finalize_regr(func, n, xa, ya, ck, xm2, ym2):
    """-> (value, valid); Spark regr_* null/zero semantics."""
    if func == "regr_count":
        return float(n), True
    if n <= 0:
        return 0.0, False
    if func == "regr_avgx":
        return xa, True
    if func == "regr_avgy":
        return ya, True
    if func == "regr_sxx":
        return xm2, True
    if func == "regr_syy":
        return ym2, True
    if func == "regr_sxy":
        return ck, True
    if xm2 == 0.0:
        return 0.0, False
    slope = ck / xm2
    if func == "regr_slope":
        return slope, True
    if func == "regr_intercept":
        return ya - slope * xa, True
    if ym2 == 0.0:
        return 1.0, True
    return (ck * ck) / (xm2 * ym2), True


def _agg_one(a: PN.AggregateExpression, ac: Optional[CpuCol],
             rows_per_group, ansi):
    ng = len(rows_per_group)
    func = a.func
    if func == "any_value":
        func = "first"
    if func in ("bool_and", "bool_or"):
        func = "min" if func == "bool_and" else "max"
    if func == "count_star":
        return (np.array([len(r) for r in rows_per_group], np.int64),
                np.ones(ng, np.bool_))
    if func in ("collect_list", "collect_set"):
        vals = np.empty(ng, object)
        for gi in range(ng):
            xs = [ac.row(i) for i in rows_per_group[gi] if ac.validity[i]]
            if func == "collect_set":
                # NaN == NaN for set membership (Spark total order); output
                # ascending with NaN last, matching the TPU kernel's keys
                has_nan = any(isinstance(x, float) and math.isnan(x)
                              for x in xs)
                rest = sorted({x for x in xs
                               if not (isinstance(x, float)
                                       and math.isnan(x))})
                xs = rest + ([float("nan")] if has_nan else [])
            vals[gi] = xs
        return vals, np.ones(ng, np.bool_)
    if func == "bloom_filter_agg":
        vals = np.empty(ng, object)
        for gi in range(ng):
            vals[gi] = _bloom_words(ac, rows_per_group[gi],
                                    int(a.args[0]), int(a.args[1]))
        return vals, np.ones(ng, np.bool_)
    out = []
    valid = np.ones(ng, np.bool_)
    dec = isinstance(a.result_type, T.DecimalType)
    if isinstance(ac, tuple):  # covariance/regr family: two inputs
        is_regr = func in PN.REGR_FUNCS
        for gi in range(ng):
            # regr_f(y, x): the independent x is the SECOND argument
            pair_ac = (ac[1], ac[0]) if is_regr else ac
            pairs = _cov_pairs(pair_ac, rows_per_group[gi])
            n_, xa, ya, ck, xm2, ym2 = _cov_stats(pairs)
            if is_regr:
                v, ok = _finalize_regr(func, n_, xa, ya, ck, xm2, ym2)
            else:
                v, ok = _finalize_cov(func, n_, ck, xm2, ym2)
            out.append(v if ok else None)
            valid[gi] = ok
        if func == "regr_count":
            return (np.array([int(v) if v is not None else 0
                              for v in out], np.int64), valid)
        return (np.array([v if v is not None else 0.0 for v in out],
                         np.float64), valid)
    for gi in range(ng):
        idxs = [i for i in rows_per_group[gi] if ac.validity[i]]
        if func == "count":
            out.append(len(idxs))
            continue
        if func == "count_if":
            out.append(sum(1 for i in idxs if bool(ac.values[i])))
            continue
        if func == "approx_count_distinct":
            out.append(_hll_estimate(_hll_regs(ac, rows_per_group[gi])))
            continue
        if func in ("first", "last"):
            # Spark First/Last default ignoreNulls=false: nulls count
            all_rows = rows_per_group[gi]
            i = all_rows[0] if func == "first" else all_rows[-1]
            if ac.validity[i]:
                out.append(ac.values[i])
            else:
                out.append(None)
                valid[gi] = False
            continue
        if not idxs:
            out.append(None)
            valid[gi] = False
            continue
        vs = [ac.values[i] for i in idxs]
        if func == "sum":
            out.append(sum(int(v) for v in vs) if dec or
                       isinstance(a.result_type, T.LongType)
                       else float(np.sum(np.array(vs, np.float64))))
        elif func == "min":
            out.append(_minmax(vs, ac.dtype, mx=False))
        elif func == "max":
            out.append(_minmax(vs, ac.dtype, mx=True))
        elif func == "avg":
            if isinstance(ac.dtype, T.DecimalType):
                import decimal as pydec

                s = sum(int(v) for v in vs)
                rt: T.DecimalType = a.result_type
                q = (pydec.Decimal(s).scaleb(-ac.dtype.scale)
                     / pydec.Decimal(len(vs)))
                out.append(int(q.scaleb(rt.scale).quantize(
                    pydec.Decimal(1), rounding=pydec.ROUND_HALF_UP)))
            else:
                out.append(float(np.mean(np.array(vs, np.float64))))
        elif func == "first":
            out.append(vs[0])
        elif func == "last":
            out.append(vs[-1])
        elif func in PN.VARIANCE_FUNCS:
            vscale = (10.0 ** -ac.dtype.scale
                      if isinstance(ac.dtype, T.DecimalType) else 1.0)
            xs = [float(v) * vscale for v in vs]
            m = sum(xs) / len(xs)
            m2 = sum((x - m) ** 2 for x in xs)
            v, ok = _finalize_variance(func, float(len(xs)), m2)
            if ok:
                out.append(v)
            else:
                out.append(None)
                valid[gi] = False
        elif func in PN.HIGHER_MOMENT_FUNCS:
            xs = _scaled_floats(ac, idxs)
            n_, m, m2, m3, m4 = _moment_stats(xs)
            v, ok = _finalize_moment(func, n_, m2, m3, m4)
            if ok:
                out.append(v)
            else:
                out.append(None)
                valid[gi] = False
        elif func in ("percentile", "median"):
            xs = _percentile_sorted(ac, idxs)
            if not xs:
                out.append(None)
                valid[gi] = False
                continue
            pscale = (10.0 ** -ac.dtype.scale
                      if isinstance(ac.dtype, T.DecimalType) else 1.0)
            p = 0.5 if func == "median" else float(a.args[0])
            r = p * (len(xs) - 1)
            lo, hi = int(math.floor(r)), int(math.ceil(r))
            frac = r - lo
            out.append((float(xs[lo]) * (1 - frac)
                        + float(xs[hi]) * frac) * pscale)
        elif func == "approx_percentile":
            xs = _percentile_sorted(ac, idxs)
            if not xs:
                out.append(None)
                valid[gi] = False
                continue
            p = float(a.args[0])
            out.append(xs[int(math.floor(p * (len(xs) - 1)))])
        elif func in ("bit_and", "bit_or", "bit_xor"):
            acc = -1 if func == "bit_and" else 0
            for i in idxs:
                v = int(ac.values[i])
                acc = acc & v if func == "bit_and" else (
                    acc | v if func == "bit_or" else acc ^ v)
            out.append(acc)
        else:
            raise NotImplementedError(func)
    if dec or isinstance(a.result_type, T.StringType):
        vals = np.array([v if v is not None else None for v in out], object)
    else:
        sdt = T.storage_dtype(a.result_type)
        if a.result_type.is_integral:
            # Spark sum(long) wraps silently in non-ANSI mode (Java +)
            out = [((int(v) + 2 ** 63) % 2 ** 64) - 2 ** 63
                   if v is not None else None for v in out]
        vals = np.array([v if v is not None else 0 for v in out], sdt)
    return vals, valid


def _minmax(vs, dtype, mx):
    if isinstance(dtype, T.StringType):
        key = lambda s: s.encode()
        return (max if mx else min)(vs, key=key)
    fv = [v for v in vs]
    floats = [v for v in fv if isinstance(v, float)]
    if floats and any(math.isnan(v) for v in floats):
        # Spark: NaN is greater than everything
        non_nan = [v for v in fv if not (isinstance(v, float) and math.isnan(v))]
        if mx:
            return math.nan
        return min(non_nan) if non_nan else math.nan
    return (max if mx else min)(fv)


def _join_key(cols: List[CpuCol], i: int):
    parts = []
    for c in cols:
        if not c.validity[i]:
            return None  # null keys never match
        v = c.values[i]
        if isinstance(v, float) and math.isnan(v):
            v = ("\0NAN",)
        parts.append(v)
    return tuple(parts)


def _cpu_join(plan: PN._BaseJoin, ansi: bool):
    lcols, ln = execute_cpu_plan(plan.left, ansi)
    rcols, rn = execute_cpu_plan(plan.right, ansi)
    lkeys = [eval_expr(k, lcols, ln, ansi) for k in plan.left_keys]
    rkeys = [eval_expr(k, rcols, rn, ansi) for k in plan.right_keys]
    build: Dict[tuple, List[int]] = {}
    for j in range(rn):
        k = _join_key(rkeys, j)
        if k is not None:
            build.setdefault(k, []).append(j)
    jt = plan.join_type
    pairs: List[Tuple[int, Optional[int]]] = []
    matched_right = np.zeros(rn, np.bool_)
    for i in range(ln):
        k = _join_key(lkeys, i)
        matches = build.get(k, []) if k is not None else []
        if jt == PN.JoinType.LEFT_SEMI:
            if matches:
                pairs.append((i, None))
            continue
        if jt == PN.JoinType.LEFT_ANTI:
            if not matches:
                pairs.append((i, None))
            continue
        if matches:
            for j in matches:
                pairs.append((i, j))
                matched_right[j] = True
        elif jt in (PN.JoinType.LEFT_OUTER, PN.JoinType.FULL_OUTER):
            pairs.append((i, None))
    if jt in (PN.JoinType.RIGHT_OUTER, PN.JoinType.FULL_OUTER):
        if jt == PN.JoinType.RIGHT_OUTER:
            # keep matched pairs plus unmatched right
            pass
        for j in range(rn):
            if not matched_right[j]:
                pairs.append((None, j))
        if jt == PN.JoinType.RIGHT_OUTER:
            pairs = [(i, j) for (i, j) in pairs if j is not None]
    # apply residual condition on joined rows (inner-style filter)
    out_cols = _materialize_join(plan, lcols, rcols, pairs, jt)
    nrows = len(pairs)
    if plan.condition is not None and jt == PN.JoinType.INNER:
        pred = eval_expr(plan.condition, out_cols, nrows, ansi)
        keep = pred.values.astype(bool) & pred.validity
        out_cols = [CpuCol(c.dtype, c.values[keep], c.validity[keep])
                    for c in out_cols]
        nrows = int(keep.sum())
    if plan.emit is not None:
        # a pruned plan's join (plan/pruning.py) re-tagged to the oracle
        out_cols = [out_cols[i] for i in plan.emit]
    return out_cols, nrows


def _materialize_join(plan, lcols, rcols, pairs, jt):
    def take(cols, idxs):
        out = []
        for c in cols:
            vals = np.array(
                [c.values[i] if i is not None else
                 (None if c.values.dtype == object else 0)
                 for i in idxs],
                dtype=c.values.dtype if c.values.dtype == object else
                c.values.dtype)
            valid = np.array([c.validity[i] if i is not None else False
                              for i in idxs], np.bool_)
            out.append(CpuCol(c.dtype, vals, valid))
        return out

    li = [p[0] for p in pairs]
    out = take(lcols, li)
    if jt not in (PN.JoinType.LEFT_SEMI, PN.JoinType.LEFT_ANTI):
        ri = [p[1] for p in pairs]
        out += take(rcols, ri)
    return out


def _sort_key_fn(c: CpuCol, spec):
    def key(i):
        if not c.validity[i]:
            return (0 if spec.nulls_first else 2, 0, 0)
        v = c.values[i]
        if isinstance(v, str):
            b = v.encode()
            if not spec.ascending:
                # desc for bytes: invert and terminate so prefixes sort after
                b = bytes(255 - x for x in b) + b"\xff"
                return (1, b, 0)
            return (1, b, 0)
        if isinstance(v, float) and math.isnan(v):
            # NaN is strictly greatest (above +inf)
            return ((1, math.inf, 1) if spec.ascending
                    else (1, -math.inf, -1))
        v2 = float(v) if not isinstance(v, int) else v
        return (1, -v2 if not spec.ascending else v2, 0)

    return key


def _cpu_sort(plan: PN.Sort, ansi: bool):
    cols, n = execute_cpu_plan(plan.child, ansi)
    kcols = [eval_expr(e, cols, n, ansi) for e, _ in plan.orders]
    idx = list(range(n))
    # stable multi-key: sort by last key first
    for (e, spec), kc in reversed(list(zip(plan.orders, kcols))):
        keyf = _sort_key_fn(kc, spec)
        idx.sort(key=keyf)
    take = np.array(idx, np.int64) if n else np.zeros(0, np.int64)
    out = [CpuCol(c.dtype, c.values[take], c.validity[take]) for c in cols]
    return out, n


def _cpu_generate(plan: PN.Generate, ansi: bool):
    cols, n = execute_cpu_plan(plan.child, ansi)
    arr = eval_expr(plan.gen_expr, cols, n, ansi)
    rows = []           # (src_row, pos or None, value, value_valid)
    for i in range(n):
        v = arr.values[i] if arr.validity[i] else None
        if v is None or len(v) == 0:
            if plan.outer:
                rows.append((i, None, None, False))
            continue
        for k, e in enumerate(v):
            rows.append((i, k, e, e is not None))
    m = len(rows)
    out = []
    for c in cols:
        if c.values.dtype == object:
            # np.array() would collapse equal-length lists into a 2-D array
            vals = np.empty(m, object)
            for j, r in enumerate(rows):
                vals[j] = c.values[r[0]]
        else:
            vals = np.array([c.values[r[0]] for r in rows],
                            dtype=c.values.dtype)
        valid = np.array([c.validity[r[0]] for r in rows], np.bool_)
        out.append(CpuCol(c.dtype, vals, valid))
    if plan.position:
        out.append(CpuCol(T.INT, np.array(
            [r[1] if r[1] is not None else 0 for r in rows], np.int32),
            np.array([r[1] is not None for r in rows], np.bool_)))
    et = plan.gen_expr.dataType.elementType
    evalid = np.array([r[3] for r in rows], np.bool_)
    if isinstance(et, T.StringType):
        evals = np.empty(m, object)
        for j, r in enumerate(rows):
            evals[j] = r[2] if r[3] else None
    else:
        evals = np.array([r[2] if r[3] else 0 for r in rows],
                         T.storage_dtype(et))
    out.append(CpuCol(et, evals, evalid))
    return out, m


def _cpu_bnlj(plan, ansi: bool):
    lcols, nl = execute_cpu_plan(plan.left, ansi)
    rcols, nr = execute_cpu_plan(plan.right, ansi)
    jt = plan.join_type
    # expand all pairs, evaluate the condition on the pair table
    li = np.repeat(np.arange(nl), max(nr, 1)) if nr else np.array([], np.int64)
    ri = np.tile(np.arange(max(nr, 1)), nl) if nr else np.array([], np.int64)
    pair_cols = [CpuCol(c.dtype, c.values[li], c.validity[li])
                 for c in lcols] +                 [CpuCol(c.dtype, c.values[ri], c.validity[ri])
                 for c in rcols] if nr else []
    npairs = nl * nr
    if plan.condition is not None and npairs:
        pred = eval_expr(plan.condition, pair_cols, npairs, ansi)
        ok = pred.values.astype(bool) & pred.validity
    else:
        ok = np.ones(npairs, np.bool_)
    matched_left = np.zeros(nl, np.bool_)
    if npairs:
        for i in range(npairs):
            if ok[i]:
                matched_left[li[i]] = True
    if jt in (PN.JoinType.LEFT_SEMI, PN.JoinType.LEFT_ANTI):
        keep = matched_left if jt == PN.JoinType.LEFT_SEMI else ~matched_left
        idx = np.nonzero(keep)[0]
        return [CpuCol(c.dtype, c.values[idx], c.validity[idx])
                for c in lcols], len(idx)
    sel = np.nonzero(ok)[0] if npairs else np.array([], np.int64)
    out = [CpuCol(c.dtype, c.values[li[sel]], c.validity[li[sel]])
           for c in lcols] +           [CpuCol(c.dtype, c.values[ri[sel]], c.validity[ri[sel]])
           for c in rcols]
    m = len(sel)
    if jt == PN.JoinType.LEFT_OUTER:
        um = np.nonzero(~matched_left)[0]
        if len(um):
            for ci, c in enumerate(lcols):
                out[ci] = CpuCol(c.dtype,
                                 np.concatenate([out[ci].values,
                                                 c.values[um]]),
                                 np.concatenate([out[ci].validity,
                                                 c.validity[um]]))
            for ci, c in enumerate(rcols):
                k = len(lcols) + ci
                pad_vals = np.zeros(len(um), dtype=c.values.dtype) \
                    if c.values.dtype != object else np.array(
                        [None] * len(um), object)
                out[k] = CpuCol(c.dtype,
                                np.concatenate([out[k].values, pad_vals]),
                                np.concatenate([out[k].validity,
                                                np.zeros(len(um),
                                                         np.bool_)]))
            m += len(um)
    return out, m


def _order_peer_key(ocols, i):
    """Order-key tuple for peer/rank comparison; NaN maps to a sentinel so
    NaN rows peer with each other (Spark: NaN == NaN in ordering — plain
    tuple equality would make every NaN its own peer group)."""
    out = []
    for oc in ocols:
        v = oc.row(i)
        if isinstance(v, (float, np.floating)) and math.isnan(v):
            v = "__nan__"
        out.append(v)
    return tuple(out)


def _cpu_window(plan: PN.Window, ansi: bool):
    cols, n = execute_cpu_plan(plan.child, ansi)
    pcols = [eval_expr(e, cols, n, ansi) for e in plan.partition_by]
    ocols = [eval_expr(e, cols, n, ansi) for e, _ in plan.order_by]
    # partition rows
    parts: Dict[tuple, List[int]] = {}
    for i in range(n):
        k = _group_key(pcols, i) if pcols else ()
        parts.setdefault(k, []).append(i)
    # order within partition
    for k, idxs in parts.items():
        for (e, spec), oc in reversed(list(zip(plan.order_by, ocols))):
            keyf = _sort_key_fn(oc, spec)
            idxs.sort(key=keyf)
    out_cols = list(cols)
    for wf in plan.functions:
        ac = (eval_expr(wf.child, cols, n, ansi)
              if wf.child is not None else None)
        vals = [None] * n
        valid = np.ones(n, np.bool_)
        for k, idxs in parts.items():
            if wf.func == "row_number":
                for r, i in enumerate(idxs):
                    vals[i] = r + 1
            elif wf.func in ("rank", "dense_rank"):
                rank = 0
                dense = 0
                prev = object()
                for r, i in enumerate(idxs):
                    cur = _order_peer_key(ocols, i)
                    if cur != prev:
                        rank = r + 1
                        dense += 1
                        prev = cur
                    vals[i] = rank if wf.func == "rank" else dense
            elif wf.func == "percent_rank":
                prev = object()
                rank = 0
                nr = len(idxs)
                for r, i in enumerate(idxs):
                    cur = _order_peer_key(ocols, i)
                    if cur != prev:
                        rank = r + 1
                        prev = cur
                    vals[i] = ((rank - 1) / (nr - 1)) if nr > 1 else 0.0
            elif wf.func == "cume_dist":
                nr = len(idxs)
                keys = [_order_peer_key(ocols, i) for i in idxs]
                for r, i in enumerate(idxs):
                    last = r
                    while last + 1 < nr and keys[last + 1] == keys[r]:
                        last += 1
                    vals[i] = (last + 1) / nr
            elif wf.func == "ntile":
                nb = max(int(wf.buckets), 1)
                nr = len(idxs)
                q, rem = divmod(nr, nb)
                for r, i in enumerate(idxs):
                    big = rem * (q + 1)
                    vals[i] = (r // (q + 1) if r < big
                               else rem + (r - big) // max(q, 1)) + 1
            elif wf.func in ("lead", "lag"):
                off = int(wf.offset) * (1 if wf.func == "lead" else -1)
                for r, i in enumerate(idxs):
                    j = r + off
                    if 0 <= j < len(idxs):
                        src = idxs[j]
                        if ac.validity[src]:
                            vals[i] = ac.values[src]
                        else:
                            vals[i] = None
                            valid[i] = False
                    elif wf.default is not None:
                        from spark_rapids_tpu.expr.base import Literal

                        vals[i] = Literal(wf.default,
                                          wf.result_type).storage_value()
                    else:
                        vals[i] = None
                        valid[i] = False
            elif wf.func in ("first_value", "last_value"):
                for r, i in enumerate(idxs):
                    sel = _frame_rows(plan, idxs, r, ocols)
                    order = sel if wf.func == "first_value" \
                        else list(reversed(sel))
                    vals[i] = None
                    valid[i] = False
                    for j in order:
                        if wf.ignore_nulls and not ac.validity[j]:
                            continue
                        if ac.validity[j]:
                            vals[i] = ac.values[j]
                            valid[i] = True
                        break
            elif wf.func in ("sum", "count", "avg", "min", "max",
                             "var_pop", "var_samp", "stddev_pop",
                             "stddev_samp"):
                # incremental/shared accumulators for the linear frames;
                # per-row _frame_rows only for peer/bounded frames (the
                # oracle is the production CPU fallback — O(n^2) frame
                # rebuilds would melt large partitions)
                if plan.frame == "running":
                    acc: List = []
                    for i in idxs:
                        if ac.validity[i]:
                            acc.append(ac.values[i])
                        vals[i] = _wagg(wf, acc, valid, i)
                elif plan.frame == "unbounded":
                    acc = [ac.values[i] for i in idxs if ac.validity[i]]
                    for i in idxs:
                        vals[i] = _wagg(wf, acc, valid, i)
                else:
                    for r, i in enumerate(idxs):
                        sel = _frame_rows(plan, idxs, r, ocols)
                        acc = [ac.values[j] for j in sel
                               if ac.validity[j]]
                        vals[i] = _wagg(wf, acc, valid, i)
            else:
                raise NotImplementedError(wf.func)
        if isinstance(wf.result_type, (T.DecimalType, T.StringType)):
            arr = np.array(vals, object)
        else:
            arr = np.array([v if v is not None else 0 for v in vals],
                           T.storage_dtype(wf.result_type))
        out_cols.append(CpuCol(wf.result_type, arr, valid))
    return out_cols, n


def _frame_rows(plan: PN.Window, idxs, r, ocols):
    """Row indices in the window frame of sorted-position ``r``
    (frame forms per plan.nodes.normalize_frame)."""
    fr = plan.frame
    nr = len(idxs)
    if fr == "running":
        return idxs[:r + 1]
    if fr == "unbounded":
        return idxs
    if fr == "range_running":
        # peers (equal order keys, nulls peer with nulls) are included
        kr = _order_peer_key(ocols, idxs[r])
        last = r
        while last + 1 < nr and \
                _order_peer_key(ocols, idxs[last + 1]) == kr:
            last += 1
        return idxs[:last + 1]
    if fr[0] == "rows":
        lo = max(0, r - int(fr[1]))
        hi = min(nr, r + int(fr[2]) + 1)
        return idxs[lo:hi]
    # ("range", lo, hi) over the single (numeric) order key.  "PRECEDING"
    # means towards the partition start, so the value-space bounds flip for
    # descending order.  Null order keys frame only their null peers.
    lo_off, hi_off = fr[1], fr[2]
    ov = ocols[0]
    i = idxs[r]
    if not ov.validity[i]:
        return [j for j in idxs if not ov.validity[j]]
    asc = plan.order_by[0][1].ascending
    v = ov.values[i]
    if isinstance(v, (float, np.floating)) and math.isnan(v):
        # NaN order keys frame their NaN peers (Spark: NaN == NaN in
        # ordering; NaN ± offset comparisons would otherwise all be False)
        return [j for j in idxs
                if ov.validity[j]
                and isinstance(ov.values[j], (float, np.floating))
                and math.isnan(ov.values[j])]
    if isinstance(v, (int, np.integer)) and not isinstance(v, bool):
        # exact python-int arithmetic: np.int64 boundaries would wrap at
        # the extremes (the device side saturates, which is equivalent)
        v = int(v)
        lo_v = v - int(lo_off) if asc else v - int(hi_off)
        hi_v = v + int(hi_off) if asc else v + int(lo_off)
        return [j for j in idxs
                if ov.validity[j] and lo_v <= int(ov.values[j]) <= hi_v]
    lo_v = v - lo_off if asc else v - hi_off
    hi_v = v + hi_off if asc else v + lo_off
    return [j for j in idxs
            if ov.validity[j] and lo_v <= ov.values[j] <= hi_v]


def _wagg(wf, acc, valid, i):
    if wf.func == "count":
        return len(acc)
    if wf.func in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
        xs = [float(v) for v in acc]
        n = len(xs)
        den = n if wf.func.endswith("pop") else n - 1
        if den <= 0:  # Spark nullOnDivideByZero: samp of n<=1 -> NULL
            valid[i] = False
            return None
        mean = sum(xs) / n
        m2 = sum((x - mean) ** 2 for x in xs)
        var = m2 / den
        return var if wf.func.startswith("var") else math.sqrt(var)
    if not acc:
        valid[i] = False
        return None
    if wf.func == "sum":
        return sum(acc) if not isinstance(acc[0], float) else float(sum(acc))
    if wf.func == "avg":
        return float(sum(float(v) for v in acc)) / len(acc)
    floats = isinstance(acc[0], float) or isinstance(acc[0], np.floating)
    if wf.func == "min":
        if floats:
            # Spark total order: NaN is the GREATEST value — min prefers
            # any non-NaN (python min() is positional on NaN)
            non_nan = [v for v in acc if not math.isnan(v)]
            return min(non_nan) if non_nan else float("nan")
        return min(acc)
    if wf.func == "max":
        if floats:
            if any(math.isnan(float(v)) for v in acc):
                return float("nan")
            return max(acc)
        return max(acc)
    raise NotImplementedError(wf.func)


# -- round-5 breadth: luhn/binary/bitmap/number-format/xml/avro/etc ----------

def _h_luhn(e, cols, n, ansi):
    (s,) = _kids(e, cols, n, ansi)
    out = np.zeros(n, np.bool_)
    for i in range(n):
        if not s.validity[i]:
            continue
        t = s.values[i]
        if not t or not t.isdigit():
            continue
        total = 0
        for j, ch in enumerate(reversed(t)):
            d = ord(ch) - 48
            if j % 2 == 1:
                d *= 2
                if d > 9:
                    d -= 9
            total += d
        out[i] = total % 10 == 0
    return CpuCol(T.BOOLEAN, out, s.validity.copy())


def _h_empty2null(e, cols, n, ansi):
    (s,) = _kids(e, cols, n, ansi)
    validity = s.validity & np.array(
        [bool(v) for v in s.values], np.bool_)
    return CpuCol(T.STRING, s.values.copy(), validity)


def _h_unary_positive(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    return c


def _h_to_binary(e, cols, n, ansi):
    import base64 as b64

    kids = _kids(e, cols, n, ansi)
    s = kids[0]
    fmt = e._fmt
    out = np.empty(n, object)
    validity = s.validity.copy()
    bad = np.zeros(n, np.bool_)
    for i in range(n):
        if not validity[i]:
            out[i] = None
            continue
        t = s.values[i]
        if fmt in ("utf-8", "utf8"):
            out[i] = t
            continue
        try:
            if fmt == "hex":
                if not all(c2 in "0123456789abcdefABCDEF" for c2 in t):
                    raise ValueError
                tt = ("0" + t) if len(t) % 2 else t
                out[i] = bytes.fromhex(tt).decode("utf-8", "replace")
            else:
                out[i] = b64.b64decode(t.encode(), validate=True).decode(
                    "utf-8", "replace")
        except Exception:
            out[i] = None
            validity[i] = False
            bad[i] = True
    if not e._try and ansi and bad.any():
        raise E.SparkArithmeticException(
            f"to_binary: malformed {fmt} input")
    return CpuCol.from_objs(list(out), T.STRING)


def _h_bitmap_bit_position(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    v = c.values.astype(np.int64)
    adj = np.where(v > 0, v - 1, v)
    pos = np.remainder(adj, 32768)
    return CpuCol(T.LONG, pos.astype(np.int64), c.validity.copy())


def _h_bitmap_bucket_number(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    v = c.values.astype(np.int64)
    adj = np.where(v > 0, v - 1, v)
    b = np.floor_divide(adj, 32768)
    b = np.where(v > 0, b + 1, b)
    return CpuCol(T.LONG, b.astype(np.int64), c.validity.copy())


def _h_bitmap_count(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)
    out = np.zeros(n, np.int64)
    for i in range(n):
        if c.validity[i] and c.values[i] is not None:
            out[i] = sum(bin(b).count("1")
                         for b in c.values[i].encode("utf-8", "replace"))
    return CpuCol(T.LONG, out, c.validity.copy())


def _h_randn(e, cols, n, ansi):
    from spark_rapids_tpu.expr.base import Literal as _L

    seed = 0
    ch = e.child
    if isinstance(ch, _L) and ch.value is not None:
        seed = int(ch.value)
    idx = np.arange(n, dtype=np.uint64)

    def unit(salt):
        z = idx * np.uint64(0x9E3779B97F4A7C15) + np.uint64(salt)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
        return (z >> np.uint64(11)).astype(np.float64) / float(1 << 53)

    u1 = unit((seed * 2654435769 + 1) % (1 << 64))
    u2 = unit((seed * 2654435769 + 2) % (1 << 64))
    r = np.sqrt(-2.0 * np.log(np.maximum(u1, 1e-300)))
    out = r * np.cos(2.0 * np.pi * u2)
    return CpuCol(T.DOUBLE, out, np.ones(n, np.bool_))


def _h_sentences(e, cols, n, ansi):
    import re as _re

    (s,) = _kids(e, cols, n, ansi)[:1]
    out = np.empty(n, object)
    for i in range(n):
        if not s.validity[i]:
            out[i] = None
            continue
        sents = [x for x in _re.split(r"[.!?]+", s.values[i]) if x.strip()]
        out[i] = [[w for w in _re.split(r"[^\w']+", x) if w]
                  for x in sents]
    return CpuCol(e.dataType, out, s.validity.copy())


def _h_try_element_at(e, cols, n, ansi):
    return _h_element_at(e, cols, n, ansi)


def _h_cardinality(e, cols, n, ansi):
    (a,) = _kids(e, cols, n, ansi)
    out = np.zeros(n, np.int32)
    for i in range(n):
        if a.validity[i] and a.values[i] is not None:
            out[i] = len(a.values[i])
    return CpuCol(T.INT, out, a.validity.copy())


def _h_map_from_entries(e, cols, n, ansi):
    (a,) = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    validity = a.validity.copy()
    for i in range(n):
        if not validity[i]:
            continue
        entries = a.values[i]
        m = {}
        for kv in entries:
            if kv is None:
                validity[i] = False
                break
            k, v = (kv if isinstance(kv, tuple) else tuple(kv))
            if k is None:
                raise E.SparkArithmeticException(
                    "Cannot use null as map key")
            if k in m:
                raise E.SparkArithmeticException(
                    "Duplicate map key was found")
            m[k] = v
        else:
            out[i] = m
    return CpuCol(e.dataType, out, validity)


def _h_map_sort(e, cols, n, ansi):
    (m,) = _kids(e, cols, n, ansi)
    out = np.empty(n, object)
    for i in range(n):
        if m.validity[i] and m.values[i] is not None:
            out[i] = dict(sorted(m.values[i].items()))
    return CpuCol(e.dataType, out, m.validity.copy())


def _h_shuffle(e, cols, n, ansi):
    (a,) = _kids(e, cols, n, ansi)
    seed = getattr(e, "_seed", 0)
    out = np.empty(n, object)
    for i in range(n):
        if not a.validity[i] or a.values[i] is None:
            continue
        arr = list(a.values[i])
        w = len(arr)
        ranks = []
        np.seterr(over="ignore")     # uint64 mix wraps by design
        for j in range(w):
            idx = np.uint64(i) * np.uint64(1 << 17) + np.uint64(j)
            z = idx * np.uint64(0x9E3779B97F4A7C15) + np.uint64(
                (seed * 2654435769 + 11) % (1 << 64))
            z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            ranks.append(np.int64(z ^ (z >> np.uint64(31))))
        order = sorted(range(w), key=lambda j: ranks[j])
        np.seterr(over="warn")
        out[i] = [arr[j] for j in order]
    return CpuCol(e.dataType, out, a.validity.copy())


def _h_parse_to_date(e, cols, n, ansi):
    inner = type(e).__mro__  # noqa: F841  (delegation below)
    from spark_rapids_tpu.expr.datetime import ToDate as _TD, \
        ToTimestamp as _TT

    name = type(e).__name__
    d = (_TD if name == "ParseToDate" else _TT)(e.children[0])
    d._resolve_type()
    return eval_expr(d, cols, n, ansi if name != "TryToTimestamp" else False)


def _h_to_number(e, cols, n, ansi):
    import re as _re
    from decimal import Decimal as _D

    kids = _kids(e, cols, n, ansi)
    s = kids[0]
    spec = e._spec
    scale = spec["scale"]
    out = np.empty(n, object)
    validity = s.validity.copy()
    for i in range(n):
        if not validity[i]:
            out[i] = None
            continue
        t = s.values[i].strip()
        sign = ""
        if spec["sign"] == "S_START" and t[:1] in "+-":
            sign, t = t[0], t[1:]
        if spec["currency"]:
            if not t.startswith("$"):
                out[i] = None
                validity[i] = False
                continue
            t = t[1:]
        if spec["sign"] == "S_END" and t[-1:] in "+-":
            sign, t = t[-1], t[:-1]
        elif spec["sign"] == "MI" and t.endswith("-"):
            sign, t = "-", t[:-1]
        fr = r"(?:\.([0-9]{0,%d}))?" % scale if scale else "()?"
        pat = (r"^([0-9][0-9,]*)?" if spec["grouping"]
               else r"^([0-9]+)?") + fr + "$"
        m2 = _re.match(pat, t)
        if not m2 or (not (m2.group(1) or "") and not (m2.group(2) or "")):
            out[i] = None
            validity[i] = False
            continue
        digits = (m2.group(1) or "").replace(",", "")
        fpart = (m2.group(2) or "")
        if len(digits.lstrip("0")) > spec["int_digits"]:
            out[i] = None
            validity[i] = False
            continue
        unscaled = int((digits or "0") + fpart.ljust(scale, "0"))
        if sign == "-":
            unscaled = -unscaled
        out[i] = unscaled     # CpuCol decimal storage = unscaled int
    if not e._try and ansi:
        bad = s.validity & ~validity
        if bad.any():
            raise E.SparkArithmeticException(
                "to_number: input does not match the format")
    return CpuCol.from_objs(
        [None if v is None else v for v in out], e.dataType)


def _h_to_character(e, cols, n, ansi):
    from decimal import Decimal as _D

    kids = _kids(e, cols, n, ansi)
    c = kids[0]
    spec = e._spec
    scale = spec["scale"]
    in_dt = e.children[0]._dataType
    out = np.empty(n, object)
    validity = c.validity.copy()
    for i in range(n):
        if not validity[i]:
            out[i] = None
            continue
        v = c.values[i]
        in_scale = in_dt.scale if isinstance(in_dt, T.DecimalType) else 0
        v = (v if isinstance(v, _D)
             else _D(int(v)).scaleb(-in_scale))
        q = v.quantize(_D(1).scaleb(-scale)) if scale else v.quantize(_D(1))
        neg = q < 0
        digits = format(abs(q), "f")
        ipart, _, fpart = digits.partition(".")
        if len(ipart.lstrip("0") or "") > spec["int_digits"]:
            out[i] = "#" * (spec["precision"] + (1 if scale else 0))
            continue
        if spec["grouping"]:
            rev = ipart[::-1]
            ipart = ",".join(rev[j:j + 3]
                             for j in range(0, len(rev), 3))[::-1]
        s2 = ipart + (("." + fpart.ljust(scale, "0")) if scale else "")
        if spec["currency"]:
            s2 = "$" + s2
        if spec["sign"] == "S_START":
            s2 = ("-" if neg else "+") + s2
        elif spec["sign"] == "S_END":
            s2 = s2 + ("-" if neg else "+")
        elif spec["sign"] == "MI":
            s2 = s2 + ("-" if neg else " ")
        elif neg:
            s2 = "-" + s2
        out[i] = s2
    return CpuCol.from_objs(list(out), T.STRING)


def _h_input_file_name(e, cols, n, ansi):
    from spark_rapids_tpu.expr.misc import CURRENT_INPUT_FILE

    path = getattr(cols, "input_file", None)
    if path is None:
        path = CURRENT_INPUT_FILE[0]
    return CpuCol.from_objs([path or ""] * n, T.STRING)


def _h_from_avro(e, cols, n, ansi):
    from spark_rapids_tpu.io.avro import _Reader, _decode_value

    (c,) = _kids(e, cols, n, ansi)[:1]
    st = e.dataType
    out = np.empty(n, object)
    validity = c.validity.copy()
    for i in range(n):
        if not validity[i]:
            continue
        try:
            r = _Reader(c.values[i].encode("latin-1", "replace")
                        if isinstance(c.values[i], str) else c.values[i])
            rec = _decode_value(r, e._avro_schema)
            out[i] = tuple(rec.get(f.name) for f in st.fields)
        except Exception:
            validity[i] = False
    return CpuCol(st, out, validity)


def _h_to_avro(e, cols, n, ansi):
    from spark_rapids_tpu.io.avro import _encode_value

    (c,) = _kids(e, cols, n, ansi)[:1]
    st = e.children[0]._dataType
    out = np.empty(n, object)
    for i in range(n):
        if not c.validity[i]:
            continue
        row = c.values[i]
        rec = {f.name: (row[j] if not isinstance(row, dict)
                        else row.get(f.name))
               for j, f in enumerate(st.fields)}
        buf = bytearray()
        _encode_value(buf, e._avro_schema, rec)
        out[i] = bytes(buf).decode("latin-1")
    return CpuCol.from_objs(list(out), T.STRING)


def _h_from_xml(e, cols, n, ansi):
    import xml.etree.ElementTree as _ET

    (c,) = _kids(e, cols, n, ansi)[:1]
    st = e.schema
    out = np.empty(n, object)
    validity = c.validity.copy()
    from spark_rapids_tpu.expr.jsonexprs import convert_json_field as _cjf
    for i in range(n):
        if not validity[i]:
            continue
        try:
            root = _ET.fromstring(c.values[i])
        except _ET.ParseError:
            out[i] = tuple([None] * len(st.fields))
            continue
        vals = []
        for f in st.fields:
            el = root.find(f.name)
            txt = None if el is None else (el.text or "")
            if txt is None:
                vals.append(None)
                continue
            sv = txt
            if not isinstance(f.dataType, T.StringType):
                try:
                    if isinstance(f.dataType, T.BooleanType):
                        sv = txt.strip().lower() == "true"
                    elif isinstance(f.dataType, (T.FloatType, T.DoubleType)):
                        sv = float(txt)
                    else:
                        sv = int(txt.strip())
                except ValueError:
                    vals = [None] * len(st.fields)
                    break
            ok, sv = _cjf(sv, f.dataType)
            if not ok:
                vals = [None] * len(st.fields)
                break
            vals.append(sv)
        out[i] = tuple(vals)
    return CpuCol(st, out, validity)


def _h_to_xml(e, cols, n, ansi):
    (c,) = _kids(e, cols, n, ansi)[:1]
    st = e.children[0]._dataType
    out = np.empty(n, object)

    def esc(s):
        return (s.replace("&", "&amp;").replace("<", "&lt;")
                .replace(">", "&gt;"))

    for i in range(n):
        if not c.validity[i]:
            continue
        row = c.values[i]
        body = []
        for j, f in enumerate(st.fields):
            v = row[j] if not isinstance(row, dict) else row.get(f.name)
            if v is None:
                continue
            if isinstance(f.dataType, T.StringType):
                sv = esc(str(v))
            elif isinstance(f.dataType, T.BooleanType):
                sv = "true" if v else "false"
            elif isinstance(f.dataType, (T.FloatType, T.DoubleType)):
                sv = repr(float(v))
            else:
                sv = str(int(v))
            body.append(f"<{f.name}>{sv}</{f.name}>")
        out[i] = "<row>" + "".join(body) + "</row>"
    return CpuCol.from_objs(list(out), T.STRING)


_HANDLERS.update({
    "Luhn": _h_luhn,
    "Empty2Null": _h_empty2null,
    "UnaryPositive": _h_unary_positive,
    "ToBinary": _h_to_binary, "TryToBinary": _h_to_binary,
    "BitmapBitPosition": _h_bitmap_bit_position,
    "BitmapBucketNumber": _h_bitmap_bucket_number,
    "BitmapCount": _h_bitmap_count,
    "Randn": _h_randn,
    "Sentences": _h_sentences,
    "TryElementAt": _h_try_element_at,
    "Cardinality": _h_cardinality,
    "MapFromEntries": _h_map_from_entries,
    "MapSort": _h_map_sort,
    "Shuffle": _h_shuffle,
    "ParseToDate": _h_parse_to_date,
    "ParseToTimestamp": _h_parse_to_date,
    "TryToTimestamp": _h_parse_to_date,
    "ToNumber": _h_to_number, "TryToNumber": _h_to_number,
    "ToCharacter": _h_to_character,
    "InputFileName": _h_input_file_name,
    "AvroDataToCatalyst": _h_from_avro,
    "CatalystDataToAvro": _h_to_avro,
    "XmlToStructs": _h_from_xml,
    "StructsToXml": _h_to_xml,
})


def _h_extract(e, cols, n, ansi):
    from spark_rapids_tpu.expr.datetime import _EXTRACT_FIELDS
    from spark_rapids_tpu.expr.base import Literal as _L

    f = e.children[0]
    name = str(f.value).lower() if isinstance(f, _L) else None
    cls = _EXTRACT_FIELDS.get(name)
    if cls is None:
        if name == "epoch":
            (src_col,) = [eval_expr(e.children[1], cols, n, ansi)]
            out = np.zeros(n, np.int64)
            for i in range(n):
                if src_col.validity[i]:
                    v = int(src_col.values[i])
                    # date days -> seconds; timestamps are micros
                    if isinstance(e.children[1]._dataType, T.DateType):
                        out[i] = v * 86400
                    else:
                        out[i] = v // 1_000_000
            return CpuCol(T.LONG, out, src_col.validity.copy())
        raise NotImplementedError(f"oracle extract field {name!r}")
    d = cls(e.children[1])
    d._resolve_type()
    return eval_expr(d, cols, n, ansi)


_HANDLERS["Extract"] = _h_extract
