"""Expression tree core — the GpuExpression analog.

Reference analog: com/nvidia/spark/rapids/GpuExpression (columnarEval
returning a GpuColumnVector) plus Spark Catalyst's Expression/BoundReference/
Literal/Alias.  TPU-first difference: ``eval_tpu`` is *traceable* — it runs
under ``jax.jit`` as part of a whole-stage fused program, so an entire
project/filter chain compiles to one XLA executable (the reference needs
GpuTieredProject + cuDF AST fusion to approximate this; XLA gives it to us).

Every expression:
  * knows its resolved ``dataType`` and ``nullable``;
  * evaluates on device via ``eval_tpu(ctx) -> DeviceColumn`` (jnp ops only —
    no host syncs, no data-dependent Python control flow);
  * is independently re-implemented by the CPU oracle
    (spark_rapids_tpu/cpu/oracle.py) which the differential test harness
    treats as golden, mirroring how the reference tests GPU vs CPU Spark.

Spark null semantics: unless an expression overrides ``null_intolerant``
machinery, output validity = AND of input validities (null-propagating).
Three-valued logic (And/Or), Coalesce, IsNull etc. override eval entirely.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.batch import ColumnarBatch


class SparkArithmeticException(Exception):
    """ANSI-mode overflow / invalid operation (matches Spark's error class)."""


@dataclasses.dataclass
class EvalContext:
    """Per-batch evaluation context threaded through eval_tpu.

    ansi errors: device-side ops cannot raise, so ANSI violations set flags
    collected here; ``check_errors`` syncs once per batch at the stage
    boundary (the TPU analog of cuDF kernels throwing from device checks).
    """

    batch: ColumnarBatch
    ansi: bool = False
    error_flags: List = dataclasses.field(default_factory=list)
    # absolute row position of this batch's first row (host int; consumed
    # by Rand / monotonically_increasing_id, which force the eager stage
    # path so the value is concrete)
    row_offset: int = 0

    @property
    def num_rows(self) -> int:
        return self.batch.num_rows

    @property
    def row_mask(self) -> jax.Array:
        return self.batch.row_mask

    def add_error(self, flag_per_row: jax.Array, message: str):
        self.error_flags.append((flag_per_row & self.row_mask, message))

    def check_errors(self):
        for flags, message in self.error_flags:
            if bool(jnp.any(flags)):
                raise SparkArithmeticException(message)
        self.error_flags.clear()


def contains_host_kernel(e: "Expression") -> bool:
    """True if any node evaluates through a host kernel — the enclosing
    stage then runs eagerly, op by op, instead of as one jitted program
    (whether such stages should jit around a ``pure_callback`` on the
    chip is an open speed question, see ROADMAP.md)."""
    return bool(e.collect(lambda x: getattr(x, "is_host_kernel", False)))


def call_host_kernel(fn, shapes, *args):
    """Run a host kernel over device arrays.

    Under a trace: jax.pure_callback.  Concrete arrays (the eager stage
    path, see contains_host_kernel): call the kernel directly on host
    copies — no program is compiled for the hop."""
    import jax.core

    if any(isinstance(a, jax.core.Tracer) for a in args):
        return jax.pure_callback(fn, shapes, *args)
    res = fn(*(np.asarray(a) for a in args))
    return jax.tree_util.tree_map(jnp.asarray, res)


class Expression:
    """Base expression; subclasses set children and implement do_columnar_eval."""

    is_host_kernel = False  # True: evaluates via jax.pure_callback

    def __init__(self, children: Sequence["Expression"] = ()):
        self.children: List[Expression] = list(children)
        self._dataType: Optional[T.DataType] = None
        self._nullable: bool = True
        self.resolved: bool = False

    # -- naming -------------------------------------------------------------
    @property
    def pretty_name(self) -> str:
        return type(self).__name__

    @property
    def name(self) -> str:
        return self.sql_string()

    def sql_string(self) -> str:
        args = ", ".join(c.sql_string() for c in self.children)
        return f"{self.pretty_name.lower()}({args})"

    # -- typing -------------------------------------------------------------
    @property
    def dataType(self) -> T.DataType:
        assert self._dataType is not None, f"{self} not resolved"
        return self._dataType

    @property
    def nullable(self) -> bool:
        return self._nullable

    def resolve(self, schema: T.StructType) -> "Expression":
        """Bind attribute references and compute output types, bottom-up.

        Returns self (mutated) for chaining; mirrors Catalyst analysis enough
        for the harness — real Spark would hand us a resolved tree.
        """
        self.children = [c.resolve(schema) for c in self.children]
        self._resolve_type()
        self.resolved = True
        return self

    def _resolve_type(self):
        """Subclasses compute self._dataType / self._nullable here."""
        raise NotImplementedError(type(self).__name__)

    # -- device evaluation --------------------------------------------------
    def eval_tpu(self, ctx: EvalContext) -> DeviceColumn:
        cols = [c.eval_tpu(ctx) for c in self.children]
        return self.do_columnar_eval(ctx, cols)

    def do_columnar_eval(self, ctx: EvalContext,
                         cols: List[DeviceColumn]) -> DeviceColumn:
        raise NotImplementedError(type(self).__name__)

    # -- helpers ------------------------------------------------------------
    @staticmethod
    def and_validity(cols: Sequence[DeviceColumn]) -> jax.Array:
        v = cols[0].validity
        for c in cols[1:]:
            v = v & c.validity
        return v

    def map_children(self, fn) -> "Expression":
        self.children = [fn(c) for c in self.children]
        return self

    def transform_up(self, fn) -> "Expression":
        self.children = [c.transform_up(fn) for c in self.children]
        return fn(self)

    def collect(self, pred) -> List["Expression"]:
        out = []
        for c in self.children:
            out.extend(c.collect(pred))
        if pred(self):
            out.append(self)
        return out

    def __repr__(self):
        return self.sql_string()

    # -- operator sugar for the DataFrame API -------------------------------
    def _bin(self, other, cls):
        return cls(self, _wrap(other))

    def __add__(self, o):
        from spark_rapids_tpu.expr.arithmetic import Add
        return self._bin(o, Add)

    def __sub__(self, o):
        from spark_rapids_tpu.expr.arithmetic import Subtract
        return self._bin(o, Subtract)

    def __mul__(self, o):
        from spark_rapids_tpu.expr.arithmetic import Multiply
        return self._bin(o, Multiply)

    def __truediv__(self, o):
        from spark_rapids_tpu.expr.arithmetic import Divide
        return self._bin(o, Divide)

    def __mod__(self, o):
        from spark_rapids_tpu.expr.arithmetic import Remainder
        return self._bin(o, Remainder)

    def __neg__(self):
        from spark_rapids_tpu.expr.arithmetic import UnaryMinus
        return UnaryMinus(self)

    def __lt__(self, o):
        from spark_rapids_tpu.expr.predicates import LessThan
        return self._bin(o, LessThan)

    def __le__(self, o):
        from spark_rapids_tpu.expr.predicates import LessThanOrEqual
        return self._bin(o, LessThanOrEqual)

    def __gt__(self, o):
        from spark_rapids_tpu.expr.predicates import GreaterThan
        return self._bin(o, GreaterThan)

    def __ge__(self, o):
        from spark_rapids_tpu.expr.predicates import GreaterThanOrEqual
        return self._bin(o, GreaterThanOrEqual)

    def eq(self, o):
        from spark_rapids_tpu.expr.predicates import EqualTo
        return self._bin(o, EqualTo)

    def __and__(self, o):
        from spark_rapids_tpu.expr.predicates import And
        return self._bin(o, And)

    def __or__(self, o):
        from spark_rapids_tpu.expr.predicates import Or
        return self._bin(o, Or)

    def __invert__(self):
        from spark_rapids_tpu.expr.predicates import Not
        return Not(self)

    def is_null(self):
        from spark_rapids_tpu.expr.predicates import IsNull
        return IsNull(self)

    def is_not_null(self):
        from spark_rapids_tpu.expr.predicates import IsNotNull
        return IsNotNull(self)

    def cast(self, dt: T.DataType):
        from spark_rapids_tpu.expr.cast import Cast
        return Cast(self, dt)

    def alias(self, name: str):
        return Alias(self, name)

    def isin(self, *values):
        from spark_rapids_tpu.expr.predicates import In
        return In(self, [lit(v) for v in values])

    def substr(self, pos, length):
        from spark_rapids_tpu.expr.strings import Substring
        return Substring(self, _wrap(pos), _wrap(length))


def _wrap(v) -> Expression:
    return v if isinstance(v, Expression) else Literal.of(v)


class AttributeReference(Expression):
    """Unresolved column-by-name; resolve() binds it to an ordinal."""

    def __init__(self, colname: str):
        super().__init__()
        self.colname = colname

    def sql_string(self):
        return self.colname

    def resolve(self, schema: T.StructType) -> Expression:
        names = schema.field_names()
        matches = [i for i, n in enumerate(names) if n == self.colname]
        if not matches:
            matches = [i for i, n in enumerate(names)
                       if n.lower() == self.colname.lower()]
        if len(matches) != 1:
            raise KeyError(
                f"cannot resolve column '{self.colname}' in {names}")
        i = matches[0]
        return BoundReference(i, schema.fields[i].dataType,
                              schema.fields[i].nullable, name=self.colname)

    def _resolve_type(self):
        raise AssertionError("AttributeReference must be bound")


class BoundReference(Expression):
    def __init__(self, ordinal: int, dtype: T.DataType, nullable: bool = True,
                 name: Optional[str] = None):
        super().__init__()
        self.ordinal = ordinal
        self._dataType = dtype
        self._nullable = nullable
        self._name = name
        self.resolved = True

    def sql_string(self):
        return self._name or f"input[{self.ordinal}]"

    def resolve(self, schema):
        return self

    def eval_tpu(self, ctx: EvalContext) -> DeviceColumn:
        return ctx.batch.columns[self.ordinal]


class Literal(Expression):
    def __init__(self, value: Any, dtype: T.DataType):
        super().__init__()
        self.value = value
        self._dataType = dtype
        self._nullable = value is None
        self.resolved = True

    @staticmethod
    def of(v) -> "Literal":
        import datetime as _dt
        from decimal import Decimal as _Dec

        if v is None:
            return Literal(None, T.NULL)
        if isinstance(v, bool):
            return Literal(v, T.BOOLEAN)
        if isinstance(v, int):
            return Literal(v, T.INT if -(2**31) <= v < 2**31 else T.LONG)
        if isinstance(v, float):
            return Literal(v, T.DOUBLE)
        if isinstance(v, str):
            return Literal(v, T.STRING)
        if isinstance(v, _Dec):
            sign, digits, exp = v.as_tuple()
            scale = max(0, -exp)
            precision = max(len(digits), scale + 1)
            return Literal(v, T.DecimalType(min(precision, 38), scale))
        if isinstance(v, _dt.datetime):
            epoch = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
            vv = v if v.tzinfo else v.replace(tzinfo=_dt.timezone.utc)
            return Literal(int((vv - epoch).total_seconds() * 1_000_000),
                           T.TIMESTAMP)
        if isinstance(v, _dt.date):
            return Literal((v - _dt.date(1970, 1, 1)).days, T.DATE)
        raise TypeError(f"cannot make literal from {type(v)}")

    def sql_string(self):
        return repr(self.value)

    def resolve(self, schema):
        return self

    def storage_value(self):
        """Value in storage representation (decimal -> unscaled int, etc.)."""
        from decimal import Decimal as _Dec

        v = self.value
        if isinstance(self._dataType, T.DecimalType) and isinstance(v, _Dec):
            return int(v.scaleb(self._dataType.scale).to_integral_value())
        return v

    def eval_tpu(self, ctx: EvalContext) -> DeviceColumn:
        cap = ctx.batch.capacity
        dt = self._dataType
        if self.value is None:
            validity = jnp.zeros(cap, jnp.bool_)
            if isinstance(dt, T.StringType):
                return DeviceColumn(dt, validity,
                                    chars=jnp.zeros((cap, 8), jnp.uint8),
                                    lengths=jnp.zeros(cap, jnp.int32))
            if isinstance(dt, T.DecimalType) and dt.is_128:
                return DeviceColumn(dt, validity,
                                    data=jnp.zeros((cap, 2), jnp.int64))
            sdt = T.storage_dtype(dt) if not isinstance(dt, T.NullType) else np.int32
            return DeviceColumn(dt, validity, data=jnp.zeros(cap, sdt))
        validity = jnp.ones(cap, jnp.bool_)
        if isinstance(dt, T.StringType):
            b = self.value.encode("utf-8")
            width = max(len(b), 1)
            row = np.zeros(width, np.uint8)
            row[: len(b)] = np.frombuffer(b, np.uint8)
            chars = jnp.broadcast_to(jnp.asarray(row), (cap, width))
            return DeviceColumn(dt, validity, chars=chars,
                                lengths=jnp.full(cap, len(b), jnp.int32))
        sdt = T.storage_dtype(dt)
        if isinstance(dt, T.DecimalType) and dt.is_128:
            from spark_rapids_tpu.expr.decimal128 import limbs_of

            hi, lo = limbs_of(int(self.storage_value()))
            return DeviceColumn(dt, validity, data=jnp.broadcast_to(
                jnp.asarray([hi, lo], jnp.int64), (cap, 2)))
        return DeviceColumn(dt, validity,
                            data=jnp.full(cap, self.storage_value(), sdt))


class Alias(Expression):
    def __init__(self, child: Expression, alias_name: str):
        super().__init__([child])
        self.alias_name = alias_name

    def sql_string(self):
        return f"{self.children[0].sql_string()} AS {self.alias_name}"

    @property
    def name(self):
        return self.alias_name

    def _resolve_type(self):
        self._dataType = self.children[0].dataType
        self._nullable = self.children[0].nullable

    def eval_tpu(self, ctx):
        return self.children[0].eval_tpu(ctx)


def col(name: str) -> AttributeReference:
    return AttributeReference(name)


def lit(v) -> Literal:
    return Literal.of(v)


class UnaryExpression(Expression):
    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def child(self):
        return self.children[0]


class BinaryExpression(Expression):
    def __init__(self, left: Expression, right: Expression):
        super().__init__([left, right])

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]
