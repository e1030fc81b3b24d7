"""CPU physical plan nodes — the stand-in for Spark's SparkPlan tree.

The reference is a *plugin*: Spark hands it a physical plan and GpuOverrides
rewrites it (SURVEY.md §3.2).  This framework is standalone (no JVM in the
loop), so it carries its own Catalyst-shaped physical plan; the node names
deliberately mirror Spark's (ProjectExec, FilterExec, HashAggregateExec,
SortMergeJoinExec, ShuffleExchangeExec...) so that the overrides layer, the
fallback-explain output, and the tests read exactly like the reference's.

Every node can execute on CPU via the oracle (spark_rapids_tpu/cpu/) — that
CPU path plays the role CPU Spark plays for the reference: the golden
differential baseline AND the fallback target for untagged nodes.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import List, Optional, Sequence, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.base import Alias, Expression
from spark_rapids_tpu.ops.sortkeys import SortSpec


class SparkPlan:
    """Base physical plan node (CPU side)."""

    def __init__(self, children: Sequence["SparkPlan"]):
        self.children: List[SparkPlan] = list(children)

    @property
    def output(self) -> T.StructType:
        raise NotImplementedError

    @property
    def node_name(self) -> str:
        return type(self).__name__

    def pretty(self, indent: int = 0) -> str:
        s = "  " * indent + self.describe()
        for c in self.children:
            s += "\n" + c.pretty(indent + 1)
        return s

    def describe(self) -> str:
        return self.node_name

    def with_new_children(self, children: Sequence["SparkPlan"]) -> "SparkPlan":
        import copy

        n = copy.copy(self)
        n.children = list(children)
        return n


class LocalTableScan(SparkPlan):
    def __init__(self, host_columns, schema: T.StructType):
        super().__init__([])
        self.host_columns = host_columns  # List[HostColumn]
        self._schema = schema
        # a narrowed copy (plan/pruning.py) names the user's node and the
        # ordinals it emits of it: the device cache stays on that node,
        # one entry per column, so re-planning uploads nothing twice
        self.origin = self
        self.ordinals = list(range(len(schema.fields)))

    @property
    def output(self):
        return self._schema

    def narrowed(self, keep: Sequence[int]) -> "LocalTableScan":
        """The scan of the columns ``keep`` (ordinals of this node)."""
        n = LocalTableScan([self.host_columns[i] for i in keep],
                           T.StructType([self._schema.fields[i]
                                         for i in keep]))
        n.origin = self.origin
        n.ordinals = [self.ordinals[i] for i in keep]
        return n

    def describe(self):
        return f"LocalTableScan {self._schema.simpleString}"


class CachedRelation(SparkPlan):
    """df.cache(): materialized child batches reused across actions.

    Reference analog: InMemoryRelation backed by the
    ParquetCachedBatchSerializer (SURVEY.md §2.8) — the plugin caches
    DataFrames as device-encodable batches.  Here the cache holds DEVICE
    batches registered with the spill framework, so cached data is
    reclaimable under memory pressure like any other batch."""

    def __init__(self, child: SparkPlan):
        super().__init__([child])
        self.cache_slot = {}       # filled by the exec / oracle on first run

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def describe(self):
        return "InMemoryRelation [cached]"


class FileSourceScan(SparkPlan):
    def __init__(self, fmt: str, paths: List[str], schema: T.StructType,
                 pushed_filters: Optional[List[Expression]] = None,
                 options: Optional[dict] = None):
        super().__init__([])
        self.fmt = fmt
        self.paths = list(paths)
        self._schema = schema
        self.pushed_filters = list(pushed_filters or [])
        self.options = dict(options or {})

    @property
    def output(self):
        return self._schema

    def narrowed(self, keep: Sequence[int]) -> "FileSourceScan":
        """The scan of the columns ``keep``: the output schema is the
        ``columns=`` of the read, so decode, padding and H2D shrink too."""
        return FileSourceScan(
            self.fmt, self.paths,
            T.StructType([self._schema.fields[i] for i in keep]),
            self.pushed_filters, self.options)

    def describe(self):
        return f"FileSourceScan {self.fmt} {len(self.paths)} files"


class RangeNode(SparkPlan):
    """spark.range(start, end, step) — GpuRangeExec analog."""

    def __init__(self, start: int, end: int, step: int = 1):
        super().__init__([])
        self.start, self.end, self.step = start, end, step

    @property
    def output(self):
        return T.StructType([T.StructField("id", T.LONG, nullable=False)])

    def describe(self):
        return f"Range ({self.start}, {self.end}, step={self.step})"


class Project(SparkPlan):
    def __init__(self, exprs: List[Expression], child: SparkPlan):
        super().__init__([child])
        self.exprs = exprs

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        return T.StructType([
            T.StructField(e.name, e.dataType, e.nullable) for e in self.exprs])

    def describe(self):
        return "Project [" + ", ".join(e.sql_string() for e in self.exprs) + "]"


class Filter(SparkPlan):
    def __init__(self, condition: Expression, child: SparkPlan):
        super().__init__([child])
        self.condition = condition

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def describe(self):
        return f"Filter ({self.condition.sql_string()})"


class AggregateMode(enum.Enum):
    PARTIAL = "Partial"
    FINAL = "Final"
    COMPLETE = "Complete"


# central-moment aggregates sharing the (n, avg, m2) buffer form
# (reference: Spark CentralMomentAgg, GPU'd as GpuStddevPop etc. in
# org/apache/spark/sql/rapids/aggregate — SURVEY.md §2.4 hash aggregate)
VARIANCE_FUNCS = frozenset(
    {"var_pop", "var_samp", "stddev_pop", "stddev_samp"})

# higher central moments (Spark Skewness/Kurtosis: same CentralMomentAgg
# family, buffers extended with m3/m4)
HIGHER_MOMENT_FUNCS = frozenset({"skewness", "kurtosis"})

# two-input covariance family (Spark Covariance/Corr: n, xAvg, yAvg, ck
# buffers; corr adds xMk/yMk)
COVARIANCE_FUNCS = frozenset({"covar_pop", "covar_samp", "corr"})

# linear-regression family (Spark RegrCount/RegrAvgX/...): rides the same
# covariance buffers — regr_f(y, x) observes rows where BOTH are non-null
REGR_FUNCS = frozenset(
    {"regr_count", "regr_avgx", "regr_avgy", "regr_sxx", "regr_syy",
     "regr_sxy", "regr_slope", "regr_intercept", "regr_r2"})

# bitwise aggregates (Spark BitAndAgg/BitOrAgg/BitXorAgg)
BIT_AGG_FUNCS = frozenset({"bit_and", "bit_or", "bit_xor"})

# single-phase aggregates (planned COMPLETE after a hash exchange, like
# collect_list — their state is the whole group)
SINGLE_PHASE_FUNCS = frozenset(
    {"collect_list", "collect_set", "percentile", "approx_percentile",
     "median", "bloom_filter_agg"})

# PARTIAL-mode buffer field suffixes per moment-family func; every buffer
# column is DOUBLE
MOMENT_BUFFERS = {
    "var_pop": ("_n", "_avg", "_m2"),
    "var_samp": ("_n", "_avg", "_m2"),
    "stddev_pop": ("_n", "_avg", "_m2"),
    "stddev_samp": ("_n", "_avg", "_m2"),
    "skewness": ("_n", "_avg", "_m2", "_m3"),
    "kurtosis": ("_n", "_avg", "_m2", "_m3", "_m4"),
    "covar_pop": ("_n", "_xavg", "_yavg", "_ck"),
    "covar_samp": ("_n", "_xavg", "_yavg", "_ck"),
    "corr": ("_n", "_xavg", "_yavg", "_ck", "_xm2", "_ym2"),
    **{f: ("_n", "_xavg", "_yavg", "_ck", "_xm2", "_ym2")
       for f in ("regr_count", "regr_avgx", "regr_avgy", "regr_sxx",
                 "regr_syy", "regr_sxy", "regr_slope", "regr_intercept",
                 "regr_r2")},
}

# default register-count exponent for approx_count_distinct at Spark's
# default relativeSD=0.05 (p = ceil(2 * log2(1.106 / rsd)))
HLL_DEFAULT_P = 9


@dataclasses.dataclass
class AggregateExpression:
    """One aggregate: func name + input expr (resolved) + result name.

    func in {sum, count, min, max, avg, first, last, count_star,
    var_pop, var_samp, stddev_pop, stddev_samp}.
    """

    func: str
    child: Optional[Expression]  # None for count(*)
    result_name: str
    result_type: Optional[T.DataType] = None
    distinct: bool = False
    child2: Optional[Expression] = None   # corr/covar second input
    args: tuple = ()                      # literal extras (percentage, ...)

    def resolve(self, schema: T.StructType) -> "AggregateExpression":
        if self.child is not None:
            self.child = self.child.resolve(schema)
        if self.child2 is not None:
            self.child2 = self.child2.resolve(schema)
        self.result_type = self._compute_type()
        return self

    def _compute_type(self) -> T.DataType:
        if self.func in ("count", "count_star", "count_if",
                         "approx_count_distinct"):
            return T.LONG
        if self.func == "bloom_filter_agg":
            return T.ArrayType(T.LONG, containsNull=False)
        ct = self.child.dataType
        if self.func == "sum":
            if isinstance(ct, T.DecimalType):
                return T.DecimalType(min(ct.precision + 10, 38), ct.scale)
            if ct.is_integral:
                return T.LONG
            return T.DOUBLE
        if self.func == "avg":
            if isinstance(ct, T.DecimalType):
                return T.DecimalType(min(ct.precision + 4, 38),
                                     min(ct.scale + 4, 38))
            return T.DOUBLE
        if self.func in VARIANCE_FUNCS or self.func in HIGHER_MOMENT_FUNCS \
                or self.func in COVARIANCE_FUNCS:
            return T.DOUBLE
        if self.func == "regr_count":
            return T.LONG
        if self.func in REGR_FUNCS:
            return T.DOUBLE
        if self.func in ("percentile", "median"):
            return T.DOUBLE
        if self.func == "approx_percentile":
            return ct
        if self.func in ("collect_list", "collect_set"):
            return T.ArrayType(ct)
        return ct  # min/max/first/last

    def describe(self):
        inner = self.child.sql_string() if self.child is not None else "*"
        return f"{self.func}({inner}) AS {self.result_name}"


def partial_buffer_schema(grouping, aggregates) -> T.StructType:
    """PARTIAL-mode buffer schema for a grouping+aggregate set (what a
    PARTIAL HashAggregate outputs and a FINAL one consumes)."""
    fields = [T.StructField(g.name, g.dataType, g.nullable)
              for g in grouping]
    for a in aggregates:
        if a.func == "avg":
            fields.append(T.StructField(a.result_name + "_sum", T.DOUBLE
                          if not isinstance(a.result_type, T.DecimalType)
                          else T.DecimalType(38, a.child.dataType.scale)))
            fields.append(T.StructField(a.result_name + "_count", T.LONG))
        elif a.func in MOMENT_BUFFERS:
            for suffix in MOMENT_BUFFERS[a.func]:
                fields.append(T.StructField(
                    a.result_name + suffix, T.DOUBLE))
        elif a.func == "approx_count_distinct":
            fields.append(T.StructField(
                a.result_name + "_hll",
                T.ArrayType(T.INT, containsNull=False)))
        else:
            fields.append(T.StructField(a.result_name, a.result_type))
    return T.StructType(fields)


class HashAggregate(SparkPlan):
    def __init__(self, grouping: List[Expression],
                 aggregates: List[AggregateExpression],
                 mode: AggregateMode, child: SparkPlan):
        super().__init__([child])
        self.grouping = grouping
        self.aggregates = aggregates
        self.mode = mode

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        if self.mode == AggregateMode.PARTIAL:
            return partial_buffer_schema(self.grouping, self.aggregates)
        fields = [T.StructField(g.name, g.dataType, g.nullable)
                  for g in self.grouping]
        fields += [T.StructField(a.result_name, a.result_type)
                   for a in self.aggregates]
        return T.StructType(fields)

    def describe(self):
        g = ", ".join(e.sql_string() for e in self.grouping)
        a = ", ".join(a.describe() for a in self.aggregates)
        return f"HashAggregate({self.mode.value}) keys=[{g}] aggs=[{a}]"


class JoinType(enum.Enum):
    INNER = "Inner"
    LEFT_OUTER = "LeftOuter"
    RIGHT_OUTER = "RightOuter"
    FULL_OUTER = "FullOuter"
    LEFT_SEMI = "LeftSemi"
    LEFT_ANTI = "LeftAnti"
    CROSS = "Cross"


class _BaseJoin(SparkPlan):
    def __init__(self, left: SparkPlan, right: SparkPlan,
                 left_keys: List[Expression], right_keys: List[Expression],
                 join_type: JoinType,
                 condition: Optional[Expression] = None,
                 emit: Optional[List[int]] = None):
        super().__init__([left, right])
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.join_type = join_type
        self.condition = condition
        # the ordinals of ``full_output`` the parent reads, in output
        # order (plan/pruning.py); None = all.  Keys and the condition
        # are bound to the children, not to this list
        self.emit = emit

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    @property
    def output(self):
        full = self.full_output
        if self.emit is None:
            return full
        return T.StructType([full.fields[i] for i in self.emit])

    @property
    def full_output(self):
        return join_full_output(self.left.output, self.right.output,
                                self.join_type)

    def describe(self):
        keys = ", ".join(
            f"{l.sql_string()}={r.sql_string()}"
            for l, r in zip(self.left_keys, self.right_keys))
        return (f"{self.node_name} {self.join_type.value} [{keys}]"
                + describe_emit(self.emit, self.full_output))


def join_full_output(left: T.StructType, right: T.StructType,
                     join_type: JoinType) -> T.StructType:
    """``left ++ right`` as the join type shapes it, before any emit."""
    lf, rf = list(left.fields), list(right.fields)
    if join_type in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
        return T.StructType(lf)
    if join_type in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER):
        rf = [T.StructField(f.name, f.dataType, True) for f in rf]
    if join_type in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
        lf = [T.StructField(f.name, f.dataType, True) for f in lf]
    return T.StructType(lf + rf)


def describe_emit(emit, full_output: T.StructType) -> str:
    """`` emit=[names]`` for a join that emits part of its output."""
    if emit is None:
        return ""
    return " emit=[" + ", ".join(full_output.fields[i].name
                                 for i in emit) + "]"


class SortMergeJoin(_BaseJoin):
    pass


class ShuffledHashJoin(_BaseJoin):
    pass


class BroadcastHashJoin(_BaseJoin):
    def __init__(self, *args, build_side: str = "right", **kw):
        super().__init__(*args, **kw)
        self.build_side = build_side


class BroadcastNestedLoopJoin(_BaseJoin):
    """Join without equi-keys: every pair is checked against the condition.

    Reference analog: GpuBroadcastNestedLoopJoinExec (SURVEY.md §2.4)."""

    def __init__(self, left, right, join_type: JoinType,
                 condition: Optional[Expression]):
        super().__init__(left, right, [], [], join_type, condition)

    def describe(self):
        c = self.condition.sql_string() if self.condition is not None else ""
        return f"BroadcastNestedLoopJoin {self.join_type.value} [{c}]"


class Generate(SparkPlan):
    """explode/posexplode over an array column.

    Reference analog: GpuGenerateExec (SURVEY.md §2.4)."""

    def __init__(self, gen_expr: Expression, child: SparkPlan,
                 position: bool = False, outer: bool = False,
                 out_name: str = "col"):
        super().__init__([child])
        self.gen_expr = gen_expr
        self.position = position
        self.outer = outer
        self.out_name = out_name

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        fields = list(self.child.output.fields)
        if self.position:
            # posexplode_outer synthesizes NULL pos for empty/null arrays
            fields.append(T.StructField("pos", T.INT, self.outer))
        dt = self.gen_expr.dataType
        # non-array input is rejected at tag time; keep output well-formed
        # so tagging can reach the check
        et = dt.elementType if isinstance(dt, T.ArrayType) else dt
        fields.append(T.StructField(self.out_name, et, True))
        return T.StructType(fields)

    def describe(self):
        kind = "posexplode" if self.position else "explode"
        if self.outer:
            kind += "_outer"
        return f"Generate {kind}({self.gen_expr.sql_string()})"


class Expand(SparkPlan):
    """Emit one output row per projection set per input row (rollup/cube
    building block).  Reference analog: GpuExpandExec."""

    def __init__(self, projections: List[List[Expression]],
                 output_schema: T.StructType, child: SparkPlan):
        super().__init__([child])
        self.projections = projections
        self._output = output_schema

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        return self._output

    def describe(self):
        return f"Expand [{len(self.projections)} projections]"


class Sort(SparkPlan):
    def __init__(self, orders: List[Tuple[Expression, SortSpec]],
                 is_global: bool, child: SparkPlan):
        super().__init__([child])
        self.orders = orders
        self.is_global = is_global

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def describe(self):
        o = ", ".join(
            f"{e.sql_string()} {'ASC' if s.ascending else 'DESC'}"
            for e, s in self.orders)
        return f"Sort [{o}] global={self.is_global}"


class SinglePartitioning:
    num_partitions = 1

    def describe(self):
        return "SinglePartition"


@dataclasses.dataclass
class HashPartitioning:
    keys: List[Expression]
    num_partitions: int

    def describe(self):
        k = ", ".join(e.sql_string() for e in self.keys)
        return f"hashpartitioning({k}, {self.num_partitions})"


@dataclasses.dataclass
class RangePartitioning:
    orders: List[Tuple[Expression, SortSpec]]
    num_partitions: int

    def describe(self):
        return f"rangepartitioning({self.num_partitions})"


@dataclasses.dataclass
class RoundRobinPartitioning:
    num_partitions: int

    def describe(self):
        return f"roundrobin({self.num_partitions})"


class Exchange(SparkPlan):
    """ShuffleExchangeExec analog."""

    def __init__(self, partitioning, child: SparkPlan):
        super().__init__([child])
        self.partitioning = partitioning

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        return self.child.output

    def describe(self):
        return f"Exchange {self.partitioning.describe()}"


class BroadcastExchange(SparkPlan):
    def __init__(self, child: SparkPlan):
        super().__init__([child])

    @property
    def output(self):
        return self.children[0].output


@dataclasses.dataclass
class WindowFunction:
    """window function spec: func over (partition, order, frame).

    lead/lag carry ``offset`` (+ optional literal ``default``); ntile
    carries ``buckets``; first_value/last_value carry ``ignore_nulls``."""

    func: str                      # row_number, rank, dense_rank, sum, ...
    child: Optional[Expression]
    result_name: str
    result_type: Optional[T.DataType] = None
    offset: int = 1                # lead/lag
    default: Optional[object] = None   # lead/lag literal default
    buckets: int = 2               # ntile
    ignore_nulls: bool = False     # first_value/last_value

    def resolve(self, schema):
        if self.child is not None:
            self.child = self.child.resolve(schema)
        if self.func in ("row_number", "rank", "dense_rank", "ntile"):
            self.result_type = T.INT
        elif self.func in ("percent_rank", "cume_dist"):
            self.result_type = T.DOUBLE
        elif self.func == "count":
            self.result_type = T.LONG
        elif self.func == "sum":
            ct = self.child.dataType
            if isinstance(ct, T.DecimalType):
                self.result_type = T.DecimalType(min(ct.precision + 10, 38), ct.scale)
            elif ct.is_integral:
                self.result_type = T.LONG
            else:
                self.result_type = T.DOUBLE
        elif self.func in ("avg", "var_pop", "var_samp",
                           "stddev_pop", "stddev_samp"):
            self.result_type = T.DOUBLE
        else:
            self.result_type = self.child.dataType
        return self


def normalize_frame(frame):
    """Canonical window-frame forms (GpuSpecifiedWindowFrame analog):

      "running"        ROWS  UNBOUNDED PRECEDING .. CURRENT ROW
      "range_running"  RANGE UNBOUNDED PRECEDING .. CURRENT ROW (Spark's
                       default frame when ORDER BY is present — includes
                       the current row's order-key peers)
      "unbounded"      the whole partition
      ("rows", a, b)   ROWS  BETWEEN a PRECEDING AND b FOLLOWING
      ("range", a, b)  RANGE BETWEEN a PRECEDING AND b FOLLOWING over a
                       single numeric order key

    A bare (a, b) tuple is legacy shorthand for ("rows", a, b)."""
    if isinstance(frame, tuple):
        if len(frame) == 2:
            return ("rows", frame[0], frame[1])
        if len(frame) == 3 and frame[0] in ("rows", "range"):
            return frame
        raise ValueError(f"bad window frame {frame!r}")
    if frame not in ("running", "range_running", "unbounded"):
        raise ValueError(f"bad window frame {frame!r}")
    return frame


class Window(SparkPlan):
    def __init__(self, functions: List[WindowFunction],
                 partition_by: List[Expression],
                 order_by: List[Tuple[Expression, SortSpec]],
                 child: SparkPlan,
                 frame: str = "running"):
        super().__init__([child])
        self.functions = functions
        self.partition_by = partition_by
        self.order_by = order_by
        self.frame = normalize_frame(frame)  # see normalize_frame

    @property
    def child(self):
        return self.children[0]

    @property
    def output(self):
        fields = list(self.child.output.fields)
        fields += [T.StructField(f.result_name, f.result_type)
                   for f in self.functions]
        return T.StructType(fields)

    def describe(self):
        fns = ", ".join(f.func for f in self.functions)
        return f"Window [{fns}] frame={self.frame}"


class LocalLimit(SparkPlan):
    def __init__(self, n: int, child: SparkPlan):
        super().__init__([child])
        self.n = n

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        return f"LocalLimit {self.n}"


class GlobalLimit(LocalLimit):
    def describe(self):
        return f"GlobalLimit {self.n}"


class Sample(SparkPlan):
    """Bernoulli row sample (GpuSampleExec analog).  The keep decision is
    the engine's deterministic splitmix64 stream keyed on (seed, row) —
    both backends draw identical samples (Spark's sampler is
    XORShift-based; documented divergence, same statistics)."""

    def __init__(self, fraction: float, seed: int, child: SparkPlan):
        super().__init__([child])
        self.fraction = float(fraction)
        self.seed = int(seed)

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        return f"Sample fraction={self.fraction} seed={self.seed}"


class Union(SparkPlan):
    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        return f"Union ({len(self.children)} children)"


class InsertIntoHadoopFsRelation(SparkPlan):
    """Write command (DataWritingCommand analog).

    Reference analog: InsertIntoHadoopFsRelationCommand wrapped by
    GpuDataWritingCommandExec via the dataWriteCmds registry
    (SURVEY.md §2.2 GpuOverrides.dataWriteCmds, §2.6 Writers)."""

    def __init__(self, fmt: str, path: str, child: SparkPlan,
                 partition_cols=None, mode: str = "overwrite",
                 options=None):
        super().__init__([child])
        self.fmt = fmt
        self.path = path
        self.partition_cols = list(partition_cols or [])
        self.mode = mode
        self.options = dict(options or {})

    @property
    def output(self):
        return T.StructType([])

    def describe(self):
        p = f" partitionBy={self.partition_cols}" if self.partition_cols else ""
        return f"InsertIntoHadoopFsRelation {self.fmt} {self.path}{p}"
