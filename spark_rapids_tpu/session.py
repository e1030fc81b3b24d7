"""TpuSession / DataFrame — the user entry point.

Role: in the reference, users keep using SparkSession and the plugin hooks in
via ``spark.plugins=com.nvidia.spark.SQLPlugin`` (SURVEY.md §3.1).  This
standalone framework has no JVM, so TpuSession plays both roles: it builds
Catalyst-shaped physical plans from a PySpark-flavored DataFrame API
(select/filter/groupBy/join/orderBy...), plans aggregates two-phase around
exchanges exactly like Spark (partial -> shuffle -> final), and at collect()
time applies TpuOverrides (the ColumnarOverrideRules hook analog), executes
the rewritten plan, and returns rows.

``conf`` accepts the same ``spark.rapids.*`` keys as the reference;
``spark.rapids.sql.enabled=false`` runs everything on the CPU oracle — which
is precisely what the differential test harness does to get golden results.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, List, Optional, Sequence, Tuple, Union

from spark_rapids_tpu import perfcounters as _PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.accounting import context as _ACCT_CTX
from spark_rapids_tpu.columnar.column import HostColumn
from spark_rapids_tpu.telemetry import context as _TEL_CTX
from spark_rapids_tpu.config import SHUFFLE_PARTITIONS, TpuConf
from spark_rapids_tpu.expr.base import (
    Alias,
    AttributeReference,
    Expression,
    col as _col,
    lit as _lit,
)
from spark_rapids_tpu.ops.sortkeys import SortSpec
from spark_rapids_tpu.plan import nodes as PN

ColumnLike = Union[str, Expression]


def _to_expr(c: ColumnLike) -> Expression:
    if isinstance(c, Expression):
        return c
    return _col(c)


_COMPILE_CACHE_APPLIED: Optional[str] = None     # last applied dir ("" = off)


def _apply_compile_cache(conf: "TpuConf") -> None:
    """Point XLA's persistent compile cache at the configured dir: one
    cache authority for session/tests/tools/bench.  With
    ``JAX_COMPILATION_CACHE_DIR`` set in the environment the cache was
    placed from outside and nothing is set here
    (compilecache.apply_persistent_cache_dir).  Otherwise the dir is
    ``<conf dir>/<backend>`` — by default the fixed
    ``<checkout>/.jax_compile_cache/<backend>`` (the path is part of the
    cache key, so it never moves; XLA:CPU executables are not mixed with
    the chip's).  jax.config is process-global; re-applied whenever a
    session resolves a DIFFERENT dir, so a later explicit conf is not
    silently ignored.  An empty/'0' dir opts out."""
    global _COMPILE_CACHE_APPLIED
    from spark_rapids_tpu.config import COMPILE_CACHE_DIR, COMPILE_CACHE_DIR_V2

    # preferred spelling first (spark.rapids.tpu.compile.cacheDir); unset
    # falls back to the legacy key and its repo-local default
    cache_dir = conf.get(COMPILE_CACHE_DIR_V2)
    if cache_dir is None:
        cache_dir = conf.get(COMPILE_CACHE_DIR)
    if not cache_dir or cache_dir == "0":
        cache_dir = ""
    if cache_dir:
        import jax

        cache_dir = os.path.join(cache_dir, jax.default_backend())
    if _COMPILE_CACHE_APPLIED == cache_dir:
        return
    _COMPILE_CACHE_APPLIED = cache_dir
    if cache_dir:
        from spark_rapids_tpu.compilecache import apply_persistent_cache_dir

        apply_persistent_cache_dir(cache_dir)


class TpuSession:
    def __init__(self, conf: Optional[Dict[str, str]] = None):
        self.conf = TpuConf(conf or {})
        _apply_compile_cache(self.conf)
        # Telemetry tier (ISSUE 7): the first enabling session builds the
        # process-global hub (metrics registry + sampler + flight
        # recorder + optional scrape endpoint); later sessions reuse it.
        from spark_rapids_tpu.telemetry import maybe_configure

        maybe_configure(self.conf)
        # Overload governor (ISSUE 13): the first session whose conf
        # enables spark.rapids.tpu.governor.enabled installs the
        # process-global pressure state machine; disabled (the default)
        # this is one conf read and the ambient slot stays None.
        from spark_rapids_tpu.governor import ensure_governor

        ensure_governor(self.conf)
        # Resource accounting (ISSUE 18): the first session enabling
        # spark.rapids.tpu.accounting.enabled installs the process-global
        # ledger registry; disabled (the default) the ambient slot stays
        # None and every spill-framework charge site is one attr check.
        from spark_rapids_tpu.accounting import maybe_configure as acct_configure

        acct_configure(self.conf)
        # Multi-tenant serving tier (ISSUE 19): the first session whose
        # conf enables spark.rapids.tpu.serving.enabled builds the tier
        # (fair-share scheduler installed into admission, the result-
        # fragment cache into its ambient slot).  Disabled (the
        # default): one conf read, the serving package never imports.
        from spark_rapids_tpu.config import SERVING_ENABLED

        if bool(self.conf.get(SERVING_ENABLED)):
            from spark_rapids_tpu.serving import ensure_serving

            ensure_serving(self.conf)

    @staticmethod
    def builder() -> "TpuSessionBuilder":
        return TpuSessionBuilder()

    def set_conf(self, key: str, value) -> "TpuSession":
        self.conf = self.conf.set(key, value)
        return self

    def progress(self, include_finished: bool = True) -> List[dict]:
        """Live multi-query progress snapshot (ISSUE 12): one dict per
        in-flight (and recently finished) lifecycle-managed query on
        this PROCESS — per-operator batches/rows/bytes, percent/ETA
        from the cost-model join, attributed background work, and stall
        state.  Empty when spark.rapids.tpu.progress.enabled never
        enabled a query.  The same payload the telemetry endpoint's
        /progress route serves (docs/progress.md)."""
        from spark_rapids_tpu.progress import snapshot

        return snapshot(include_finished)

    # -- data sources ---------------------------------------------------
    def create_dataframe(self, data, schema: T.StructType) -> "DataFrame":
        if isinstance(data, dict):
            cols = [HostColumn.from_pylist(data[f.name], f.dataType)
                    for f in schema.fields]
        else:  # rows
            cols = []
            for i, f in enumerate(schema.fields):
                cols.append(HostColumn.from_pylist(
                    [r[i] for r in data], f.dataType))
        return DataFrame(PN.LocalTableScan(cols, schema), self)

    createDataFrame = create_dataframe

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(PN.RangeNode(start, end, step), self)

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    @property
    def shuffle_partitions(self) -> int:
        return self.conf.get(SHUFFLE_PARTITIONS)

    def close(self, check_leaks: bool = True,
              drop_hot_cache: bool = True) -> List[str]:
        """Session shutdown (ISSUE 4 satellite): report — and then
        release — anything still held across the process singletons:
        unclosed non-persistent spillables, semaphore permits, live
        shuffle registrations.  Returns the leak report (empty for a
        well-behaved session); with spark.rapids.memory.debug the
        entries carry allocation stacks."""
        from spark_rapids_tpu.io.hot_cache import clear_hot_cache
        from spark_rapids_tpu.lifecycle import (
            leak_report_all,
            reset_leaked_state,
        )

        # hot-table cache entries are INTENTIONAL persistent spillables
        # while the process serves queries; like everything else this
        # method touches, the cache is a PROCESS singleton — shutdown
        # drops it so the leak report below (and the conftest session
        # gate) sees a clean framework.  A deployment closing one of
        # several live sessions passes drop_hot_cache=False to keep the
        # other sessions' warm tables.
        if drop_hot_cache:
            clear_hot_cache()
        leaks = leak_report_all() if check_leaks else []
        reset_leaked_state()
        # flush the telemetry JSONL sink so a shutdown-then-inspect
        # workflow sees every sampler tick; the hub itself is
        # process-global and keeps serving other live sessions
        # (telemetry.shutdown() stops it for good)
        from spark_rapids_tpu.telemetry import flush as _telemetry_flush

        _telemetry_flush()
        return leaks


class TpuSessionBuilder:
    def __init__(self):
        self._conf: Dict[str, str] = {}

    def config(self, key: str, value) -> "TpuSessionBuilder":
        self._conf[key] = value
        return self

    def get_or_create(self) -> TpuSession:
        return TpuSession(self._conf)

    getOrCreate = get_or_create


class DataFrameReader:
    def __init__(self, session: TpuSession):
        self.session = session
        self._options: Dict[str, str] = {}
        self._schema: Optional[T.StructType] = None

    def option(self, k, v) -> "DataFrameReader":
        self._options[k] = v
        return self

    def schema(self, s: T.StructType) -> "DataFrameReader":
        self._schema = s
        return self

    def _infer_schema(self, fmt: str, paths: List[str]) -> T.StructType:
        import os

        import pyarrow as pa

        if os.path.isdir(paths[0]):
            # hive-partitioned directory written by df.write.partitionBy
            import pyarrow.dataset as ds

            dset = ds.dataset(paths[0], format=fmt,
                              partitioning="hive",
                              exclude_invalid_files=True)
            arrow_schema = dset.schema
        elif fmt == "parquet":
            import pyarrow.parquet as pq

            arrow_schema = pq.read_schema(paths[0])
        elif fmt == "orc":
            import pyarrow.orc as paorc

            arrow_schema = paorc.ORCFile(paths[0]).schema
        elif fmt == "csv":
            import pyarrow.csv as pacsv

            arrow_schema = pacsv.read_csv(paths[0]).schema
        else:
            import pyarrow.json as pajson

            arrow_schema = pajson.read_json(paths[0]).schema
        fields = []
        for f in arrow_schema:
            fields.append(T.StructField(f.name, _arrow_to_sql(f.type),
                                        f.nullable))
        return T.StructType(fields)

    def parquet(self, *paths: str) -> "DataFrame":
        schema = self._schema or self._infer_schema("parquet", list(paths))
        return DataFrame(
            PN.FileSourceScan("parquet", list(paths), schema,
                              options=self._options), self.session)

    def csv(self, *paths: str) -> "DataFrame":
        opts = dict(self._options)
        if self._schema is None:
            # inference honors the reader's sep/header options (arrow
            # parse options), so the strict parse sees the same shape
            import pyarrow.csv as pacsv

            sep = str(opts.get("sep", opts.get("delimiter", ",")))
            headerless = str(opts.get("header", "")).lower() == "false"
            tbl = pacsv.read_csv(
                paths[0],
                read_options=pacsv.ReadOptions(
                    autogenerate_column_names=headerless),
                parse_options=pacsv.ParseOptions(delimiter=sep))
            fields = [T.StructField(f.name if not headerless
                                    else f"_c{i}",
                                    _arrow_to_sql(f.type), f.nullable)
                      for i, f in enumerate(tbl.schema)]
            schema = T.StructType(fields)
            # schema inference reads column names from the header line, so
            # the parse must consume it too (explicit schemas keep Spark's
            # header=false default)
            opts.setdefault("header", "true")
        else:
            schema = self._schema
        return DataFrame(
            PN.FileSourceScan("csv", list(paths), schema,
                              options=opts), self.session)

    def delta(self, path: str, version: Optional[int] = None) -> "DataFrame":
        from spark_rapids_tpu.delta import read_delta

        return read_delta(self.session, path, version)

    def iceberg(self, path: str,
                snapshot_id: Optional[int] = None) -> "DataFrame":
        from spark_rapids_tpu.io.iceberg import read_iceberg

        return read_iceberg(self.session, path, snapshot_id)

    def avro(self, *paths: str) -> "DataFrame":
        if self._schema is None:
            from spark_rapids_tpu.io.avro import (
                avro_schema_to_struct,
                read_avro_file,
            )

            schema = avro_schema_to_struct(read_avro_file(paths[0])[0])
        else:
            schema = self._schema
        return DataFrame(
            PN.FileSourceScan("avro", list(paths), schema,
                              options=self._options), self.session)

    def orc(self, *paths: str) -> "DataFrame":
        schema = self._schema or self._infer_schema("orc", list(paths))
        return DataFrame(
            PN.FileSourceScan("orc", list(paths), schema,
                              options=self._options), self.session)

    def json(self, *paths: str) -> "DataFrame":
        schema = self._schema or self._infer_schema("json", list(paths))
        return DataFrame(
            PN.FileSourceScan("json", list(paths), schema,
                              options=self._options), self.session)


def _arrow_to_sql(t) -> T.DataType:
    import pyarrow as pa

    if pa.types.is_boolean(t):
        return T.BOOLEAN
    if pa.types.is_int8(t):
        return T.BYTE
    if pa.types.is_int16(t):
        return T.SHORT
    if pa.types.is_int32(t):
        return T.INT
    if pa.types.is_int64(t):
        return T.LONG
    if pa.types.is_float32(t):
        return T.FLOAT
    if pa.types.is_float64(t):
        return T.DOUBLE
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return T.STRING
    if pa.types.is_date32(t):
        return T.DATE
    if pa.types.is_timestamp(t):
        return T.TIMESTAMP
    if pa.types.is_decimal(t):
        return T.DecimalType(t.precision, t.scale)
    raise TypeError(f"unsupported arrow type {t}")


class DataFrame:
    def __init__(self, plan: PN.SparkPlan, session: TpuSession):
        self.plan = plan
        self.session = session

    @property
    def schema(self) -> T.StructType:
        return self.plan.output

    @property
    def columns(self) -> List[str]:
        return self.schema.field_names()

    # -- transformations ------------------------------------------------
    def select(self, *cols: ColumnLike) -> "DataFrame":
        exprs = [_named(_to_expr(c).resolve(self.schema), i)
                 for i, c in enumerate(cols)]
        return DataFrame(PN.Project(exprs, self.plan), self.session)

    def with_column(self, name: str, e: Expression) -> "DataFrame":
        exprs = []
        for f in self.schema.fields:
            if f.name != name:
                exprs.append(Alias(_col(f.name).resolve(self.schema), f.name))
                exprs[-1].resolve(self.schema)
        newe = Alias(e.resolve(self.schema), name)
        newe.resolve(self.schema)
        exprs.append(newe)
        return DataFrame(PN.Project(exprs, self.plan), self.session)

    withColumn = with_column

    def filter(self, cond: Expression) -> "DataFrame":
        return DataFrame(
            PN.Filter(cond.resolve(self.schema), self.plan), self.session)

    where = filter

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(PN.Union([self.plan, other.plan]), self.session)

    def repartition(self, num_partitions: int,
                    *cols: ColumnLike) -> "DataFrame":
        """Dataset.repartition: hash exchange on ``cols`` (round-robin
        when none given).  Under mesh/ICI mode this lowers to the generic
        mesh all-to-all (exec/ici.TpuIciRepartitionExec)."""
        if cols:
            part = PN.HashPartitioning(
                [_to_expr(c).resolve(self.schema) for c in cols],
                num_partitions)
        else:
            part = PN.RoundRobinPartitioning(num_partitions)
        return DataFrame(PN.Exchange(part, self.plan), self.session)

    def group_by(self, *cols: ColumnLike) -> "GroupedData":
        return GroupedData(self, [_to_expr(c).resolve(self.schema)
                                  for c in cols])

    groupBy = group_by

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        return self.join(other, None, "cross")

    def join(self, other: "DataFrame", on, how: str = "inner") -> "DataFrame":
        jt = {"inner": PN.JoinType.INNER, "left": PN.JoinType.LEFT_OUTER,
              "left_outer": PN.JoinType.LEFT_OUTER,
              "right": PN.JoinType.RIGHT_OUTER,
              "right_outer": PN.JoinType.RIGHT_OUTER,
              "outer": PN.JoinType.FULL_OUTER,
              "full": PN.JoinType.FULL_OUTER,
              "full_outer": PN.JoinType.FULL_OUTER,
              "left_semi": PN.JoinType.LEFT_SEMI, "semi": PN.JoinType.LEFT_SEMI,
              "left_anti": PN.JoinType.LEFT_ANTI, "anti": PN.JoinType.LEFT_ANTI,
              "cross": PN.JoinType.CROSS}[how.lower()]
        if isinstance(on, Expression):
            # non-equi condition -> broadcast nested loop join; the
            # condition resolves against the combined (left ++ right) schema
            combined = T.StructType(list(self.schema.fields)
                                    + list(other.schema.fields))
            cond = on.resolve(combined)
            node = PN.BroadcastNestedLoopJoin(
                self.plan, PN.BroadcastExchange(other.plan), jt, cond)
            return DataFrame(node, self.session)
        if isinstance(on, str):
            on = [on]
        lkeys = [_col(k).resolve(self.schema) for k in on] if on else []
        rkeys = [_col(k).resolve(other.schema) for k in on] if on else []
        np_ = self.session.shuffle_partitions
        if jt == PN.JoinType.CROSS:
            node = PN.SortMergeJoin(self.plan, other.plan, [], [], jt)
            return DataFrame(node, self.session)
        # broadcast if the right side is a small local/file scan
        if _is_broadcastable(other.plan, self.session.conf):
            node = PN.BroadcastHashJoin(
                self.plan, PN.BroadcastExchange(other.plan), lkeys, rkeys, jt)
            return DataFrame(node, self.session)
        lex = PN.Exchange(PN.HashPartitioning(lkeys, np_), self.plan)
        rex = PN.Exchange(PN.HashPartitioning(rkeys, np_), other.plan)
        node = PN.SortMergeJoin(lex, rex, lkeys, rkeys, jt)
        return DataFrame(node, self.session)

    def sample(self, fraction: float, seed: int = 0) -> "DataFrame":
        return DataFrame(PN.Sample(fraction, seed, self.plan), self.session)

    def order_by(self, *cols, ascending=None) -> "DataFrame":
        orders = []
        for i, c in enumerate(cols):
            if isinstance(c, tuple):
                e, spec = c
            else:
                asc = (ascending[i] if isinstance(ascending, (list, tuple))
                       else (ascending if ascending is not None else True))
                e = _to_expr(c)
                spec = SortSpec(ascending=asc, nulls_first=asc)
            orders.append((e.resolve(self.schema), spec))
        return DataFrame(PN.Sort(orders, True, self.plan), self.session)

    orderBy = order_by
    sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(PN.GlobalLimit(n, self.plan), self.session)

    def explode(self, column: ColumnLike, outer: bool = False,
                position: bool = False, out_name: str = "col") -> "DataFrame":
        """explode/posexplode an array column; retains the other columns
        (GpuGenerateExec analog)."""
        gen = _to_expr(column).resolve(self.schema)
        return DataFrame(PN.Generate(gen, self.plan, position=position,
                                     outer=outer, out_name=out_name),
                         self.session)

    def expand(self, projections) -> "DataFrame":
        """Emit one row per projection set per input row (GpuExpandExec;
        the rollup/cube building block).  ``projections`` is a list of
        same-length expression lists; output columns take names/types from
        the first set."""
        resolved = [[_to_expr(e).resolve(self.schema) for e in ps]
                    for ps in projections]
        schema = T.StructType([
            T.StructField(e.name, e.dataType, True) for e in resolved[0]])
        return DataFrame(PN.Expand(resolved, schema, self.plan), self.session)

    def stack(self, n: int, columns, names=None) -> "DataFrame":
        """stack(n, e1..ek): n rows of k//n columns per input row — planned
        as Expand with n projection sets (exact Spark semantics: short
        rows pad with NULL literals).  Reference analog: GpuGenerateExec's
        stack generator (GpuStack)."""
        from spark_rapids_tpu.expr.base import Literal

        exprs = [_to_expr(c).resolve(self.schema) for c in columns]
        k = len(exprs)
        per = (k + n - 1) // n
        names = names or [f"col{i}" for i in range(per)]
        projections = []
        for r in range(n):
            row = []
            for c in range(per):
                i = r * per + c
                if i < k:
                    row.append(exprs[i].alias(names[c]))
                else:
                    row.append(Literal(None, exprs[c].dataType)
                               .alias(names[c]))
            projections.append(row)
        resolved = [[e.resolve(self.schema) for e in ps]
                    for ps in projections]
        schema = T.StructType([
            T.StructField(e.name, e.dataType, True) for e in resolved[0]])
        return DataFrame(PN.Expand(resolved, schema, self.plan),
                         self.session)

    def cache(self) -> "DataFrame":
        """Materialize this DataFrame's batches on first action and reuse
        them (ParquetCachedBatchSerializer analog; device batches held as
        spillable handles)."""
        if isinstance(self.plan, PN.CachedRelation):
            return self
        return DataFrame(PN.CachedRelation(self.plan), self.session)

    persist = cache

    def unpersist(self) -> "DataFrame":
        if isinstance(self.plan, PN.CachedRelation):
            for handles in self.plan.cache_slot.values():
                if isinstance(handles, list):
                    for h in handles:
                        try:
                            h.close()
                        except Exception:
                            pass
            self.plan.cache_slot.clear()
        return self

    def window(self, functions: List[PN.WindowFunction],
               partition_by: Sequence[ColumnLike],
               order_by: Sequence, frame: str = "running") -> "DataFrame":
        pb = [_to_expr(c).resolve(self.schema) for c in partition_by]
        ob = []
        for c in order_by:
            if isinstance(c, tuple):
                e, spec = c
            else:
                e, spec = _to_expr(c), SortSpec()
            ob.append((e.resolve(self.schema), spec))
        fns = [f.resolve(self.schema) for f in functions]
        return DataFrame(PN.Window(fns, pb, ob, self.plan, frame),
                         self.session)

    # -- actions --------------------------------------------------------
    def _planned(self):
        """Apply TpuOverrides; the planned exec tree is cached per conf so
        repeated collects reuse compiled XLA programs (Spark likewise reuses
        a query's compiled stages across executions of the same plan)."""
        with _PC.span("srt.plan"):
            return self._planned_impl()

    def _planned_impl(self):
        from spark_rapids_tpu.config import set_conf
        from spark_rapids_tpu.overrides import TpuOverrides

        conf = self.session.conf
        if not conf.sql_enabled:
            return self.plan, None
        # the execution-ambient conf (config.get_conf): exec nodes read
        # runtime knobs (skew split, groups-cap ladder) through it at
        # execute time, after plan construction has dropped conf refs.
        # Plan+execute run synchronously per collect, so the ambient conf
        # is stable for the query that set it; oracle (sql-disabled)
        # sessions never clobber it
        set_conf(conf)
        from spark_rapids_tpu.resilience.breaker import get_breaker

        # the breaker generation ticks on every planner-visible breaker
        # transition (trip / probe / close), so a plan cached before a
        # stage tripped is re-planned — and re-tagged to the oracle —
        # instead of re-failing on the TPU every collect.  Same rule for
        # the profiling advisory (ISSUE 8): editing/regenerating the
        # advisory file must re-tag cached plans, so its (path, mtime,
        # size) stamp is part of the key — gated on the conf so the
        # disabled path makes zero profiling-module calls
        advisory_key = None
        from spark_rapids_tpu.config import PROFILE_ADVISOR_ENABLED

        if conf.get(PROFILE_ADVISOR_ENABLED):
            from spark_rapids_tpu.profiling.advisor import advisory_state

            advisory_key = advisory_state(conf)
        cache_key = (get_breaker().generation, advisory_key) + tuple(
            sorted((k, str(v)) for k, v in conf.settings.items()))
        cached = getattr(self, "_plan_cache", None)
        if cached is not None and cached[0] == cache_key:
            return cached[1], cached[2]
        # column pruning (plan/pruning.py) on the TPU path only, and once
        # per cached plan: the oracle above keeps running the user's
        # unpruned plan, an independent check of the pass
        from spark_rapids_tpu.plan.pruning import prune_columns

        root, meta = TpuOverrides.apply(prune_columns(self.plan), conf)
        self._plan_cache = (cache_key, root, meta)
        return root, meta

    def collect(self) -> List[tuple]:
        # Query lifecycle (ISSUE 4): admission slot BEFORE planning, an
        # optional deadline armed by the watchdog, a CancelToken every
        # blocking layer observes, and guaranteed cleanup (semaphore
        # permits, tracked spillables, shuffle registrations) when the
        # exec tree unwinds — even mid-batch
        from spark_rapids_tpu.lifecycle import query_lifecycle

        # srt.collect: the outermost span of the client's thread; every
        # other span of this collect nests in it and so shares its ids
        with _PC.span("srt.collect") as sp, \
                query_lifecycle(self.session.conf) as qctx:
            if qctx is not None:
                sp.annotate(query_id=qctx.query_id, trace_id=qctx.trace_id)
            # Telemetry (ISSUE 7): lifecycle-managed queries run under
            # flight-recorder + SLO observation — a few dict appends and
            # one plan walk per QUERY.  The hub check is one ambient
            # attribute read; a telemetry-disabled session skips on the
            # conf alone (zero calls into telemetry modules — pinned by
            # tests/test_telemetry.py).
            hub = _TEL_CTX.HUB
            if hub is not None and qctx is not None:
                from spark_rapids_tpu.config import TELEMETRY_ENABLED

                if self.session.conf.get(TELEMETRY_ENABLED):
                    return hub.observed_collect(self, qctx)
            return self._collect_impl(qctx)

    def _collect_impl(self, qctx) -> List[tuple]:
        from spark_rapids_tpu.cpu.oracle import execute_cpu_plan
        from spark_rapids_tpu.exec.base import TpuExec
        from spark_rapids_tpu.exec.transitions import TpuColumnarToRowExec
        from spark_rapids_tpu.expr.misc import CURRENT_INPUT_FILE

        CURRENT_INPUT_FILE[0] = ""   # InputFileName: "" outside file scans
        root, _meta = self._planned()
        # Crash-consistent recovery (ISSUE 16): journal the planned
        # tree's identity so a reborn driver replanning the same query
        # can prove checkpoint fingerprints refer to the same plan.
        # Disabled (default): one conf read, zero journal-module calls
        # (pinned by tests/test_recovery.py).
        if qctx is not None:
            from spark_rapids_tpu.config import RECOVERY_ENABLED

            if bool(self.session.conf.get(RECOVERY_ENABLED)):
                from spark_rapids_tpu.lifecycle import journal as _jn

                try:
                    _jn.journal_plan(qctx, root, self.session.conf)
                # tpulint: disable=cancel-swallow (durability isolation:
                # the plan record is advisory; losing it weakens the
                # post-mortem, never the query)
                except Exception:
                    pass
        if isinstance(root, TpuExec):
            # Diagnostics (ISSUE 3): one QueryDiagnostics recorder spans
            # the window from AOT submission through execution — operator
            # spans, launch/sync/compile/resilience events, per-operator
            # counter attribution — flushed atomically to the configured
            # sinks on exit and kept on the DataFrame for
            # explain("analyze")
            from spark_rapids_tpu.config import PROFILE_DIR, ambient_conf
            from spark_rapids_tpu.diagnostics import query_scope

            # Profiling (ISSUE 8): with a calibration-store dir set, the
            # finished recorder's operator spans fold into the store and
            # the predicted-vs-actual record lands in the event log —
            # wired as the scope's finish hook so it runs after
            # finish() but before the sinks flush.  Unset (default):
            # one conf read, zero profiling-module calls (pinned by
            # tests/test_profiling.py).
            prof_dir = self.session.conf.get(PROFILE_DIR)
            on_finish = None
            # the prediction is threaded through this box, NOT stashed
            # on the cached (shared) plan root: a losing concurrent
            # collect of the same DataFrame must not clobber the
            # recorded query's prediction
            cost_box = {"pred": None}
            # Accounting (ISSUE 18): with the ledger registry installed
            # and a lifecycle context to own the bill, the finish hook
            # also joins + records the query's resource bill and runs
            # the regression sentinel.  Disabled: one ambient attr read.
            acct_on = _ACCT_CTX.LEDGERS is not None and qctx is not None
            if prof_dir or acct_on:
                _conf = self.session.conf

                def on_finish(diag, _conf=_conf, _box=cost_box,
                              _prof=bool(prof_dir), _acct=acct_on):
                    if _prof:
                        from spark_rapids_tpu.profiling import record_query

                        record_query(diag, _conf,
                                     prediction=_box["pred"])
                    if _acct:
                        from spark_rapids_tpu.accounting import record_bill

                        # AFTER record_query: a freshly folded operator
                        # calibration must not shift THIS query's
                        # sentinel baseline mid-flight (signatures merge
                        # on the same store but are read once here)
                        record_bill(diag, _conf)

            # Progress (ISSUE 12): lifecycle-managed queries register
            # with the process-global live tracker.  Disabled (default):
            # one conf read, zero progress-module calls (pinned by
            # tests/test_progress.py).
            prog_trk = None
            if qctx is not None:
                from spark_rapids_tpu.config import (
                    PROGRESS_ENABLED,
                    PROGRESS_MAX_FINISHED,
                )

                if self.session.conf.get(PROGRESS_ENABLED):
                    from spark_rapids_tpu.progress import ensure_tracker

                    prog_trk = ensure_tracker(int(
                        self.session.conf.get(PROGRESS_MAX_FINISHED)))
            scope = query_scope(self.session.conf, root,
                                on_finish=on_finish)
            try:
                # thread-local conf pin: concurrent collects each read
                # THEIR OWN session conf through config.get_conf() on
                # their own thread, instead of racing the process-global
                # ambient slot _planned() set (ISSUE 4: N queries with
                # different knobs must not clobber each other)
                with ambient_conf(self.session.conf), scope:
                    if scope.diag is not None and qctx is not None:
                        scope.diag.lifecycle(
                            "admitted", qctx.query_id,
                            qctx.admission_wait_ns)
                    # Plan-time cost model (ISSUE 8): predict each
                    # operator's wall/transfer from the calibration
                    # store BEFORE execution (cost_model_* counters land
                    # inside the recorder window and attribute to the
                    # query); the prediction is compared against the
                    # recorded actuals by the finish hook above
                    if prof_dir:
                        from spark_rapids_tpu.profiling import (
                            annotate_plan,
                        )

                        cost_box["pred"] = annotate_plan(
                            root, self.session.conf,
                            attributed=scope.diag is not None)
                    # Progress registration AFTER the cost model ran:
                    # the prediction joins per-operator predicted walls
                    # into percent-complete / ETA; without a store the
                    # tracker falls back to plan row estimates
                    if prog_trk is not None:
                        from spark_rapids_tpu.config import (
                            PROGRESS_STALL_MS,
                        )

                        prog_trk.register(
                            qctx, root,
                            stall_ms=float(self.session.conf.get(
                                PROGRESS_STALL_MS)),
                            prediction=cost_box["pred"],
                            diag_qid=(scope.diag.query_id
                                      if scope.diag is not None
                                      else None))
                        # live explain("analyze") key: while this
                        # collect is in flight, analyze renders the
                        # LIVE snapshot instead of the last post-hoc
                        # recorder
                        self._live_progress_qid = qctx.query_id
                    # progress finish must cover EVERYTHING after
                    # registration: a raise below (bad injection spec,
                    # semaphore conf parse) would otherwise leave a
                    # ghost "running" query in the tracker forever
                    _prog_status = "error"
                    # srt.prepare: from here until the permit is held
                    prepare = contextlib.ExitStack()
                    prepare.enter_context(_PC.span("srt.prepare"))
                    try:
                        # Plan-time AOT pipeline (compilecache/aot.py):
                        # enumerate the stage programs this exec tree
                        # will need and compile them on the background
                        # pool NOW, so the first operator's first batch
                        # overlaps the compiles of everything
                        # downstream.  Idempotent per planned tree; a
                        # warm-up failure never reaches the query.
                        # AFTER progress registration: a compile
                        # finishing before register() would drop its
                        # background attribution on the floor.
                        from spark_rapids_tpu.compilecache import (
                            maybe_submit_aot,
                        )

                        maybe_submit_aot(root, self.session.conf)
                        # Admission control: the thread driving this
                        # query's iterator chain holds a TpuSemaphore
                        # permit while it touches the device (reference:
                        # GpuSemaphore.acquireIfNecessary at first
                        # batch).
                        from spark_rapids_tpu.memory import (
                            get_semaphore,
                            get_spill_framework,
                        )
                        from spark_rapids_tpu.memory.retry import (
                            force_retry_oom,
                            force_split_and_retry_oom,
                        )
                        from spark_rapids_tpu.config import (
                            TEST_RETRY_OOM_INJECTION_MODE,
                        )

                        get_spill_framework(self.session.conf)
                        inject = self.session.conf.get(
                            TEST_RETRY_OOM_INJECTION_MODE)
                        if inject and inject != "NONE":
                            kind, _, n = inject.partition(":")
                            if kind.upper() == "RETRY":
                                force_retry_oom(int(n or 1))
                            elif kind.upper() == "SPLIT":
                                force_split_and_retry_oom(int(n or 1))
                        # chaos injection (the force_retry_oom API
                        # generalized to compile/transient/poison faults
                        # at named operators); armed once per distinct
                        # spec, process-global like the fault list
                        from spark_rapids_tpu.config import (
                            RESILIENCE_TEST_INJECT,
                        )
                        from spark_rapids_tpu.resilience.faults import (
                            arm_conf_spec,
                        )

                        arm_conf_spec(self.session.conf.get(
                            RESILIENCE_TEST_INJECT))
                        from spark_rapids_tpu.config import (
                            SEMAPHORE_ACQUIRE_TIMEOUT_MS,
                        )

                        sem_timeout_ms = int(self.session.conf.get(
                            SEMAPHORE_ACQUIRE_TIMEOUT_MS))
                        sem = get_semaphore(
                            self.session.conf.concurrent_tpu_tasks)
                        try:
                            with sem.scope(
                                    timeout=(sem_timeout_ms / 1000.0
                                             if sem_timeout_ms > 0
                                             else None)):
                                prepare.close()
                                host = TpuColumnarToRowExec(
                                    root).collect_host()
                        except Exception as e:
                            from spark_rapids_tpu.lifecycle.context import (
                                QueryCancelled,
                                QueryDeadlineExceeded,
                            )

                            if isinstance(e, QueryCancelled) \
                                    and scope.diag is not None:
                                scope.diag.lifecycle(
                                    "deadline_trip"
                                    if isinstance(e, QueryDeadlineExceeded)
                                    else "cancelled", str(e))
                            # the whole-query CPU re-run makes no batch
                            # pulls: exempt it from stall detection so
                            # the frozen clock is not read as a wedge
                            if prog_trk is not None:
                                prog_trk.mark_untracked(qctx.query_id)
                            host = self._query_fallback(e)
                        _prog_status = "ok"
                    except BaseException as _pe:
                        _prog_status = type(_pe).__name__
                        raise
                    finally:
                        prepare.close()
                        # progress finish INSIDE the diagnostics scope:
                        # the summary event must land before query_end.
                        # Compare-and-clear the live-explain key: a
                        # concurrent collect of the same DataFrame may
                        # have overwritten it with ITS query id
                        if prog_trk is not None:
                            if getattr(self, "_live_progress_qid",
                                       None) == qctx.query_id:
                                self._live_progress_qid = None
                            prog_trk.finish_query(qctx.query_id,
                                                  _prog_status)
            finally:
                # None when this collect ran unrecorded; assigned on the
                # FAILURE path too — explain("analyze") must not report a
                # stale previous query's diagnostics as if they described
                # the latest (failed) execution
                self._last_diag = scope.diag
            with _PC.span("srt.rows"):
                lists = [h.to_pylist() for h in host]
                return list(zip(*lists)) if lists else []
        # full-oracle runs pin the session conf thread-locally too: the
        # oracle file scan reads the per-file tolerance confs (ISSUE 5)
        # through config.get_conf(), which must see THIS session's
        # settings, not the process-global slot
        from spark_rapids_tpu.config import ambient_conf

        with ambient_conf(self.session.conf):
            cols, n = execute_cpu_plan(
                root, ansi=self.session.conf.ansi_enabled)
        lists = [c.to_pylist() for c in cols]
        return list(zip(*lists)) if lists else []

    def _query_fallback(self, exc: Exception):
        """Whole-query oracle fallback of last resort: a deterministic
        failure that escaped every stage-level fault domain (e.g. a stage
        with no CPU twin, or a mid-stream failure after yields) re-runs
        the ORIGINAL logical plan on the CPU oracle — the runtime analog
        of spark.rapids.sql.enabled=false.  Semantic errors (ANSI,
        FAILFAST) and recoverable classes re-raise unchanged; if the
        oracle also fails, the original device error stays primary."""
        from spark_rapids_tpu import perfcounters as PC
        from spark_rapids_tpu.config import (
            RESILIENCE_ENABLED,
            RESILIENCE_RUNTIME_FALLBACK,
        )
        from spark_rapids_tpu.cpu.oracle import execute_cpu_plan
        from spark_rapids_tpu.resilience.classify import (
            DETERMINISTIC,
            classify_failure,
        )

        conf = self.session.conf
        if not (conf.get(RESILIENCE_ENABLED)
                and conf.get(RESILIENCE_RUNTIME_FALLBACK)):
            raise exc
        # a transient/OOM failure whose retry budget a stage domain
        # already exhausted is as good as deterministic here — retrying
        # the whole query would re-derive the same exhaustion
        if classify_failure(exc) != DETERMINISTIC \
                and not getattr(exc, "_srt_retries_exhausted", False):
            raise exc
        try:
            cols, _n = execute_cpu_plan(self.plan,
                                        ansi=conf.ansi_enabled)
        except Exception as oracle_err:
            raise exc from oracle_err
        PC.bump("query_fallbacks")
        from spark_rapids_tpu.diagnostics import context as DIAG_CTX

        rec = DIAG_CTX.RECORDER
        if rec is not None:
            rec.resilience("query_fallback", "collect",
                           f"{type(exc).__name__}: {exc}")
        return [c.to_host() for c in cols]

    def to_pydict(self) -> Dict[str, list]:
        rows = self.collect()
        names = self.columns
        return {n: [r[i] for r in rows] for i, n in enumerate(names)}

    def count(self) -> int:
        rows = self.agg(("count_star", None, "count")).collect()
        return int(rows[0][0]) if rows else 0

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)

    def metrics_report(self) -> str:
        """Per-operator metrics CUMULATIVE across every execution of this
        DataFrame's cached plan (run collect() first) — the Spark SQL UI
        metrics analog, which likewise accumulates across a query's
        tasks."""
        root, _ = self._planned()
        from spark_rapids_tpu.exec.base import TpuExec

        if isinstance(root, TpuExec):
            return root.metrics_report()
        return "(plan ran on the CPU oracle; no TPU metrics)"

    def explain(self, mode: str = "formatted") -> str:
        """``mode="analyze"``: re-print the plan tree annotated with each
        node's metrics, attributed counter deltas, compile-cache hits,
        and fallback status from the LAST collect() (requires
        spark.rapids.tpu.diagnostics.enabled for the counter columns;
        falls back to metrics-only otherwise) — the diagnostics analog of
        Spark's AQE ``explain`` with runtime statistics.

        ``mode="cost"``: annotate the plan with the profiling cost
        model's PRE-execution predictions — per-operator wall / transfer
        bytes / confidence from the calibration store
        (spark.rapids.tpu.profile.dir), plus predicted-vs-actual when
        the last collect was diagnosed (docs/profiling.md)."""
        from spark_rapids_tpu.exec.base import TpuExec

        if mode == "cost":
            from spark_rapids_tpu.profiling import explain_cost

            return explain_cost(self)
        if mode == "analyze":
            # Live introspection (ISSUE 12): while a collect of this
            # DataFrame is in flight, analyze renders the LIVE progress
            # snapshot (operator table, pct/ETA, background work)
            # instead of the last finished recorder — checked BEFORE
            # _planned() so an explain from another thread never
            # touches plan state mid-collect
            qid = getattr(self, "_live_progress_qid", None)
            if qid is not None:
                from spark_rapids_tpu.progress import (
                    render_snapshot,
                    snapshot_for,
                )

                snap = snapshot_for(qid)
                if snap is not None and snap["status"] == "running":
                    return ("live progress (query in flight — see "
                            "docs/progress.md):\n" + render_snapshot(snap))
        root, meta = self._planned()
        if mode == "analyze":
            if not isinstance(root, TpuExec):
                return "(plan ran on the CPU oracle; no TPU metrics)"
            from spark_rapids_tpu.config import METRICS_LEVEL
            from spark_rapids_tpu.diagnostics.report import analyze_tree

            return analyze_tree(root, getattr(self, "_last_diag", None),
                                meta,
                                self.session.conf.get(METRICS_LEVEL))
        s = root.pretty() if isinstance(root, TpuExec) else root.pretty()
        if meta is not None:
            fb = meta.explain(only_fallback=True)
            if fb:
                s += "\nFallback reasons:\n" + fb
        return s


class DataFrameWriter:
    """df.write API (DataFrameWriter analog); executes the write command
    through the plan rewrite so GPU-vs-CPU write placement follows the same
    tagging rules as reads."""

    def __init__(self, df: DataFrame):
        self.df = df
        self._mode = "overwrite"
        self._partition_by: List[str] = []
        self._options: Dict[str, str] = {}

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = m
        return self

    def partition_by(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    partitionBy = partition_by

    def option(self, k, v) -> "DataFrameWriter":
        self._options[k] = v
        return self

    def _run(self, fmt: str, path: str) -> None:
        node = PN.InsertIntoHadoopFsRelation(
            fmt, path, self.df.plan, self._partition_by, self._mode,
            self._options)
        DataFrame(node, self.df.session).collect()

    def parquet(self, path: str) -> None:
        self._run("parquet", path)

    def orc(self, path: str) -> None:
        self._run("orc", path)

    def csv(self, path: str) -> None:
        self._run("csv", path)

    def json(self, path: str) -> None:
        self._run("json", path)

    def iceberg(self, path: str) -> None:
        from spark_rapids_tpu.io.iceberg import write_iceberg

        mode = {"error": "error", "errorifexists": "error"}.get(
            self._mode, self._mode)
        write_iceberg(self.df, path, mode=mode,
                      partition_by=self._partition_by)

    def delta(self, path: str) -> None:
        from spark_rapids_tpu.delta import write_delta

        mode = {"overwrite": "overwrite", "append": "append",
                "error": "error", "errorifexists": "error",
                "ignore": "ignore"}.get(self._mode, self._mode)
        write_delta(self.df, path, mode=mode,
                    partition_by=self._partition_by)


def _estimated_plan_bytes(plan: PN.SparkPlan):
    """Size estimate for broadcast decisions; None = unknown (never
    broadcast).  LocalTableScan: exact host bytes; FileSourceScan: file
    sizes on disk (the stats Spark reads from the file system)."""
    if isinstance(plan, PN.LocalTableScan):
        total = 0
        for h in plan.host_columns:
            if h.chars is not None:
                total += int(h.lengths.sum()) + 4 * h.num_rows
            elif h.data is not None:
                total += h.data.nbytes
            total += h.num_rows  # validity
        return total
    if isinstance(plan, PN.FileSourceScan):
        import os

        try:
            return sum(os.path.getsize(p) for p in plan.paths)
        except OSError:
            return None
    if isinstance(plan, (PN.Project, PN.Filter, PN.GlobalLimit,
                         PN.LocalLimit, PN.CachedRelation)):
        # narrow nodes: bounded by the child (filters/limits only shrink)
        return _estimated_plan_bytes(plan.children[0])
    return None


def _is_broadcastable(plan: PN.SparkPlan, conf) -> bool:
    """spark.sql.autoBroadcastJoinThreshold applied to the size estimate
    (reference: GpuBroadcastHashJoin selection — a 10-row file scan
    broadcasts instead of shuffling both sides)."""
    from spark_rapids_tpu.config import AUTO_BROADCAST_JOIN_THRESHOLD

    threshold = conf.get(AUTO_BROADCAST_JOIN_THRESHOLD)
    if threshold < 0:
        return False
    est = _estimated_plan_bytes(plan)
    return est is not None and est <= threshold


def _named(e: Expression, i: int) -> Expression:
    return e


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[Expression]):
        self.df = df
        self.keys = keys

    def agg(self, *aggs) -> "DataFrame":
        """aggs: tuples (func, column-or-None, result_name) or
        AggregateExpression.  count_distinct/sum_distinct expand to a
        two-level aggregation at plan time (dedup on (keys, expr), then
        aggregate) — Spark's single-distinct-column rewrite — so both the
        TPU path and the oracle execute the same plan."""
        specs = list(aggs)
        distinct = [a for a in specs if isinstance(a, tuple)
                    and a[0] in ("count_distinct", "sum_distinct")]
        if distinct:
            if len(distinct) != len(specs):
                raise NotImplementedError(
                    "mixing distinct and non-distinct aggregates is not "
                    "supported yet")
            children = {str(a[1]) for a in distinct}
            if len(children) != 1:
                raise NotImplementedError(
                    "distinct aggregates over multiple columns are not "
                    "supported yet")
            schema = self.df.schema
            dcol = _to_expr(distinct[0][1]).resolve(schema)
            dedup = GroupedData(self.df, self.keys + [dcol]).agg()
            outer_keys = [k.name for k in self.keys]
            outer = [(a[0].replace("_distinct", ""), dcol.name, a[2])
                     for a in distinct]
            return dedup.group_by(*outer_keys).agg(*outer) if outer_keys \
                else dedup.agg(*outer)
        collect = [a for a in specs
                   if (isinstance(a, tuple)
                       and a[0] in PN.SINGLE_PHASE_FUNCS)
                   or (isinstance(a, PN.AggregateExpression)
                       and a.func in PN.SINGLE_PHASE_FUNCS)]
        if collect:
            # single-phase plan: co-locate each key's rows with a hash
            # exchange, then ONE COMPLETE-mode aggregate builds the arrays
            # (partial/final would need array-buffer merges)
            schema = self.df.schema
            aexprs = []
            for a in specs:
                if isinstance(a, PN.AggregateExpression):
                    aexprs.append(a.resolve(schema))
                    continue
                func, child, name = a
                ce = _to_expr(child) if child is not None else None
                aexprs.append(PN.AggregateExpression(
                    func, ce, name).resolve(schema))
            if self.keys:
                ex = PN.Exchange(
                    PN.HashPartitioning(self.keys,
                                        self.df.session.shuffle_partitions),
                    self.df.plan)
            else:
                ex = PN.Exchange(PN.SinglePartitioning(), self.df.plan)
            comp = PN.HashAggregate(self.keys, aexprs,
                                    PN.AggregateMode.COMPLETE, ex)
            return DataFrame(comp, self.df.session)
        schema = self.df.schema
        aexprs: List[PN.AggregateExpression] = []
        for a in aggs:
            if isinstance(a, PN.AggregateExpression):
                aexprs.append(a.resolve(schema))
            else:
                func, child, name = a
                ce = _to_expr(child) if child is not None else None
                aexprs.append(PN.AggregateExpression(
                    func, ce, name).resolve(schema))
        np_ = self.df.session.shuffle_partitions
        partial = PN.HashAggregate(self.keys, aexprs,
                                   PN.AggregateMode.PARTIAL, self.df.plan)
        if self.keys:
            # re-key the exchange + final agg on the partial output
            pschema = partial.output
            fkeys = [AttributeReference(g.name).resolve(pschema)
                     for g in self.keys]
            ex = PN.Exchange(PN.HashPartitioning(fkeys, np_), partial)
        else:
            fkeys = []
            ex = PN.Exchange(PN.SinglePartitioning(), partial)
        final_aggs = [PN.AggregateExpression(a.func, a.child, a.result_name,
                                             a.result_type,
                                             child2=a.child2, args=a.args)
                      for a in aexprs]
        final = PN.HashAggregate(fkeys, final_aggs,
                                 PN.AggregateMode.FINAL, ex)
        return DataFrame(final, self.df.session)


# convenience re-exports (pyspark.sql.functions flavored)
col = _col
lit = _lit


def sum_(c: ColumnLike, name: str = "sum") -> Tuple[str, ColumnLike, str]:
    return ("sum", c, name)


def count_(c: Optional[ColumnLike] = None, name: str = "count"):
    return ("count", c, name) if c is not None else ("count_star", None, name)


def count_distinct_(c: ColumnLike, name: str = "count_distinct"):
    return ("count_distinct", c, name)


def sum_distinct_(c: ColumnLike, name: str = "sum_distinct"):
    return ("sum_distinct", c, name)


def collect_list_(c: ColumnLike, name: str = "collect_list"):
    return ("collect_list", c, name)


def collect_set_(c: ColumnLike, name: str = "collect_set"):
    return ("collect_set", c, name)


def min_(c: ColumnLike, name: str = "min"):
    return ("min", c, name)


def max_(c: ColumnLike, name: str = "max"):
    return ("max", c, name)


def avg_(c: ColumnLike, name: str = "avg"):
    return ("avg", c, name)


def rlike_(c: ColumnLike, pattern: str):
    from spark_rapids_tpu.expr.strings import RLike

    return RLike(_to_expr(c), _lit(pattern))


def hash_(*cols: ColumnLike):
    from spark_rapids_tpu.expr.hashexprs import Murmur3Hash

    return Murmur3Hash([_to_expr(c) for c in cols])


def xxhash64_(*cols: ColumnLike):
    from spark_rapids_tpu.expr.hashexprs import XxHash64

    return XxHash64([_to_expr(c) for c in cols])


def stddev_(c: ColumnLike, name: str = "stddev"):
    return ("stddev_samp", c, name)


def stddev_pop_(c: ColumnLike, name: str = "stddev_pop"):
    return ("stddev_pop", c, name)


def variance_(c: ColumnLike, name: str = "variance"):
    return ("var_samp", c, name)


def var_pop_(c: ColumnLike, name: str = "var_pop"):
    return ("var_pop", c, name)


def count_if_(c: ColumnLike, name: str = "count_if"):
    return ("count_if", c, name)


def skewness_(c: ColumnLike, name: str = "skewness"):
    return ("skewness", c, name)


def kurtosis_(c: ColumnLike, name: str = "kurtosis"):
    return ("kurtosis", c, name)


def bool_and_(c: ColumnLike, name: str = "bool_and"):
    return PN.AggregateExpression("bool_and", _to_expr(c), name)


def bool_or_(c: ColumnLike, name: str = "bool_or"):
    return PN.AggregateExpression("bool_or", _to_expr(c), name)


def bit_and_(c: ColumnLike, name: str = "bit_and"):
    return PN.AggregateExpression("bit_and", _to_expr(c), name)


def bit_or_(c: ColumnLike, name: str = "bit_or"):
    return PN.AggregateExpression("bit_or", _to_expr(c), name)


def bit_xor_(c: ColumnLike, name: str = "bit_xor"):
    return PN.AggregateExpression("bit_xor", _to_expr(c), name)


def any_value_(c: ColumnLike, name: str = "any_value"):
    return PN.AggregateExpression("any_value", _to_expr(c), name)


def median_(c: ColumnLike, name: str = "median"):
    return PN.AggregateExpression("median", _to_expr(c), name)


def _regr(func):
    def helper(y: ColumnLike, x: ColumnLike, name: str = None):
        return PN.AggregateExpression(func, _to_expr(y), name or func,
                                      child2=_to_expr(x))
    helper.__name__ = func + "_"
    return helper


regr_count_ = _regr("regr_count")
regr_avgx_ = _regr("regr_avgx")
regr_avgy_ = _regr("regr_avgy")
regr_sxx_ = _regr("regr_sxx")
regr_syy_ = _regr("regr_syy")
regr_sxy_ = _regr("regr_sxy")
regr_slope_ = _regr("regr_slope")
regr_intercept_ = _regr("regr_intercept")
regr_r2_ = _regr("regr_r2")


def corr_(x: ColumnLike, y: ColumnLike, name: str = "corr"):
    return PN.AggregateExpression("corr", _to_expr(x), name,
                                  child2=_to_expr(y))


def covar_pop_(x: ColumnLike, y: ColumnLike, name: str = "covar_pop"):
    return PN.AggregateExpression("covar_pop", _to_expr(x), name,
                                  child2=_to_expr(y))


def covar_samp_(x: ColumnLike, y: ColumnLike, name: str = "covar_samp"):
    return PN.AggregateExpression("covar_samp", _to_expr(x), name,
                                  child2=_to_expr(y))


def percentile_(c: ColumnLike, percentage: float, name: str = "percentile"):
    return PN.AggregateExpression("percentile", _to_expr(c), name,
                                  args=(float(percentage),))


def approx_percentile_(c: ColumnLike, percentage: float,
                       accuracy: int = 10000,
                       name: str = "approx_percentile"):
    return PN.AggregateExpression("approx_percentile", _to_expr(c), name,
                                  args=(float(percentage), int(accuracy)))


def approx_count_distinct_(c: ColumnLike,
                           name: str = "approx_count_distinct"):
    return ("approx_count_distinct", c, name)


def bloom_filter_agg_(c: ColumnLike, name: str = "bloom_filter_agg",
                      num_items: int = 4096, num_bits: int = 65536):
    return PN.AggregateExpression("bloom_filter_agg", _to_expr(c), name,
                                  args=(int(num_items), int(num_bits)))
