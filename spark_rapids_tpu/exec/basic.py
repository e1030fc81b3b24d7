"""Scan / Project / Filter / Union / Range + whole-stage fusion.

Reference analog: basicPhysicalOperators.scala (GpuProjectExec, GpuFilterExec,
GpuTieredProject, GpuUnionExec, GpuRangeExec).

The TPU-first centerpiece is ``TpuStageExec``: a chain of narrow operators
(project/filter) is traced ONCE into a single jitted function per shape
bucket — XLA fuses every expression, the filter's mask/compaction, and the
ANSI error-flag reductions into one executable.  This is strictly stronger
than the reference's cuDF AST fusion (which only fuses simple expression
trees); it is why `spark.rapids.tpu.wholeStageFusion.enabled` exists.

Filters keep the row count on device until the stage boundary, where one
host sync reads (count, error flags) back.
"""
from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax
from spark_rapids_tpu.perfcounters import sync_get, tpu_jit
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, HostColumn
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.expr.base import (
    Alias,
    BoundReference,
    EvalContext,
    Expression,
    SparkArithmeticException,
)


class _StageOp:
    """One narrow op inside a fused stage."""

    def apply(self, ctx: EvalContext, batch: ColumnarBatch) -> ColumnarBatch:
        raise NotImplementedError

    def apply_masked(self, ctx: EvalContext, batch: ColumnarBatch, mask):
        """Selection-vector mode: no compaction — filters only narrow the
        row mask.  Used when the stage is fused into a downstream aggregate
        (the TPU-first answer to compaction scatters: aggregates consume the
        mask directly, so filtered rows never move)."""
        raise NotImplementedError

    def out_schema(self, in_schema: T.StructType) -> T.StructType:
        raise NotImplementedError


class ProjectOp(_StageOp):
    def __init__(self, exprs: List[Expression]):
        self.exprs = exprs

    def apply(self, ctx, batch):
        ctx.batch = batch
        cols = [e.eval_tpu(ctx) for e in self.exprs]
        return ColumnarBatch(cols, batch.num_rows, self.out_schema(batch.schema))

    def apply_masked(self, ctx, batch, mask):
        return self.apply(ctx, batch), mask

    def out_schema(self, in_schema):
        return T.StructType([
            T.StructField(e.name, e.dataType, e.nullable) for e in self.exprs])


class FilterOp(_StageOp):
    def __init__(self, condition: Expression):
        self.condition = condition

    def _mask(self, ctx, batch, mask):
        ctx.batch = batch
        pred = self.condition.eval_tpu(ctx)
        return pred.data & pred.validity & mask

    def apply(self, ctx, batch):
        from spark_rapids_tpu.ops.filterops import compact_columns

        mask = self._mask(ctx, batch, batch.row_mask)
        cols, count = compact_columns(mask, batch.columns)
        return ColumnarBatch(cols, count, batch.schema)

    def apply_masked(self, ctx, batch, mask):
        return batch, self._mask(ctx, batch, mask)

    def out_schema(self, in_schema):
        return in_schema


class FilterProjectOp(_StageOp):
    """Filter immediately followed by Project, fused: projections evaluate on
    the *uncompacted* batch (vector lanes are free), then only the projected
    columns are compacted — halves scatter traffic vs compacting the full
    input.  Not used under ANSI (a removed row must not raise)."""

    def __init__(self, condition: Expression, exprs: List[Expression]):
        self.condition = condition
        self.exprs = exprs

    def apply(self, ctx, batch):
        from spark_rapids_tpu.ops.filterops import compact_columns

        ctx.batch = batch
        pred = self.condition.eval_tpu(ctx)
        mask = pred.data & pred.validity & batch.row_mask
        cols = [e.eval_tpu(ctx) for e in self.exprs]
        out, count = compact_columns(mask, cols)
        return ColumnarBatch(out, count, self.out_schema(batch.schema))

    def apply_masked(self, ctx, batch, mask):
        ctx.batch = batch
        pred = self.condition.eval_tpu(ctx)
        mask = pred.data & pred.validity & mask
        cols = [e.eval_tpu(ctx) for e in self.exprs]
        out = ColumnarBatch(cols, batch.num_rows,
                            self.out_schema(batch.schema))
        return out, mask

    def out_schema(self, in_schema):
        return T.StructType([
            T.StructField(e.name, e.dataType, e.nullable) for e in self.exprs])


def _fuse_filter_project(ops: List[_StageOp], ansi: bool) -> List[_StageOp]:
    if ansi:
        return ops
    out: List[_StageOp] = []
    i = 0
    while i < len(ops):
        if (i + 1 < len(ops) and isinstance(ops[i], FilterOp)
                and isinstance(ops[i + 1], ProjectOp)):
            out.append(FilterProjectOp(ops[i].condition, ops[i + 1].exprs))
            i += 2
        else:
            out.append(ops[i])
            i += 1
    return out


def _selection(ops: Sequence[_StageOp]) -> Optional[List[int]]:
    """The input ordinals a chain of bare-reference projections emits, or
    None when an op computes anything: such a chain (what column pruning
    inserts below an exchange, sort or window) selects column objects —
    no program, no copy."""
    cur: Optional[List[int]] = None
    for op in ops:
        if type(op) is not ProjectOp:
            return None
        refs = [e.children[0] if isinstance(e, Alias) else e
                for e in op.exprs]
        if not all(isinstance(r, BoundReference) for r in refs):
            return None
        cur = [r.ordinal if cur is None else cur[r.ordinal] for r in refs]
    return cur


class TpuStageExec(TpuExec):
    """A fused chain of narrow ops over one child."""

    def __init__(self, ops: Sequence[_StageOp], child: TpuExec,
                 ansi: bool = False):
        super().__init__([child])
        self.ops = _fuse_filter_project(list(ops), ansi)
        self.ansi = ansi
        self._jitted = None
        self._offset_holder = [0]
        self._out_schema = child.output
        for op in self.ops:
            self._out_schema = op.out_schema(self._out_schema)

    @property
    def output(self):
        return self._out_schema

    def describe(self):
        names = "+".join(type(o).__name__.replace("Op", "") for o in self.ops)
        return f"TpuStageExec[{names}]"

    def _op_expressions(self) -> List[Expression]:
        out: List[Expression] = []
        for op in self.ops:
            out.extend(getattr(op, "exprs", []) or [])
            cond = getattr(op, "condition", None)
            if cond is not None:
                out.append(cond)
        return out

    def _has_host_kernels(self) -> bool:
        from spark_rapids_tpu.expr.base import contains_host_kernel

        return any(contains_host_kernel(e) for e in self._op_expressions())

    def _stage_fn(self, in_schema: T.StructType):
        """The traceable stage function + its ANSI message store (filled as
        a trace-time side effect, so it must travel WITH the executable)."""
        ops = self.ops
        ansi = self.ansi

        msgs_store: List[str] = []  # filled as a trace-time side effect

        offset_holder = self._offset_holder

        def fn(cols, num_rows):
            batch = ColumnarBatch(list(cols), num_rows, in_schema)
            # row_offset is only consumed by host-kernel expressions, which
            # force the EAGER path — under jit the closure value would be
            # baked at trace time, but jitted stages never contain them
            ctx = EvalContext(batch, ansi=ansi,
                              # tpulint: disable=trace-closure-state
                              # (eager-only read, per the comment above)
                              row_offset=offset_holder[0])
            for op in ops:
                batch = op.apply(ctx, batch)
            # tpulint: disable=trace-closure-state (deliberate trace-time
            # aux: the store travels WITH the executable as entry.aux)
            msgs_store.clear()
            # tpulint: disable=trace-closure-state (same aux store)
            msgs_store.extend(m for _, m in ctx.error_flags)
            flags = tuple(jnp.any(f) for f, _ in ctx.error_flags)
            return batch.columns, jnp.asarray(batch.num_rows), flags

        return fn, msgs_store

    def _program(self, in_schema: T.StructType):
        """(registry key parts, factory) — shared verbatim by the runtime
        build and the plan-time AOT enumeration so both land on the same
        registry entry."""
        from spark_rapids_tpu.compilecache.keys import (
            conf_fp,
            schema_fp,
            stage_ops_fp,
        )

        ops_fp = stage_ops_fp(self.ops)
        key_parts = None if ops_fp is None else (
            "stage", schema_fp(in_schema), ops_fp, bool(self.ansi),
            conf_fp())

        def factory():
            fn, msgs = self._stage_fn(in_schema)
            return tpu_jit(fn), msgs

        return key_parts, factory

    def _build(self, in_schema: T.StructType):
        sel = _selection(self.ops)
        if sel is not None:
            return lambda batch: ColumnarBatch(
                [batch.columns[i] for i in sel], batch.num_rows,
                self._out_schema)
        # host-kernel expressions (JSON, digests, ... — jax.pure_callback)
        # cannot live inside a compiled TPU program (the PJRT plugin has no
        # host-callback channel); the stage runs op-by-op eagerly instead —
        # callbacks execute directly and the jnp ops still dispatch to the
        # device.  CPU/test backends jit as usual.
        if self._has_host_kernels():
            jitted, msgs_store = self._stage_fn(in_schema)
        else:
            from spark_rapids_tpu.compilecache.registry import cached_program

            key_parts, factory = self._program(in_schema)
            entry = cached_program(key_parts, factory,
                                   label=self.describe())
            jitted, msgs_store = entry.jitted, entry.aux

        def run(batch: ColumnarBatch) -> ColumnarBatch:
            cols, count, flags = jitted(
                tuple(batch.columns), jnp.int32(batch.num_rows))
            # row count + every ANSI error flag in ONE logical round
            # trip — a per-flag bool() was a device sync per flag per
            # batch (tracelint: trace-split-sync)
            host = sync_get((count,) + tuple(flags))
            for f, m in zip(host[1:], list(msgs_store)):
                if f:
                    raise SparkArithmeticException(m)
            return ColumnarBatch(list(cols), int(host[0]),
                                 self._out_schema)

        return run

    def fusion_segment(self):
        """Whole-plan fusion slice (exec/fusion.py): the stage's traced
        chain inlines into a larger fused program.  The ANSI message
        store ``_stage_fn`` fills at trace time travels with the fused
        executable as registry aux — the manifest's fusable-with-rewrite
        rewrite for Filter/Project.  Host-kernel stages must run
        eagerly, so they refuse."""
        if self._has_host_kernels():
            return None
        from spark_rapids_tpu.compilecache.keys import stage_ops_fp
        from spark_rapids_tpu.exec.fusion import PipelineSegment

        ops_fp = stage_ops_fp(self.ops)
        return PipelineSegment(
            name=self.describe(),
            fp=None if ops_fp is None else (
                "stage", ops_fp, bool(self.ansi)),
            make=self._stage_fn,
            out_schema=self._out_schema,
            count_map=None if self._aot_filters_rows()
            else (lambda n: n),
            programs_unfused=0 if _selection(self.ops) is not None else 1)

    # -- plan-time AOT enumeration (compilecache/aot.py) -----------------
    def _aot_filters_rows(self) -> bool:
        return any(getattr(op, "condition", None) is not None
                   for op in self.ops)

    def aot_output_rows(self):
        # projections preserve row counts exactly; a filtering stage's
        # OUTPUT rows are data-dependent (a concat above would size its
        # capacity from the post-filter counts), though per-batch
        # capacity still passes through (aot_output_caps)
        if self._aot_filters_rows():
            return None
        return self.aot_input_rows()

    def aot_output_caps(self):
        return self.aot_input_caps()

    def aot_emits_single_batch(self):
        # one output batch per input batch
        return self.aot_child_single_batch()

    def aot_programs(self):
        from spark_rapids_tpu.compilecache.aot import (
            AotProgram,
            dummy_batch_args,
        )

        if self._has_host_kernels() or _selection(self.ops) is not None:
            return []
        caps = self.aot_input_caps()
        if not caps:
            return []
        in_schema = self.children[0].output
        key_parts, factory = self._program(in_schema)

        def args_factory():
            return [dummy_batch_args(in_schema, c) for c in caps]

        return [AotProgram(key_parts, factory, args_factory,
                           f"stage:{self.describe()[:48]}")]

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        child = self.children[0]
        self._offset_holder[0] = 0
        for batch in child.execute_columnar():
            if self._jitted is None:
                self._jitted = self._build(batch.schema)
            with self.metrics["opTime"].timed():
                out = self._jitted(batch)
            self._offset_holder[0] += batch.num_rows
            yield self._count_output(out)


class TpuProjectExec(TpuStageExec):
    def __init__(self, exprs: List[Expression], child: TpuExec,
                 ansi: bool = False):
        super().__init__([ProjectOp(exprs)], child, ansi)
        self.exprs = exprs

    def describe(self):
        return ("TpuProject [" +
                ", ".join(e.sql_string() for e in self.exprs) + "]")


class TpuFilterExec(TpuStageExec):
    def __init__(self, condition: Expression, child: TpuExec,
                 ansi: bool = False):
        super().__init__([FilterOp(condition)], child, ansi)
        self.condition = condition

    def describe(self):
        return f"TpuFilter ({self.condition.sql_string()})"


def fuse_stages(root: TpuExec) -> TpuExec:
    """Collapse adjacent TpuStageExec chains (whole-stage fusion pass).

    Reference analog: GpuTransitionOverrides' post-processing; here it turns
    Project(Filter(Project(x))) into one jitted XLA program.  A stage feeding
    a row-consuming aggregate is absorbed INTO the aggregate's program
    (mask mode): scan batch -> filter/project/partial-agg is then ONE XLA
    executable with no compaction scatter and no intermediate HBM round trip
    — strictly stronger than the reference's cuDF AST fusion."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.plan.nodes import AggregateMode

    root.children = [fuse_stages(c) for c in root.children]
    if isinstance(root, TpuStageExec):
        child = root.children[0]
        if isinstance(child, TpuStageExec) and child.ansi == root.ansi:
            merged = TpuStageExec(child.ops + root.ops, child.children[0],
                                  root.ansi)
            return fuse_stages(merged)
    if isinstance(root, TpuHashAggregateExec):
        child = root.children[0]
        if (isinstance(child, TpuStageExec) and not child.ansi
                and not root.ansi and not root.pre_ops
                and root.mode in (AggregateMode.PARTIAL,
                                  AggregateMode.COMPLETE)):
            root.pre_ops = list(child.ops)
            root.input_schema = child.children[0].output
            root.children = [child.children[0]]
    return root


class TpuLocalTableScanExec(TpuExec):
    def __init__(self, host_columns: List[HostColumn], schema: T.StructType,
                 target_batch_rows: Optional[int] = None,
                 cache_device: bool = False, cache_slot=None,
                 ordinals: Optional[List[int]] = None):
        super().__init__([])
        self.host_columns = host_columns
        self._schema = schema
        self.target_batch_rows = target_batch_rows
        self.cache_device = cache_device
        # cache lives on the user's plan node so it survives re-planning;
        # it is kept per column, under the column's ordinal in that node,
        # so the narrowed scans of different queries (plan/pruning.py)
        # share what they both read and upload only what is missing
        self._slot = cache_slot if cache_slot is not None else self
        self._ordinals = list(ordinals) if ordinals is not None \
            else list(range(len(host_columns)))
        self._resident_cache = None     # this exec's batches, assembled

    @property
    def output(self):
        return self._schema

    def execute_columnar(self):
        if not self.cache_device or not self._ordinals:
            for b in self._materialize(range(len(self.host_columns))):
                yield self._count_output(b)
            return
        if self._resident_cache is None:
            self._resident_cache = self._resident_batches()
        for b in self._resident_cache:
            yield self._count_output(b)

    def _resident_batches(self) -> List[ColumnarBatch]:
        """This scan's batches over the slot's per-column cache, uploading
        the columns no earlier planning or query left there."""
        cache = getattr(self._slot, "_device_cache", None)
        if cache is None:
            cache = self._slot._device_cache = {"rows": [], "cols": {}}
        missing = [i for i, o in enumerate(self._ordinals)
                   if o not in cache["cols"]]
        if missing:
            fresh = list(self._materialize(missing))
            # rows before columns: a concurrent collect that finds every
            # column it reads also finds the row counts
            cache["rows"] = [b.num_rows for b in fresh]
            for k, i in enumerate(missing):
                cache["cols"][self._ordinals[i]] = [
                    b.columns[k] for b in fresh]
        schema = T.StructType([T.StructField(f.name, f.dataType)
                               for f in self._schema.fields])
        return [ColumnarBatch([cache["cols"][o][k] for o in self._ordinals],
                              n, schema)
                for k, n in enumerate(cache["rows"])]

    def aot_output_rows(self):
        """Exact per-batch row counts (mirrors _materialize's chunking) —
        the AOT pipeline's ground truth for shape buckets."""
        n = self.host_columns[0].num_rows if self.host_columns else 0
        step = self.target_batch_rows or max(n, 1)
        out = []
        for start in range(0, max(n, 1), step):
            out.append(min(start + step, n) - start if n else 0)
            if n == 0:
                break
        return out

    def _materialize(self, which):
        """Upload the columns ``which`` (positions in this scan), chunked."""
        n = self.host_columns[0].num_rows if self.host_columns else 0
        step = self.target_batch_rows or max(n, 1)
        names = self._schema.field_names()
        for start in range(0, max(n, 1), step):
            end = min(start + step, n)
            if n == 0 and start > 0:
                break
            chunk = [self.host_columns[i].slice_rows(start, end)
                     for i in which]
            yield ColumnarBatch.from_host_columns(
                chunk, [names[i] for i in which])
            if n == 0:
                break


class TpuRangeExec(TpuExec):
    """GpuRangeExec analog: generate id column on device."""

    def __init__(self, start: int, end: int, step: int = 1,
                 batch_rows: int = 1 << 20):
        super().__init__([])
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows

    @property
    def output(self):
        return T.StructType([T.StructField("id", T.LONG, nullable=False)])

    def aot_output_rows(self):
        total = max(0, -(-(self.end - self.start) // self.step))
        out, emitted = [], 0
        while emitted < total or (total == 0 and emitted == 0):
            count = min(self.batch_rows, total - emitted)
            out.append(count)
            emitted += count
            if total == 0:
                break
        return out

    def execute_columnar(self):
        total = max(0, -(-(self.end - self.start) // self.step))
        from spark_rapids_tpu.columnar.column import round_up_bucket, DEFAULT_ROW_BUCKETS

        emitted = 0
        while emitted < total or (total == 0 and emitted == 0):
            count = min(self.batch_rows, total - emitted)
            cap = round_up_bucket(max(count, 1), DEFAULT_ROW_BUCKETS)
            base = self.start + emitted * self.step
            data = base + jnp.arange(cap, dtype=jnp.int64) * self.step
            validity = jnp.arange(cap) < count
            col = DeviceColumn(T.LONG, validity, data=data)
            yield self._count_output(
                ColumnarBatch([col], count, self.output))
            emitted += count
            if total == 0:
                break


class TpuUnionExec(TpuExec):
    @property
    def output(self):
        return self.children[0].output

    def aot_output_rows(self):
        out = []
        for c in self.children:
            fn = getattr(c, "aot_output_rows", None)
            rows = fn() if fn is not None else None
            if rows is None:
                return None
            out.extend(rows)
        return out

    def execute_columnar(self):
        for c in self.children:
            for b in c.execute_columnar():
                yield self._count_output(b)


class TpuInMemoryTableScanExec(TpuExec):
    """df.cache() exec: first run materializes the child's batches into
    SPILLABLE handles stored on the plan node (so the cache survives
    re-planning and is reclaimable under memory pressure); later runs
    replay them.

    Reference analog: GpuInMemoryTableScanExec + ParquetCachedBatchSerializer
    (SURVEY.md §2.8) — device-resident cached batches instead of
    parquet-encoded host buffers (HBM spill handles play the same role)."""

    def __init__(self, child: TpuExec, cache_slot: dict):
        super().__init__([child])
        self.cache_slot = cache_slot

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        state = "hit" if "tpu" in self.cache_slot else "cold"
        return f"TpuInMemoryTableScan [{state}]"

    def execute_columnar(self):
        from spark_rapids_tpu.memory.spill import get_spill_framework

        cached = self.cache_slot.get("tpu")
        if cached is None:
            # materialize eagerly BEFORE yielding: an abandoned generator
            # (e.g. a limit above the cache) must not leak tracked handles
            # or leave a partial cache
            fw = get_spill_framework()
            acc = []
            try:
                # persistent: cache handles intentionally outlive the
                # query (until unpersist), so query-end cleanup and the
                # leak gate must not reap them
                for b in self.children[0].execute_columnar():
                    acc.append(fw.track(b, persistent=True))
            except BaseException:
                for s in acc:
                    s.close()
                raise
            self.cache_slot["tpu"] = acc
            cached = acc
        for s in cached:
            s.pin()
            try:
                b = s.get_batch()
            finally:
                s.unpin()
            yield self._count_output(b)
