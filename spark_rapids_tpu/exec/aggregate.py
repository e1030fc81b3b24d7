"""TpuHashAggregateExec — sort-based group-by aggregation.

Reference analog: GpuHashAggregateExec / GpuAggregateIterator /
GpuMergeAggregateIterator (SURVEY.md §2.4): batches are aggregated, partials
merged, with a sort-based fallback when merge output is too big.  TPU-first
redesign: the *primary* algorithm is sort-based (lax.sort by packed key words
+ segmented reductions) because Pallas/XLA favor sorting networks over
device-wide-atomic hash tables (SURVEY.md §7 hard part #3).  The reference's
"fall back to sort" becomes our main path; its hash fast-path can come later
as a Pallas kernel if profiling demands.

Partial/Final mode split matches Spark exactly (partial before the exchange,
final after), including avg -> (sum, count) partial buffers.

The entire aggregation — key packing, sort, segmentation, every aggregate
update — is one jitted XLA program per shape bucket.
"""
from __future__ import annotations

from typing import Iterator, List, Optional

import jax
from spark_rapids_tpu.perfcounters import bump, tpu_jit
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan.nodes import REGR_FUNCS as PN_REGR_FUNCS
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.expr.base import BoundReference, EvalContext, Expression
from spark_rapids_tpu.ops import segment as SEG
from spark_rapids_tpu.ops.sortkeys import (
    SortSpec,
    _column_key_words,
    group_segments,
)
from spark_rapids_tpu.plan.nodes import (
    COVARIANCE_FUNCS,
    HIGHER_MOMENT_FUNCS,
    HLL_DEFAULT_P,
    MOMENT_BUFFERS,
    SINGLE_PHASE_FUNCS,
    VARIANCE_FUNCS,
    AggregateExpression,
    AggregateMode,
)


def _is_float(dt: T.DataType) -> bool:
    return isinstance(dt, (T.FloatType, T.DoubleType))


class TpuHashAggregateExec(TpuExec):
    def __init__(self, grouping: List[Expression],
                 aggregates: List[AggregateExpression],
                 mode: AggregateMode, child: TpuExec,
                 child_plan_output: T.StructType,
                 output_schema: T.StructType,
                 ansi: bool = False):
        super().__init__([child])
        self.grouping = grouping
        self.aggregates = aggregates
        self.mode = mode
        self.child_schema = child_plan_output
        self._output = output_schema
        self.ansi = ansi
        # whole-stage fusion (fuse_stages): narrow ops absorbed into this
        # node's jitted program, applied in selection-mask mode
        self.pre_ops = []
        self.input_schema = child_plan_output
        from spark_rapids_tpu.config import AGG_SMALL_GROUPS_CAP, get_conf

        # the groups-cap ladder's first rung, read when the plan is built
        # (never inside a trace): full-width programs past it take the
        # end-row form (_ends_form)
        self._ends_above = get_conf().get(AGG_SMALL_GROUPS_CAP)

    @property
    def output(self):
        return self._output

    def describe(self):
        g = ", ".join(e.sql_string() for e in self.grouping)
        a = ", ".join(a.describe() for a in self.aggregates)
        fused = ""
        if self.pre_ops:
            names = "+".join(type(o).__name__.replace("Op", "")
                             for o in self.pre_ops)
            fused = f" fused=[{names}]"
        # the form the last full-width grouped program took
        seg = getattr(self, "_seg_form", None)
        seg = f" seg={seg}" if seg else ""
        return (f"TpuHashAggregate({self.mode.value}) keys=[{g}] "
                f"aggs=[{a}]{fused}{seg}")

    @property
    def _has_collect(self) -> bool:
        return any(a.func in SINGLE_PHASE_FUNCS for a in self.aggregates)

    # ------------------------------------------------------------------
    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        if self._has_collect:
            yield from self._execute_collect()
            return
        yield from self._execute_streaming()

    def _execute_collect(self) -> Iterator[ColumnarBatch]:
        """collect_list/collect_set: concat all input (a hash exchange has
        already co-located keys), ONE aggregate pass (array-buffer merges
        across partials are not implemented — reference: GpuCollectList is
        likewise a memory-hungry TypedImperativeAggregate)."""
        batches = list(self.children[0].execute_columnar())
        if not batches:
            from spark_rapids_tpu.columnar.batch import empty_batch

            if not self.grouping:
                yield self._count_output(self._collect_empty_global())
            else:
                yield self._count_output(empty_batch(self._output))
            return
        with self.metrics["opTime"].timed():
            batch = (batches[0] if len(batches) == 1
                     else ColumnarBatch.concat(batches))
            yield self._count_output(self._aggregate_batch(batch))

    def _collect_empty_global(self) -> ColumnarBatch:
        cols = []
        for a, f in zip(self.aggregates, self._output.fields):
            if a.func in ("collect_list", "collect_set"):
                # empty array, not null
                cols.append(DeviceColumn(
                    f.dataType, jnp.ones(1, jnp.bool_),
                    data=jnp.zeros((1, 1),
                                   T.storage_dtype(f.dataType.elementType)),
                    lengths=jnp.zeros(1, jnp.int32),
                    elem_valid=jnp.zeros((1, 1), jnp.bool_)))
            elif a.func == "bloom_filter_agg":
                words = int(a.args[1]) // 64
                cols.append(DeviceColumn(
                    f.dataType, jnp.ones(1, jnp.bool_),
                    data=jnp.zeros((1, words), jnp.int64),
                    lengths=jnp.full(1, words, jnp.int32),
                    elem_valid=jnp.ones((1, words), jnp.bool_)))
            elif a.func in ("count", "count_star"):
                cols.append(DeviceColumn(
                    f.dataType, jnp.ones(1, jnp.bool_),
                    data=jnp.zeros(1, T.storage_dtype(f.dataType))))
            else:
                cols.append(DeviceColumn(
                    f.dataType, jnp.zeros(1, jnp.bool_),
                    data=jnp.zeros(1, T.storage_dtype(f.dataType))))
        return ColumnarBatch(cols, 1, self._output)

    def _execute_streaming(self) -> Iterator[ColumnarBatch]:
        """Streaming aggregation with bounded memory.

        Reference analog: GpuAggregateIterator + GpuMergeAggregateIterator —
        each input batch is pre-aggregated on its own, the per-batch results
        (buffer form) are kept *spillable*, then merged pairwise; only at the
        end does FINAL mode apply the finalizing transform.  Peak HBM is
        ~2 batches regardless of input count, and every step runs inside the
        OOM-retry framework (split-and-retry on the pre-aggregation, since
        splitting input rows pre-agg is always sound)."""
        from spark_rapids_tpu.memory.retry import with_retry, with_retry_no_split
        from spark_rapids_tpu.memory.spill import get_spill_framework

        fw = get_spill_framework()
        if self.mode == AggregateMode.COMPLETE:
            yield from self._execute_complete(fw)
            return
        spillables = []
        any_input = False
        for b in self.children[0].execute_columnar():
            any_input = True
            with self.metrics["opTime"].timed():
                for out in with_retry(fw.track(b), self._preagg_batch):
                    spillables.append(fw.track(out))
        if not any_input:
            from spark_rapids_tpu.columnar.batch import empty_batch

            if not self.grouping:
                yield self._global_agg_empty()
            else:
                yield empty_batch(self._output)
            return
        with self.metrics["opTime"].timed():
            # pairwise merge tree over spillable partials
            while len(spillables) > 1:
                a, b2 = spillables.pop(0), spillables.pop(0)
                merged = with_retry_no_split(lambda: self._merge_pair(a, b2))
                spillables.append(fw.track(merged))
            last = spillables[0]
            buf = last.get_batch()
            last.close()
            out = self._finalize(buf)
        yield self._count_output(out)

    # -- streaming pieces ----------------------------------------------
    def _buffer_schema(self) -> T.StructType:
        """Schema of the intermediate buffer form (PARTIAL-shaped)."""
        if self.mode == AggregateMode.FINAL:
            return self.child_schema
        return self._output  # PARTIAL output is the buffer form

    # -- COMPLETE mode --------------------------------------------------
    def _complete_twins(self):
        """PARTIAL/FINAL twin execs for multi-batch COMPLETE execution.

        A COMPLETE aggregate cannot merge its own finalized outputs
        (avg/variance would average averages), so when more than one input
        batch arrives the work routes through a PARTIAL twin (buffer form
        per batch), buffer-form merges, and one FINAL finalize — exactly
        the two-phase plan, minus the exchange."""
        cached = getattr(self, "_twin_cache", None)
        if cached is not None:
            return cached
        from spark_rapids_tpu.expr.base import AttributeReference
        from spark_rapids_tpu.plan.nodes import partial_buffer_schema

        buf_schema = partial_buffer_schema(self.grouping, self.aggregates)
        p = TpuHashAggregateExec(self.grouping, self.aggregates,
                                 AggregateMode.PARTIAL, self.children[0],
                                 self.child_schema, buf_schema, self.ansi)
        p.pre_ops = self.pre_ops
        p.input_schema = self.input_schema
        fkeys = [AttributeReference(g.name).resolve(buf_schema)
                 for g in self.grouping]
        faggs = [AggregateExpression(a.func, a.child, a.result_name,
                                     a.result_type, child2=a.child2,
                                     args=a.args)
                 for a in self.aggregates]
        f = TpuHashAggregateExec(fkeys, faggs, AggregateMode.FINAL,
                                 self.children[0], buf_schema, self._output,
                                 self.ansi)
        self._twin_cache = (p, f)
        return self._twin_cache

    def _execute_complete(self, fw) -> Iterator[ColumnarBatch]:
        """COMPLETE: one input batch -> ONE fused program (aggregate +
        finalize); multiple batches -> two-phase via twins."""
        from spark_rapids_tpu.memory.retry import (
            with_retry,
            with_retry_no_split,
        )

        it = self.children[0].execute_columnar()
        first = next(it, None)
        if first is None:
            from spark_rapids_tpu.columnar.batch import empty_batch

            if not self.grouping:
                yield self._global_agg_empty()
            else:
                yield empty_batch(self._output)
            return
        second = next(it, None)
        if second is None:
            from spark_rapids_tpu.memory.retry import TpuSplitAndRetryOOM

            s = fw.track(first)
            try:
                with self.metrics["opTime"].timed():
                    s.pin()
                    try:
                        out = with_retry_no_split(
                            lambda: self._aggregate_batch(s.get_batch()))
                    finally:
                        s.unpin()
            except TpuSplitAndRetryOOM:
                # the fused single program cannot split; the two-phase
                # twins can (PARTIAL buffers merge correctly over pieces)
                yield from self._complete_two_phase(iter(()), fw, [s])
                return
            except BaseException:
                s.close()
                raise
            s.close()
            yield self._count_output(out)
            return

        def feed():
            yield first
            yield second
            yield from it

        yield from self._complete_two_phase(feed(), fw, [])

    def _complete_two_phase(self, batches, fw,
                            tracked) -> Iterator[ColumnarBatch]:
        """Multi-batch (or split-forced) COMPLETE: PARTIAL per batch ->
        buffer merges -> one FINAL finalize."""
        from spark_rapids_tpu.memory.retry import (
            with_retry,
            with_retry_no_split,
        )

        partial, final = self._complete_twins()
        spillables = []
        for s in tracked:
            with self.metrics["opTime"].timed():
                for out in with_retry(s, partial._aggregate_batch):
                    spillables.append(fw.track(out))
        for b in batches:
            with self.metrics["opTime"].timed():
                for out in with_retry(fw.track(b), partial._aggregate_batch):
                    spillables.append(fw.track(out))
        with self.metrics["opTime"].timed():
            while len(spillables) > 1:
                a, b2 = spillables.pop(0), spillables.pop(0)
                merged = with_retry_no_split(lambda: final._merge_pair(a, b2))
                spillables.append(fw.track(merged))
            last = spillables[0]
            last.pin()
            try:
                buf = last.get_batch()
            finally:
                last.unpin()
            last.close()
            out = final._finalize(buf)
        yield self._count_output(out)

    def _preagg_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        """One input batch -> buffer-form partial result."""
        if self.mode == AggregateMode.FINAL:
            # child feeds buffer rows: reduce them with merge semantics
            return self._merge_batch(batch)
        return self._aggregate_batch(batch)

    def _merge_pair(self, a, b) -> ColumnarBatch:
        # inputs close only AFTER the merge succeeds: callers run this under
        # with_retry_no_split, whose contract requires the block to be
        # re-runnable — closing first would hand a retry freed buffers
        a.pin()
        b.pin()
        try:
            cat = ColumnarBatch.concat([a.get_batch(), b.get_batch()])
            out = self._merge_batch(cat)
        finally:
            a.unpin()
            b.unpin()
        a.close()
        b.close()
        return out

    def _program_fp(self):
        """Registry fingerprint parts for this aggregate's programs, or
        None when an expression is not safely fingerprintable (then every
        jit stays instance-private)."""
        from spark_rapids_tpu.compilecache.keys import (
            aggs_fp,
            conf_fp,
            exprs_fp,
            schema_fp,
            stage_ops_fp,
        )

        g = exprs_fp(self.grouping)
        a = aggs_fp(self.aggregates)
        p = stage_ops_fp(self.pre_ops)
        if g is None or a is None or p is None:
            return None
        return ("agg", g, a, p, self.mode.value,
                schema_fp(self.input_schema), schema_fp(self.child_schema),
                schema_fp(self._output), bool(self.ansi), conf_fp())

    def _merge_jit(self):
        if getattr(self, "_merge_jitted", None) is None:
            from spark_rapids_tpu.compilecache.registry import (
                cached_program,
            )

            fpp = self._program_fp()
            key_parts = None if fpp is None else fpp + ("mergefn",)
            self._merge_jitted = cached_program(
                key_parts,
                lambda: (tpu_jit(self.detached_for_trace()._merge_fn),
                         None),
                label=f"agg-merge:{self.describe()[:40]}").jitted
        return self._merge_jitted

    def _merge_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Re-aggregate buffer-form rows with per-agg merge functions."""
        cols, nrows = self._merge_jit()(tuple(batch.columns),
                                        jnp.int32(batch.num_rows))
        # global aggregates have a statically known single output row —
        # skip the device sync (int(nrows) blocks until the program ends)
        n = 1 if not self.grouping else int(nrows)
        return ColumnarBatch(list(cols), n, self._buffer_schema())

    def _finalize(self, buf: ColumnarBatch) -> ColumnarBatch:
        """Buffer form -> this node's output form."""
        if self.mode == AggregateMode.FINAL:
            return self._aggregate_batch(buf)
        return buf  # PARTIAL / COMPLETE buffers are the output

    def _merge_fn(self, cols, num_rows, row_valid=None):
        schema = self._buffer_schema()
        batch = ColumnarBatch(list(cols), num_rows, schema)
        ctx = EvalContext(batch, ansi=self.ansi)
        k = len(self.grouping)
        key_cols = list(batch.columns[:k])
        cap = batch.capacity
        mask = batch.row_mask
        if row_valid is not None:
            # mesh epoching: accumulator + all-to-all-received rows carry an
            # explicit occupancy mask instead of a dense [0, num_rows) prefix
            mask = mask & row_valid
        if not key_cols:
            seg = jnp.where(mask, 0, 1).astype(jnp.int32)
            perm = None
            mask_sorted = mask
            group_valid = jnp.ones(1, jnp.bool_)
            ngroups = jnp.int32(1)
            nseg = 1
        else:
            keys: List[jax.Array] = []
            hi = jnp.int64(9223372036854775807)
            for kc in key_cols:
                nullk = jnp.where(kc.validity, 0, -1).astype(jnp.int64)
                keys.append(jnp.where(mask, nullk, hi))
                for w in _column_key_words(kc):
                    keys.append(jnp.where(mask, jnp.where(kc.validity, w, 0), hi))
            perm = jax.lax.sort(
                tuple(keys) + (jnp.arange(cap, dtype=jnp.int32),),
                num_keys=len(keys), is_stable=True)[-1]
            sorted_keys = [kk[perm] for kk in keys]
            mask_sorted = mask[perm]
            seg, ngroups = group_segments(sorted_keys, mask_sorted)
            seg = jnp.where(mask_sorted, seg, cap - 1)
            group_valid = jnp.arange(cap) < ngroups
            nseg = cap
        out_cols: List[DeviceColumn] = []
        if key_cols:
            first_idx = SEG.seg_first_index(seg, mask_sorted, cap)
            safe_first = jnp.clip(first_idx, 0, cap - 1)
            for kc in key_cols:
                kcs = _gather_col(kc, perm)
                g = _gather_col(kcs, safe_first)
                out_cols.append(DeviceColumn(
                    g.dtype, g.validity & group_valid, data=g.data,
                    chars=g.chars, lengths=g.lengths))
        pos = k
        for a, nbuf in zip(self.aggregates, self._buffer_widths()):
            bufs = [batch.columns[pos + i] for i in range(nbuf)]
            fields = [schema.fields[pos + i] for i in range(nbuf)]
            pos += nbuf
            out_cols.extend(self._eval_merge(
                a, bufs, fields, perm, seg, mask_sorted, cap, group_valid,
                nseg))
        return tuple(out_cols), (ngroups.astype(jnp.int32)
                                 if key_cols else jnp.int32(1))

    def _buffer_widths(self) -> List[int]:
        return [len(MOMENT_BUFFERS[a.func]) if a.func in MOMENT_BUFFERS
                else (2 if a.func == "avg" else 1)
                for a in self.aggregates]

    def _eval_merge(self, a, bufs, fields, perm, seg, mask_sorted, cap,
                    group_valid, nseg) -> List[DeviceColumn]:
        """Merge semantics per aggregate: sum->sum, count->sum, min->min,
        max->max, first->first, last->last, avg(sum,count)->(sum,sum)."""
        func = ("count" if a.func in ("count_star", "count_if")
                else a.func)
        if func == "any_value":
            func = "first"
        if func in ("bool_and", "bool_or"):
            func = "min" if func == "bool_and" else "max"
        if func in VARIANCE_FUNCS:
            cn, ca, cm = (c if perm is None else _gather_col(c, perm)
                          for c in bufs)
            ntot, nz, mean, m2tot = _chan_merge(cn, ca, cm, mask_sorted,
                                                seg, nseg)
            fn_, fa, fm = fields
            return [
                DeviceColumn(fn_.dataType, group_valid, data=ntot),
                DeviceColumn(fa.dataType, group_valid & nz, data=mean),
                DeviceColumn(fm.dataType, group_valid & nz, data=m2tot),
            ]
        if func in HIGHER_MOMENT_FUNCS:
            cs = [c if perm is None else _gather_col(c, perm) for c in bufs]
            merged = _merge_moment_bufs(cs, mask_sorted, seg, nseg)
            ntot, nz = merged[0], merged[1]
            out = [DeviceColumn(fields[0].dataType, group_valid, data=ntot)]
            for f, arr in zip(fields[1:], merged[2:]):
                out.append(DeviceColumn(f.dataType, group_valid & nz,
                                        data=arr))
            return out
        if func in COVARIANCE_FUNCS or func in PN_REGR_FUNCS:
            cs = [c if perm is None else _gather_col(c, perm) for c in bufs]
            merged = _merge_cov_bufs(cs, mask_sorted, seg, nseg)
            ntot, nz = merged[0], merged[1]
            out = [DeviceColumn(fields[0].dataType, group_valid, data=ntot)]
            for f, arr in zip(fields[1:], merged[2:]):
                out.append(DeviceColumn(f.dataType, group_valid & nz,
                                        data=arr))
            return out
        if a.func == "approx_count_distinct":
            c = bufs[0] if perm is None else _gather_col(bufs[0], perm)
            ok = c.validity & mask_sorted
            m = c.ewidth
            seg_safe = jnp.where(ok, seg, nseg)
            regs = jnp.zeros((nseg, m), jnp.int32).at[seg_safe].max(
                c.data.astype(jnp.int32), mode="drop")
            lengths = jnp.full(nseg, m, jnp.int32)
            ev = jnp.ones((nseg, m), jnp.bool_)
            return [DeviceColumn(fields[0].dataType, group_valid, data=regs,
                                 lengths=lengths, elem_valid=ev)]
        out = []
        for f, c in zip(fields, bufs):
            cs = c if perm is None else _gather_col(c, perm)
            validity = cs.validity & mask_sorted
            if (func in ("sum", "avg") and isinstance(f.dataType, T.DecimalType)
                    and (f.dataType.is_128 or cs.is_dec128)):
                out.append(_sum_dec128(cs, validity, seg, nseg, group_valid,
                                       f.dataType))
                continue
            if func in ("min", "max") and cs.is_dec128:
                out.append(_minmax_dec128(cs, func, seg, validity, nseg,
                                          group_valid, f))
                continue
            if func in ("sum", "count", "avg"):
                s, has = SEG.seg_sum(
                    cs.data.astype(jnp.float64)
                    if _is_float(f.dataType) else cs.data.astype(jnp.int64),
                    validity, seg, nseg)
                if func == "count" or f.name.endswith("_count"):
                    out.append(DeviceColumn(
                        f.dataType, group_valid,
                        data=s.astype(T.storage_dtype(f.dataType))))
                else:
                    out.append(DeviceColumn(
                        f.dataType, group_valid & has,
                        data=s.astype(T.storage_dtype(f.dataType))))
            elif func in ("min", "max"):
                if cs.is_string:
                    out.append(self._minmax_string(
                        cs, func, seg, validity, cap, group_valid, f, nseg))
                else:
                    fn = SEG.seg_min if func == "min" else SEG.seg_max
                    m, has = fn(cs.data, validity, seg, nseg,
                                _is_float(f.dataType))
                    out.append(DeviceColumn(
                        f.dataType, group_valid & has,
                        data=m.astype(T.storage_dtype(f.dataType))
                        if not isinstance(f.dataType, T.BooleanType) else m))
            elif func in ("first", "last"):
                idx_fn = (SEG.seg_first_index if func == "first"
                          else _seg_last_index)
                idx = idx_fn(seg, mask_sorted, nseg)
                g = _gather_col(cs, jnp.clip(idx, 0, cap - 1))
                out.append(DeviceColumn(f.dataType, g.validity & group_valid,
                                        data=g.data, chars=g.chars,
                                        lengths=g.lengths))
            elif func in ("bit_and", "bit_or", "bit_xor"):
                op = {"bit_and": (lambda x, y: x & y, -1),
                      "bit_or": (lambda x, y: x | y, 0),
                      "bit_xor": (lambda x, y: x ^ y, 0)}[func]
                m, has = SEG.seg_fold(cs.data, validity, seg, nseg,
                                      op[0], op[1])
                out.append(DeviceColumn(
                    f.dataType, group_valid & has,
                    data=m.astype(T.storage_dtype(f.dataType))))
            else:
                raise NotImplementedError(f"merge for {func}")
        return out

    def _global_agg_empty(self) -> ColumnarBatch:
        """Zero input batches, no grouping keys -> one row of initial agg
        values, in buffer form for PARTIAL (so multi-wide avg/variance
        buffers stay aligned with the declared schema)."""
        cols = []
        for a, fields in zip(self.aggregates, self._agg_fields()):
            for fi, f in enumerate(fields):
                # position within the buffer group decides the initial
                # value: counts start at valid 0, everything else NULL
                if a.func in ("count", "count_star"):
                    zero_valued = True
                elif a.func == "avg" and len(fields) == 2:
                    zero_valued = fi == 1  # (sum, count)
                elif a.func in VARIANCE_FUNCS and len(fields) == 3:
                    zero_valued = fi == 0  # (n, avg, m2)
                else:
                    zero_valued = False
                shape = ((1, 2) if isinstance(f.dataType, T.DecimalType)
                         and f.dataType.is_128 else (1,))
                if zero_valued:
                    cols.append(DeviceColumn(
                        f.dataType, jnp.ones(1, jnp.bool_),
                        data=jnp.zeros(shape, T.storage_dtype(f.dataType))))
                elif isinstance(f.dataType, T.StringType):
                    cols.append(DeviceColumn(
                        f.dataType, jnp.zeros(1, jnp.bool_),
                        chars=jnp.zeros((1, 8), jnp.uint8),
                        lengths=jnp.zeros(1, jnp.int32)))
                else:
                    cols.append(DeviceColumn(
                        f.dataType, jnp.zeros(1, jnp.bool_),
                        data=jnp.zeros(shape, T.storage_dtype(f.dataType))))
        return ColumnarBatch(cols, 1, self._output)

    # ------------------------------------------------------------------
    def _aggregate_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        if self._has_collect:
            # array output width must be static: pre-pass for the largest
            # group's row count, bucketed (jit cached per bucket)
            from spark_rapids_tpu.columnar.column import (
                DEFAULT_WIDTH_BUCKETS,
                round_up_bucket,
            )

            if getattr(self, "_maxgrp_jit", None) is None:
                self._maxgrp_jit = tpu_jit(self._max_group_rows_fn)
            mx = int(self._maxgrp_jit(tuple(batch.columns),
                                      jnp.int32(batch.num_rows)))
            self._collect_ewidth = round_up_bucket(
                max(mx, 1), DEFAULT_WIDTH_BUCKETS)
            cache = getattr(self, "_collect_jits", None)
            if cache is None:
                cache = self._collect_jits = {}
            if self._collect_ewidth not in cache:
                cache[self._collect_ewidth] = tpu_jit(self._agg_fn)
            jitted = cache[self._collect_ewidth]
            cols, nrows = jitted(tuple(batch.columns),
                                 jnp.int32(batch.num_rows))
            n = 1 if not self.grouping else int(nrows)
            return ColumnarBatch(list(cols), n, self._output)
        args = (tuple(batch.columns), jnp.int32(batch.num_rows))
        B = self._bounded_groups_cap(batch.capacity)
        if B:
            # bounded-cardinality ladder: run the
            # B-wide boundary-form program; the output row count (synced
            # anyway) doubles as the overflow check, growing B to the
            # next power of two when the data has more groups
            cols, nrows = self._agg_jit(B)(*args)
            n = int(nrows)
            while n > B:
                B2 = min(max(1 << (n - 1).bit_length(), B * 2),
                         batch.capacity)
                self._groups_cap_hint = B2
                bump("agg_groups_cap_regrows")
                if B2 >= batch.capacity:
                    self._launch_full_width(batch.capacity)
                    cols, nrows = self._agg_jit(None)(*args)
                    n = int(nrows)
                    break
                cols, nrows = self._agg_jit(B2)(*args)
                n = int(nrows)
                B = B2
            return ColumnarBatch(list(cols), n, self._output)
        self._launch_full_width(batch.capacity)
        cols, nrows = self._agg_jit(None)(*args)
        n = 1 if not self.grouping else int(nrows)
        return ColumnarBatch(list(cols), n, self._output)

    def _agg_program(self, groups_cap=None):
        """(registry key parts, factory) for the aggregation program at
        one groups-cap rung — shared by runtime and AOT enumeration."""
        fpp = self._program_fp()
        key_parts = None if fpp is None else fpp + ("aggfn", groups_cap)

        def factory():
            # detached clone: registry entries outlive the query and must
            # not pin the input subtree through the bound method
            clone = self.detached_for_trace()
            if groups_cap is None:
                return tpu_jit(clone._agg_fn), None

            def fn(cols, num_rows, _b=groups_cap):
                return clone._agg_fn(cols, num_rows, groups_cap=_b)

            return tpu_jit(fn, "agg_groups_cap"), None

        return key_parts, factory

    def _agg_jit(self, groups_cap=None):
        cache = getattr(self, "_agg_jits", None)
        if cache is None:
            cache = self._agg_jits = {}
        if groups_cap not in cache:
            from spark_rapids_tpu.compilecache.registry import (
                cached_program,
            )

            key_parts, factory = self._agg_program(groups_cap)
            cache[groups_cap] = cached_program(
                key_parts, factory,
                label=f"agg:{self.describe()[:40]}").jitted
        return cache[groups_cap]

    # -- plan-time AOT enumeration (compilecache/aot.py) -----------------
    def aot_output_caps(self):
        """Output capacity is predictable even though the group COUNT is
        not: the bounded-groups ladder emits B-capacity batches on its
        first rung, the full-width path keeps the input capacity — this
        is what lets a window/sort ABOVE an aggregate enumerate its
        program at plan time."""
        if self._has_collect:
            return None
        in_caps = self.aot_input_caps()
        if not in_caps:
            return None
        out = set()
        for c in in_caps:
            B = self._bounded_groups_cap(c)
            out.add(B if B else c)
        return sorted(out)

    def aot_emits_single_batch(self):
        # streaming/COMPLETE merge down to one output batch; PARTIAL
        # emits one buffer batch per input batch
        return self.mode != AggregateMode.PARTIAL

    def aot_programs(self):
        from spark_rapids_tpu.compilecache.aot import (
            AotProgram,
            dummy_batch_args,
        )

        if self._has_collect:
            return []
        caps = self.aot_input_caps()
        if not caps:
            return []
        if self.mode == AggregateMode.COMPLETE \
                and not self.aot_child_single_batch():
            # multi-batch COMPLETE runs through the two-phase twins, not
            # this node's fused program
            return []
        if self.mode == AggregateMode.FINAL:
            return []  # consumes data-dependent buffer rows
        schema = self.input_schema
        out = []
        for B in {self._bounded_groups_cap(c) for c in caps}:
            key_parts, factory = self._agg_program(B)
            # only the capacities whose ladder rung IS this B — the
            # runtime pairs each batch capacity with exactly its rung, so
            # warming the (B x capacity) cross-product would burn pool
            # time on specializations nothing ever dispatches
            b_caps = tuple(c for c in caps
                           if self._bounded_groups_cap(c) == B)

            def args_factory(_caps=b_caps):
                return [dummy_batch_args(schema, c) for c in _caps]

            out.append(AotProgram(
                key_parts, factory, args_factory,
                f"agg:{self.describe()[:48]}"))
        return out

    def _bounded_groups_cap(self, cap: int):
        """The groups-cap ladder rung for this batch, or None when the
        bounded path does not apply (no grouping / collect aggs / conf
        off / batch small enough that full width is already cheap)."""
        if not self.grouping or self._has_collect:
            return None
        from spark_rapids_tpu.config import AGG_SMALL_GROUPS_CAP, get_conf

        B = get_conf().get(AGG_SMALL_GROUPS_CAP)
        if not B:
            return None
        B = max(B, getattr(self, "_groups_cap_hint", 0))
        return B if B < cap else None

    def _ends_form(self, cap: int) -> bool:
        """Whether the full-width grouped program at capacity ``cap``
        takes the end-row form (``SegEnds``, ``_compact_ends``): above the
        ladder's first rung, below which full width is already cheap (at
        every capacity with the ladder off), and every aggregate an
        integer or decimal sum, average or count.  Float sums, min/max,
        first/last, folds and the rest keep the scatter program whole."""
        if not self.grouping or self._has_collect:
            return False
        if self._ends_above and cap <= self._ends_above:
            return False
        exact = (T.IntegralType, T.DecimalType)
        for a, fields in zip(self.aggregates, self._agg_fields()):
            if a.func in ("count", "count_star", "count_if"):
                continue
            if a.func not in ("sum", "avg"):
                return False
            if self.mode == AggregateMode.FINAL:
                sch = self.child_schema
                name = a.result_name + ("_sum" if a.func == "avg" else "")
                in_dt = sch.fields[sch.field_names().index(name)].dataType
            elif a.child is None:
                return False
            else:
                in_dt = a.child.dataType
            # an average divides its exact sum row by row: its result may
            # be a double, its partial sum buffer may not
            out_ok = (a.func == "avg" and self.mode != AggregateMode.PARTIAL
                      or isinstance(fields[0].dataType, exact))
            if not (isinstance(in_dt, exact) and out_ok):
                return False
        return True

    def _launch_full_width(self, cap: int) -> None:
        """Books a launch of this node's full-width grouped program at
        capacity ``cap``: ``seg=ends|scatter`` in describe(), and the
        counter ``agg_segment_compactions`` where it took the end-row
        form."""
        if not self.grouping:
            return
        ends = self._ends_form(cap)
        self._seg_form = "ends" if ends else "scatter"
        if ends:
            bump("agg_segment_compactions")

    def _max_group_rows_fn(self, cols, num_rows):
        """Largest per-group row count (the collect array width bound)."""
        batch = ColumnarBatch(list(cols), num_rows, self.input_schema)
        ctx = EvalContext(batch, ansi=self.ansi)
        mask = batch.row_mask
        for op in self.pre_ops:
            batch, mask = op.apply_masked(ctx, batch, mask)
        ctx.batch = batch
        key_cols = [g.eval_tpu(ctx) for g in self.grouping]
        if not key_cols:
            return jnp.sum(mask.astype(jnp.int32))
        cap = batch.capacity
        keys: List[jax.Array] = []
        hi = jnp.int64(9223372036854775807)
        for kc in key_cols:
            nullk = jnp.where(kc.validity, 0, -1).astype(jnp.int64)
            keys.append(jnp.where(mask, nullk, hi))
            for w in _column_key_words(kc):
                keys.append(jnp.where(mask, jnp.where(kc.validity, w, 0), hi))
        sorted_keys = jax.lax.sort(tuple(keys), num_keys=len(keys))
        mask_sorted = jnp.sort(~mask)  # row_mask sorted: valid first
        seg, _ = group_segments(list(sorted_keys), ~mask_sorted)
        seg = jnp.where(~mask_sorted, seg, cap - 1)
        cnt = jax.ops.segment_sum((~mask_sorted).astype(jnp.int32), seg,
                                  num_segments=cap)
        return jnp.max(cnt)

    def _agg_fn(self, cols, num_rows, row_valid=None, groups_cap=None):
        batch = ColumnarBatch(list(cols), num_rows, self.input_schema)
        ctx = EvalContext(batch, ansi=self.ansi)
        mask = batch.row_mask
        if row_valid is not None:
            # mesh execution: rows received over the ICI all-to-all carry an
            # explicit occupancy mask instead of a dense [0, num_rows) prefix
            mask = mask & row_valid
        for op in self.pre_ops:
            batch, mask = op.apply_masked(ctx, batch, mask)
        ctx.batch = batch
        key_cols = [g.eval_tpu(ctx) for g in self.grouping]
        if not key_cols:
            return self._global_agg(ctx, batch, mask)
        cap = batch.capacity
        # ---- sort rows by group keys (stable, padding last) ----
        keys: List[jax.Array] = []
        key_at = []         # each key column's null word in ``keys``
        hi = jnp.int64(9223372036854775807)
        for kc in key_cols:
            key_at.append(len(keys))
            nullk = jnp.where(kc.validity, 0, -1).astype(jnp.int64)
            keys.append(jnp.where(mask, nullk, hi))
            for w in _column_key_words(kc):
                keys.append(jnp.where(mask, jnp.where(kc.validity, w, 0), hi))
        # CO-SORT the aggregate-input payloads with the keys: one fused
        # sorting network moves the data, replacing one full-width random
        # gather PER INPUT (each ~380ms at 20M rows on v5e — round-5
        # calibration) with a small per-operand sort cost
        payload = self._presortable_inputs(ctx)
        carried = self._carried_refs(ctx, payload)
        extra_ops: List[jax.Array] = []
        layout = []
        for pk, c, arrs in (payload if carried is None else carried):
            layout.append((pk, c, len(arrs)))
            extra_ops.extend(arrs)
        iota = jnp.arange(cap, dtype=jnp.int32)
        sorted_all = jax.lax.sort(
            tuple(keys) + (iota, mask) + tuple(extra_ops),
            num_keys=len(keys), is_stable=True)
        nk = len(keys)
        sorted_keys = list(sorted_all[:nk])
        perm = sorted_all[nk]
        mask_sorted = sorted_all[nk + 1]
        rest = sorted_all[nk + 2:]
        self._presorted = {}
        pos = 0
        for pk, c, k in layout:
            self._presorted[pk] = _rebuild_flat_col(c, rest[pos:pos + k])
            pos += k
        if carried is not None:
            # the inputs, evaluated on the columns they read in sorted order
            cols = list(batch.columns)
            for (_, o), c in self._presorted.items():
                cols[o] = c
            sctx = EvalContext(
                ColumnarBatch(cols, batch.num_rows, batch.schema),
                ansi=self.ansi)
            self._presorted = {pk: c for pk, c, _ in
                               self._presortable_inputs(sctx)}
        try:
            seg, ngroups = group_segments(sorted_keys, mask_sorted)
            seg = jnp.where(mask_sorted, seg, cap - 1)  # padding -> last
            nseg = cap
            bscope = None
            ends = None
            if groups_cap:
                # bounded-cardinality mode: outputs are
                # groups_cap wide; every SEG primitive in this trace takes
                # the boundary form (no full-width scatters).  The caller
                # verifies ngroups <= groups_cap from the synced row count
                # and re-runs on the next ladder rung if not.
                nseg = groups_cap
                bscope = SEG.bounds_scope(SEG.SegBounds(seg, nseg))
                bscope.__enter__()
            elif self._ends_form(cap):
                # end-row form: every output is formed at its group's last
                # row, then one sort moves the end rows to their slots
                ends = SEG.SegEnds(seg, mask_sorted)
                bscope = SEG.bounds_scope(ends)
                bscope.__enter__()
            try:
                # ---- group-key output columns ----
                out_cols: List[DeviceColumn] = []
                if ends is not None:
                    # a group's rows are all valid: its end row's key is
                    # the group's
                    group_valid = ends.is_end
                    out_cols = [_key_at_rows(kc, sorted_keys, at,
                                             group_valid)
                                for kc, at in zip(key_cols, key_at)]
                else:
                    first_idx = SEG.seg_first_index(seg, mask_sorted, nseg)
                    safe_first = jnp.clip(first_idx, 0, cap - 1)
                    group_valid = jnp.arange(nseg) < ngroups
                    for kc in key_cols:
                        g = _gather_col(kc, perm[safe_first])
                        out_cols.append(DeviceColumn(
                            g.dtype, g.validity & group_valid, data=g.data,
                            chars=g.chars, lengths=g.lengths))
                # ---- aggregates ----
                for a, f in zip(self.aggregates, self._agg_fields()):
                    out_cols.extend(self._eval_agg(
                        a, f, ctx, perm, seg, mask_sorted, cap,
                        group_valid, nseg=nseg))
            finally:
                if bscope is not None:
                    bscope.__exit__()
        finally:
            self._presorted = None
        if ends is not None:
            out_cols = _compact_ends(out_cols, key_cols, seg, ends.is_end,
                                     perm, ngroups)
        return tuple(out_cols), ngroups.astype(jnp.int32)

    _PRESORTABLE_FUNCS = frozenset({
        "sum", "count", "min", "max", "avg", "first", "last",
        "any_value", "bool_and", "bool_or", "bit_and", "bit_or",
        "bit_xor", "count_if"})

    def _presortable_inputs(self, ctx):
        """Aggregate-input columns eligible for key co-sorting, with their
        flat operand arrays.  Strings/nested stay on the gather path."""
        out = []
        for a in self.aggregates:
            if a.func not in self._PRESORTABLE_FUNCS:
                continue
            suffixes = [None]
            if self.mode == AggregateMode.FINAL and a.func == "avg":
                suffixes = ["_sum", "_count"]
            if a.child is None and self.mode != AggregateMode.FINAL:
                continue     # count(*): a constant ones column
            for sfx in suffixes:
                c = self._input_col(a, ctx, None, sfx)
                arrs = _flat_sort_operands(c)
                if arrs is not None:
                    out.append(((a.result_name, sfx), c, arrs))
        return out

    def _carried_refs(self, ctx, payload):
        """The columns ``payload``'s inputs read, as payload entries keyed
        ``(None, ordinal)``, where they are fewer sort operands than the
        inputs themselves (seven ``CASE WHEN flag THEN price END`` read
        one flag word and one price: 4 operands, not 14, and a sort's
        compile time grows with its operands); else None."""
        if self.ansi or self.mode == AggregateMode.FINAL or not payload:
            return None
        from spark_rapids_tpu.plan.pruning import _refs

        names = {pk[0] for pk, _, _ in payload}
        refs = _refs([a.child for a in self.aggregates
                      if a.result_name in names])
        if not refs:
            return None
        cols = ctx.batch.columns
        carried = [((None, o), cols[o], _flat_sort_operands(cols[o]))
                   for o in sorted(refs)]
        if any(arrs is None for _, _, arrs in carried):
            return None
        if (sum(len(arrs) for _, _, arrs in carried)
                >= sum(len(arrs) for _, _, arrs in payload)):
            return None
        return carried

    def _agg_fields(self):
        """Output fields per aggregate (partial avg takes two)."""
        fields = list(self._output.fields[len(self.grouping):])
        out = []
        i = 0
        for a in self.aggregates:
            if a.func == "avg" and self.mode == AggregateMode.PARTIAL:
                out.append((fields[i], fields[i + 1]))
                i += 2
            elif (a.func in MOMENT_BUFFERS
                  and self.mode == AggregateMode.PARTIAL):
                k = len(MOMENT_BUFFERS[a.func])
                out.append(tuple(fields[i:i + k]))
                i += k
            else:
                out.append((fields[i],))
                i += 1
        return out

    # -- per-aggregate evaluation --------------------------------------
    def _input_col(self, a: AggregateExpression, ctx, perm,
                   suffix: Optional[str] = None):
        """Column holding this aggregate's input (already sorted via perm).

        When the enclosing _agg_fn co-sorted this input with the keys the
        presorted column comes back directly — no gather."""
        pres = getattr(self, "_presorted", None)
        if perm is not None and pres is not None:
            hit = pres.get((a.result_name, suffix))
            if hit is not None:
                return hit
        if self.mode == AggregateMode.FINAL:
            # inputs are the partial buffers by position in child schema
            name = a.result_name + (suffix or "")
            names = self.child_schema.field_names()
            ord_ = names.index(name)
            c = ctx.batch.columns[ord_]
        else:
            if a.child is None:
                c = DeviceColumn(T.LONG,
                                 jnp.ones(ctx.batch.capacity, jnp.bool_),
                                 data=jnp.ones(ctx.batch.capacity, jnp.int64))
            else:
                c = a.child.eval_tpu(ctx)
        return c if perm is None else _gather_col(c, perm)

    def _eval_agg(self, a: AggregateExpression, fields, ctx, perm, seg,
                  mask_sorted, cap, group_valid,
                  nseg: int = None) -> List[DeviceColumn]:
        nseg = cap if nseg is None else nseg
        mode = self.mode
        func = a.func
        if func == "count_star":
            func = "count"
        if func == "any_value":
            func = "first"          # Spark AnyValue == First(ignoreNulls=F)
        if func in ("bool_and", "bool_or"):
            func = "min" if func == "bool_and" else "max"
        out = []
        if func in VARIANCE_FUNCS:
            return self._eval_variance(a, fields, ctx, perm, seg, mask_sorted,
                                       cap, group_valid, nseg)
        if func in HIGHER_MOMENT_FUNCS:
            return self._eval_higher_moment(a, fields, ctx, perm, seg,
                                            mask_sorted, cap, group_valid,
                                            nseg)
        if func in COVARIANCE_FUNCS or func in PN_REGR_FUNCS:
            return self._eval_covariance(a, fields, ctx, perm, seg,
                                         mask_sorted, cap, group_valid, nseg)
        if func in ("bit_and", "bit_or", "bit_xor"):
            (f,) = fields
            c = self._input_col(a, ctx, perm)
            validity = c.validity & mask_sorted
            op = {"bit_and": (lambda x, y: x & y, -1),
                  "bit_or": (lambda x, y: x | y, 0),
                  "bit_xor": (lambda x, y: x ^ y, 0)}[func]
            m, has = SEG.seg_fold(c.data, validity, seg, nseg,
                                  op[0], op[1])
            return [DeviceColumn(f.dataType, group_valid & has,
                                 data=m.astype(T.storage_dtype(f.dataType)))]
        if func == "count_if":
            (f,) = fields
            if mode == AggregateMode.FINAL:
                c = self._input_col(a, ctx, perm)
                s, _ = SEG.seg_sum(c.data, c.validity & mask_sorted, seg,
                                   nseg)
                cnt = s
            else:
                c = self._input_col(a, ctx, perm)
                hit = c.validity & mask_sorted & c.data.astype(jnp.bool_)
                cnt = SEG.seg_count(hit, seg, nseg)
            return [DeviceColumn(T.LONG, group_valid, data=cnt)]
        if func == "approx_count_distinct":
            return self._eval_hll(a, fields, ctx, perm, seg, mask_sorted,
                                  cap, group_valid, nseg)
        if func in ("percentile", "approx_percentile", "median"):
            return self._eval_percentile(a, fields, ctx, perm, seg,
                                         mask_sorted, cap, group_valid, nseg)
        if func == "bloom_filter_agg":
            return self._eval_bloom(a, fields, ctx, perm, seg, mask_sorted,
                                    cap, group_valid, nseg)
        if func == "avg":
            sum_dt = (fields[0].dataType if mode == AggregateMode.PARTIAL
                      else (self.child_schema.fields[
                          self.child_schema.field_names().index(
                              a.result_name + "_sum")].dataType
                          if mode == AggregateMode.FINAL else None))
            dec_in = (a.child is not None
                      and isinstance(a.child.dataType, T.DecimalType)) \
                if mode != AggregateMode.FINAL else isinstance(
                    sum_dt, T.DecimalType)
            buf128 = (isinstance(sum_dt, T.DecimalType) and sum_dt.is_128) \
                if sum_dt is not None else (
                    dec_in and a.child.dataType.precision + 10 > 18)
            if mode == AggregateMode.PARTIAL:
                c = self._input_col(a, ctx, perm)
                sum_f, cnt_f = fields
                validity = c.validity & mask_sorted
                if buf128:
                    out.append(_sum_dec128(c, validity, seg, nseg,
                                           group_valid, sum_f.dataType))
                else:
                    s, has = SEG.seg_sum(_sum_input(c, sum_f.dataType),
                                         validity, seg, nseg)
                    out.append(DeviceColumn(sum_f.dataType, group_valid & has,
                                            data=s))
                cnt = SEG.seg_count(validity, seg, nseg)
                out.append(DeviceColumn(cnt_f.dataType, group_valid, data=cnt))
                return out
            (f,) = fields
            if mode == AggregateMode.FINAL:
                cs = self._input_col(a, ctx, perm, "_sum")
                cc = self._input_col(a, ctx, perm, "_count")
                n, _ = SEG.seg_sum(cc.data, cc.validity & mask_sorted, seg,
                                   nseg)
                if buf128:
                    scol = _sum_dec128(cs, cs.validity & mask_sorted, seg,
                                       nseg, group_valid, sum_dt)
                    return [_avg_div_dec128(scol, n, sum_dt.scale,
                                            f.dataType, group_valid)]
                s, _ = SEG.seg_sum(cs.data, cs.validity & mask_sorted, seg, nseg)
            else:
                c = self._input_col(a, ctx, perm)
                validity = c.validity & mask_sorted
                n = SEG.seg_count(validity, seg, nseg)
                if buf128:
                    buf_dt = T.DecimalType(
                        min(a.child.dataType.precision + 10, 38),
                        a.child.dataType.scale)
                    scol = _sum_dec128(c, validity, seg, nseg, group_valid,
                                       buf_dt)
                    return [_avg_div_dec128(scol, n, buf_dt.scale,
                                            f.dataType, group_valid)]
                s, _ = SEG.seg_sum(_sum_input(c, None), validity, seg, nseg)
            nz = n > 0
            if isinstance(f.dataType, T.DecimalType):
                in_scale = (a.child.dataType.scale
                            if a.child is not None else 0)
                shift = f.dataType.scale - in_scale
                num = s * (10 ** min(max(shift, 0), 18))
                den = jnp.where(nz, n, 1)
                q = num // den
                rem = num - q * den
                q = q + jnp.where((rem != 0) & (num < 0), 1, 0)
                rem2 = num - q * den
                half_up = (jnp.abs(rem2) * 2 >= den) & (rem2 != 0)
                q = q + jnp.where(half_up, jnp.sign(num), 0)
                out.append(DeviceColumn(f.dataType, group_valid & nz, data=q))
            else:
                avg = s.astype(jnp.float64) / jnp.where(nz, n, 1)
                out.append(DeviceColumn(T.DOUBLE, group_valid & nz, data=avg))
            return out
        (f,) = fields
        if func == "count":
            c = self._input_col(a, ctx, perm)
            if mode == AggregateMode.FINAL:
                s, _ = SEG.seg_sum(c.data, c.validity & mask_sorted, seg, nseg)
                cnt = s
            else:
                cnt = SEG.seg_count(c.validity & mask_sorted, seg, nseg)
            out.append(DeviceColumn(T.LONG, group_valid, data=cnt))
            return out
        if func in ("collect_list", "collect_set"):
            c = self._input_col(a, ctx, perm)
            return [self._eval_collect(a, fields[0], c,
                                       c.validity & mask_sorted, seg,
                                       mask_sorted, cap, group_valid, nseg)]
        c = self._input_col(a, ctx, perm)
        validity = c.validity & mask_sorted
        if func == "sum":
            if (isinstance(f.dataType, T.DecimalType)
                    and (f.dataType.is_128 or c.is_dec128)):
                out.append(_sum_dec128(c, validity, seg, nseg, group_valid,
                                       f.dataType))
                return out
            s, has = SEG.seg_sum(_sum_input(c, f.dataType), validity, seg, nseg)
            out.append(DeviceColumn(f.dataType, group_valid & has,
                                    data=s.astype(T.storage_dtype(f.dataType))))
            return out
        if func in ("min", "max"):
            isf = _is_float(f.dataType)
            if c.is_string:
                return [self._minmax_string(c, func, seg, validity, cap,
                                            group_valid, f, nseg)]
            if c.is_dec128:
                return [_minmax_dec128(c, func, seg, validity, nseg,
                                       group_valid, f)]
            fn = SEG.seg_min if func == "min" else SEG.seg_max
            m, has = fn(c.data, validity, seg, nseg, isf)
            out.append(DeviceColumn(f.dataType, group_valid & has,
                                    data=m.astype(T.storage_dtype(f.dataType))
                                    if not isinstance(f.dataType, T.BooleanType)
                                    else m))
            return out
        if func in ("first", "last"):
            idx_fn = SEG.seg_first_index if func == "first" else _seg_last_index
            idx = idx_fn(seg, mask_sorted, nseg)
            g = _gather_col(c, jnp.clip(idx, 0, cap - 1))
            out.append(DeviceColumn(f.dataType, g.validity & group_valid,
                                    data=g.data, chars=g.chars,
                                    lengths=g.lengths))
            return out
        raise NotImplementedError(f"aggregate {func}")

    def _eval_variance(self, a, fields, ctx, perm, seg, mask_sorted, cap,
                       group_valid, nseg) -> List[DeviceColumn]:
        """Central moments (n, avg, m2).  PARTIAL emits the buffer triple;
        FINAL Chan-merges child buffers and finalizes; COMPLETE does both.
        Matches Spark's CentralMomentAgg: n==0 -> NULL, samp with n==1 ->
        NULL (default nullOnDivideByZero)."""
        if self.mode == AggregateMode.FINAL:
            cn = self._input_col(a, ctx, perm, "_n")
            ca = self._input_col(a, ctx, perm, "_avg")
            cm = self._input_col(a, ctx, perm, "_m2")
            ntot, nz, mean, m2 = _chan_merge(cn, ca, cm, mask_sorted, seg,
                                             nseg)
        else:
            c = self._input_col(a, ctx, perm)
            valid = c.validity & mask_sorted
            x = jnp.where(valid, c.data.astype(jnp.float64), 0.0)
            if isinstance(c.dtype, T.DecimalType):
                # unscaled storage -> numeric value (Spark casts to double)
                x = x * jnp.float64(10.0 ** -c.dtype.scale)
            ntot = SEG.seg_count(valid, seg, nseg).astype(jnp.float64)
            s, _ = SEG.seg_sum(x, valid, seg, nseg)
            nz = ntot > 0
            mean = s / jnp.where(nz, ntot, 1.0)
            d = jnp.where(valid, x - mean[seg], 0.0)
            m2, _ = SEG.seg_sum(d * d, valid, seg, nseg)
        if self.mode == AggregateMode.PARTIAL:
            fn_, fa, fm = fields
            return [
                DeviceColumn(fn_.dataType, group_valid, data=ntot),
                DeviceColumn(fa.dataType, group_valid & nz, data=mean),
                DeviceColumn(fm.dataType, group_valid & nz, data=m2),
            ]
        (f,) = fields
        pop = a.func.endswith("_pop")
        den = ntot if pop else ntot - 1.0
        # Spark 3.1+ default nullOnDivideByZero: samp with n==1 -> NULL
        ok = den > 0.0
        var = m2 / jnp.where(ok, den, 1.0)
        res = var if a.func.startswith("var") else jnp.sqrt(var)
        return [DeviceColumn(f.dataType, group_valid & nz & ok, data=res)]

    def _numeric_f64(self, c: DeviceColumn) -> jax.Array:
        x = c.data.astype(jnp.float64)
        if isinstance(c.dtype, T.DecimalType):
            x = x * jnp.float64(10.0 ** -c.dtype.scale)
        return x

    def _eval_higher_moment(self, a, fields, ctx, perm, seg, mask_sorted,
                            cap, group_valid, nseg) -> List[DeviceColumn]:
        """skewness / kurtosis: central moments up to m3/m4.

        Reference analog: Spark Skewness/Kurtosis (CentralMomentAgg with
        momentOrder 3/4), GPU'd in org/apache/spark/sql/rapids/aggregate.
        Merging uses the closed forms m3 = Σm3_i + 3Σm2_i·d_i + Σn_i·d_i³
        (and the order-4 analog), which are plain segmented sums — no
        sequential pairwise Chan recursion needed."""
        want_m4 = a.func == "kurtosis"
        if self.mode == AggregateMode.FINAL:
            from spark_rapids_tpu.plan.nodes import MOMENT_BUFFERS as _MB

            bufs = [self._input_col(a, ctx, perm, s)
                    for s in _MB[a.func]]
            merged = _merge_moment_bufs(bufs, mask_sorted, seg, nseg)
            if want_m4:
                ntot, nz, mean, m2, m3, m4 = merged
            else:
                ntot, nz, mean, m2, m3 = merged
        else:
            c = self._input_col(a, ctx, perm)
            valid = c.validity & mask_sorted
            x = jnp.where(valid, self._numeric_f64(c), 0.0)
            ntot = SEG.seg_count(valid, seg, nseg).astype(jnp.float64)
            s, _ = SEG.seg_sum(x, valid, seg, nseg)
            nz = ntot > 0
            mean = s / jnp.where(nz, ntot, 1.0)
            d = jnp.where(valid, x - mean[seg], 0.0)
            m2, _ = SEG.seg_sum(d * d, valid, seg, nseg)
            m3, _ = SEG.seg_sum(d ** 3, valid, seg, nseg)
            if want_m4:
                m4, _ = SEG.seg_sum(d ** 4, valid, seg, nseg)
        if self.mode == AggregateMode.PARTIAL:
            cols = [ntot, mean, m2, m3] + ([m4] if want_m4 else [])
            out = [DeviceColumn(fields[0].dataType, group_valid, data=ntot)]
            for f, arr in zip(fields[1:], cols[1:]):
                out.append(DeviceColumn(f.dataType, group_valid & nz,
                                        data=arr))
            return out
        (f,) = fields
        # Spark nullOnDivideByZero: m2 == 0 (or empty) -> NULL
        ok_res = nz & (m2 != 0.0)
        safe_m2 = jnp.where(ok_res, m2, 1.0)
        if want_m4:
            res = ntot * m4 / (safe_m2 * safe_m2) - 3.0
        else:
            res = jnp.sqrt(ntot) * m3 / jnp.power(safe_m2, 1.5)
        return [DeviceColumn(f.dataType, group_valid & ok_res, data=res)]

    def _eval_covariance(self, a, fields, ctx, perm, seg, mask_sorted, cap,
                         group_valid, nseg) -> List[DeviceColumn]:
        """covar_pop / covar_samp / corr — Spark Covariance/Corr buffers
        (n, xAvg, yAvg, ck [, xMk, yMk]); rows count only when BOTH inputs
        are non-null."""
        is_regr = a.func in PN_REGR_FUNCS
        is_corr = a.func == "corr" or is_regr   # 6-channel buffers
        if self.mode == AggregateMode.FINAL:
            from spark_rapids_tpu.plan.nodes import MOMENT_BUFFERS as _MB

            bufs = [self._input_col(a, ctx, perm, s)
                    for s in _MB[a.func]]
            merged = _merge_cov_bufs(bufs, mask_sorted, seg, nseg)
            if is_corr:
                ntot, nz, xavg, yavg, ck, xm2, ym2 = merged
            else:
                ntot, nz, xavg, yavg, ck = merged
        else:
            # regr_f(y, x): the DEPENDENT y is the first argument; the
            # covariance stats' x must be the independent (second)
            x_expr = a.child2 if is_regr else a.child
            y_expr = a.child if is_regr else a.child2
            x_col = x_expr.eval_tpu(ctx)
            y_col = y_expr.eval_tpu(ctx)
            if perm is not None:
                x_col = _gather_col(x_col, perm)
                y_col = _gather_col(y_col, perm)
            valid = x_col.validity & y_col.validity & mask_sorted
            x = jnp.where(valid, self._numeric_f64(x_col), 0.0)
            y = jnp.where(valid, self._numeric_f64(y_col), 0.0)
            ntot = SEG.seg_count(valid, seg, nseg).astype(jnp.float64)
            nz = ntot > 0
            sx, _ = SEG.seg_sum(x, valid, seg, nseg)
            sy, _ = SEG.seg_sum(y, valid, seg, nseg)
            xavg = sx / jnp.where(nz, ntot, 1.0)
            yavg = sy / jnp.where(nz, ntot, 1.0)
            dx = jnp.where(valid, x - xavg[seg], 0.0)
            dy = jnp.where(valid, y - yavg[seg], 0.0)
            ck, _ = SEG.seg_sum(dx * dy, valid, seg, nseg)
            if is_corr:
                xm2, _ = SEG.seg_sum(dx * dx, valid, seg, nseg)
                ym2, _ = SEG.seg_sum(dy * dy, valid, seg, nseg)
        if self.mode == AggregateMode.PARTIAL:
            bufs = [ntot, xavg, yavg, ck] + ([xm2, ym2] if is_corr else [])
            out = [DeviceColumn(fields[0].dataType, group_valid, data=ntot)]
            for f, arr in zip(fields[1:], bufs[1:]):
                out.append(DeviceColumn(f.dataType, group_valid & nz,
                                        data=arr))
            return out
        (f,) = fields
        if is_regr:
            func = a.func
            if func == "regr_count":
                return [DeviceColumn(T.LONG, group_valid,
                                     data=jnp.where(
                                         group_valid, ntot, 0.0).astype(
                                         jnp.int64))]
            if func == "regr_avgx":
                return [DeviceColumn(f.dataType, group_valid & nz,
                                     data=xavg)]
            if func == "regr_avgy":
                return [DeviceColumn(f.dataType, group_valid & nz,
                                     data=yavg)]
            if func == "regr_sxx":
                return [DeviceColumn(f.dataType, group_valid & nz,
                                     data=xm2)]
            if func == "regr_syy":
                return [DeviceColumn(f.dataType, group_valid & nz,
                                     data=ym2)]
            if func == "regr_sxy":
                return [DeviceColumn(f.dataType, group_valid & nz,
                                     data=ck)]
            ok = nz & (xm2 != 0.0)
            slope = ck / jnp.where(xm2 != 0.0, xm2, 1.0)
            if func == "regr_slope":
                return [DeviceColumn(f.dataType, group_valid & ok,
                                     data=slope)]
            if func == "regr_intercept":
                return [DeviceColumn(f.dataType, group_valid & ok,
                                     data=yavg - slope * xavg)]
            # regr_r2: syy==0 -> 1.0; else ck^2/(sxx*syy)
            r2 = jnp.where(ym2 == 0.0, 1.0,
                           (ck * ck) / jnp.where(
                               (xm2 * ym2) != 0.0, xm2 * ym2, 1.0))
            return [DeviceColumn(f.dataType, group_valid & ok, data=r2)]
        if is_corr:
            # zero variance -> NaN via natural fp division (Spark Corr)
            res = ck / jnp.sqrt(xm2 * ym2)
            return [DeviceColumn(f.dataType, group_valid & nz, data=res)]
        if a.func == "covar_pop":
            res = ck / jnp.where(nz, ntot, 1.0)
            return [DeviceColumn(f.dataType, group_valid & nz, data=res)]
        ok_res = ntot > 1.0
        res = ck / jnp.where(ok_res, ntot - 1.0, 1.0)
        return [DeviceColumn(f.dataType, group_valid & ok_res, data=res)]

    def _eval_hll(self, a, fields, ctx, perm, seg, mask_sorted, cap,
                  group_valid, nseg) -> List[DeviceColumn]:
        """approx_count_distinct — HyperLogLog++ registers per group.

        Reference analog: GpuHyperLogLogPlusPlus (spark-rapids-jni HLL
        sketch, SURVEY.md §2.4).  TPU design: registers live as a padded
        list column (one m-wide int32 row per group), built with one
        scatter-max; partial merge is another scatter-max.  Estimation uses
        the standard HLL++ raw/linear-counting split WITHOUT Spark's
        empirical bias tables (documented TypeSig note)."""
        from spark_rapids_tpu.ops.hashing import xxhash64_column

        p = HLL_DEFAULT_P
        m = 1 << p
        (f,) = fields
        if self.mode == AggregateMode.FINAL:
            c = self._input_col(a, ctx, perm, "_hll")  # list col (cap, m)
            ok = c.validity & mask_sorted
            seg_safe = jnp.where(ok, seg, nseg)
            regs = jnp.zeros((nseg, m), jnp.int32).at[seg_safe].max(
                c.data.astype(jnp.int32), mode="drop")
        else:
            c = self._input_col(a, ctx, perm)
            valid = c.validity & mask_sorted
            h = xxhash64_column(c, jnp.full(cap, jnp.uint64(42)))
            h = h.view(jnp.int64)
            idx = jnp.right_shift(h, 64 - p) & (m - 1)
            w = jnp.left_shift(h, p)
            rank = jnp.minimum(jax.lax.clz(w) + 1, 65 - p).astype(jnp.int32)
            seg_safe = jnp.where(valid, seg, nseg)
            regs = jnp.zeros((nseg, m), jnp.int32).at[
                seg_safe, idx].max(rank, mode="drop")
        if self.mode == AggregateMode.PARTIAL:
            lengths = jnp.full(nseg, m, jnp.int32)
            ev = jnp.ones((nseg, m), jnp.bool_)
            return [DeviceColumn(f.dataType, group_valid, data=regs,
                                 lengths=lengths, elem_valid=ev)]
        alpha = 0.7213 / (1.0 + 1.079 / m)
        inv = jnp.sum(jnp.exp2(-regs.astype(jnp.float64)), axis=1)
        raw = alpha * m * m / inv
        zeros = jnp.sum(regs == 0, axis=1).astype(jnp.float64)
        lin = m * jnp.log(m / jnp.maximum(zeros, 1.0))
        est = jnp.where((raw <= 2.5 * m) & (zeros > 0), lin, raw)
        cnt = jnp.round(est).astype(jnp.int64)
        return [DeviceColumn(T.LONG, group_valid, data=cnt)]

    def _eval_percentile(self, a, fields, ctx, perm, seg, mask_sorted, cap,
                         group_valid, nseg) -> List[DeviceColumn]:
        """percentile (exact, interpolated) / approx_percentile (element at
        floor(p*(n-1)), exact while the group fits in one batch — the GK
        summary is uncompressed below the accuracy threshold, which is the
        same answer).  Single-phase COMPLETE (planned like collect_list)."""
        (f,) = fields
        pct = jnp.float64(0.5 if a.func == "median" else a.args[0])
        c = self._input_col(a, ctx, perm)
        valid = c.validity & mask_sorted
        # sort values within their (already sorted) segments; invalid last
        tier = (~valid).astype(jnp.int32)
        vkey = c.data.astype(jnp.int64) if not _is_float(c.dtype) else None
        if vkey is None:
            from spark_rapids_tpu.ops.sortkeys import _float_total_order

            f64 = c.data.astype(jnp.float64)
            bits = jax.lax.bitcast_convert_type(f64, jnp.int64)
            bits = jnp.where(jnp.isnan(f64),
                             jnp.int64(0x7FF8000000000000), bits)
            vkey = _float_total_order(bits)
        seg_key = jnp.where(mask_sorted, seg, nseg)
        _, _, _, sdata = jax.lax.sort(
            (seg_key.astype(jnp.int32), tier, vkey, c.data),
            dimension=0, num_keys=3, is_stable=True)
        nv = SEG.seg_count(valid, seg, nseg)
        starts = SEG.seg_first_index(seg, mask_sorted, nseg)
        has = nv > 0
        r = pct * (jnp.maximum(nv, 1) - 1).astype(jnp.float64)
        lo = jnp.floor(r).astype(jnp.int64)
        hi = jnp.ceil(r).astype(jnp.int64)
        frac = r - lo.astype(jnp.float64)
        gi_lo = jnp.clip(starts + lo, 0, cap - 1)
        gi_hi = jnp.clip(starts + hi, 0, cap - 1)
        v_lo = sdata[gi_lo]
        v_hi = sdata[gi_hi]
        validity = group_valid & has
        if a.func == "approx_percentile":
            return [DeviceColumn(f.dataType, validity,
                                 data=v_lo.astype(T.storage_dtype(
                                     f.dataType)))]
        scale = (jnp.float64(10.0 ** -c.dtype.scale)
                 if isinstance(c.dtype, T.DecimalType) else jnp.float64(1.0))
        res = (v_lo.astype(jnp.float64) * (1.0 - frac)
               + v_hi.astype(jnp.float64) * frac) * scale
        return [DeviceColumn(T.DOUBLE, validity, data=res)]

    def _eval_bloom(self, a, fields, ctx, perm, seg, mask_sorted, cap,
                    group_valid, nseg) -> List[DeviceColumn]:
        """bloom_filter_agg — the GpuBloomFilterAggregate analog.

        Layout: array<long> of num_bits/64 words (double hashing with
        xxhash64 seeds 42 and 77; NOT byte-compatible with Spark's sketch
        serialization — probed by BloomFilterMightContain with the same
        parameters)."""
        import math as _math

        from spark_rapids_tpu.ops.hashing import xxhash64_column

        (f,) = fields
        num_items, num_bits = int(a.args[0]), int(a.args[1])
        words = num_bits // 64
        k = max(1, round(num_bits / num_items * _math.log(2)))
        c = self._input_col(a, ctx, perm)
        valid = c.validity & mask_sorted
        h1 = xxhash64_column(c, jnp.full(cap, jnp.uint64(42))).view(jnp.int64)
        h2 = xxhash64_column(c, jnp.full(cap, jnp.uint64(77))).view(jnp.int64)
        bits = jnp.zeros((nseg, num_bits), jnp.bool_)
        seg_safe = jnp.where(valid, seg, nseg)
        for j in range(k):
            bit = jnp.remainder(h1 + j * h2, num_bits)
            bits = bits.at[seg_safe, bit].set(True, mode="drop")
        packed = bits.reshape(nseg, words, 64)
        weights = jnp.left_shift(jnp.int64(1), jnp.arange(64, dtype=jnp.int64))
        data = jnp.sum(packed.astype(jnp.int64) * weights[None, None, :],
                       axis=2)
        lengths = jnp.full(nseg, words, jnp.int32)
        ev = jnp.ones((nseg, words), jnp.bool_)
        return [DeviceColumn(f.dataType, group_valid, data=data,
                             lengths=lengths, elem_valid=ev)]

    def _eval_collect(self, a, f, c: DeviceColumn, validity, seg,
                      mask_sorted, cap, group_valid, nseg) -> DeviceColumn:
        """collect_list / collect_set into a padded list column.

        Reference analog: GpuCollectList/GpuCollectSet (SURVEY.md §2.4).
        Nulls are skipped (Spark).  collect_list keeps input order (rows
        are key-sorted STABLY, so within-group order is arrival order);
        collect_set emits values ASCENDING (Spark's set order is
        unspecified; the oracle sorts the same way so differential tests
        are deterministic)."""
        ew = self._collect_ewidth
        if a.func == "collect_set":
            # second sort by (segment, value words) + first-of-run mask
            words = _column_key_words(c)
            keyseq = [seg.astype(jnp.int64),
                      (~validity).astype(jnp.int64)] + \
                     [jnp.where(validity, w, 0) for w in words]
            iota = jnp.arange(cap, dtype=jnp.int32)
            perm2 = jax.lax.sort(tuple(keyseq) + (iota,),
                                 num_keys=len(keyseq), is_stable=True)[-1]
            seg = seg[perm2]
            validity = validity[perm2]
            c = _gather_col(c, perm2)
            words2 = [w[perm2] for w in keyseq[2:]]
            same = jnp.ones(cap, jnp.bool_)
            for w in words2:
                prev = jnp.concatenate([w[:1] - 1, w[:-1]])
                same = same & (w == prev)
            same_seg = jnp.concatenate(
                [jnp.zeros(1, jnp.bool_), seg[1:] == seg[:-1]])
            validity = validity & ~(same & same_seg)
        # within-group rank among VALID rows; for the global (nseg==1)
        # case seg may be unsorted (fused-filter mask), so scan globally
        if nseg == 1:
            starts = jnp.zeros(cap, jnp.bool_).at[0].set(True)
        else:
            starts = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                      seg[1:] != seg[:-1]])
        rank = (SEG.seg_scan_sum(jnp.ones(cap, jnp.int64), validity,
                                 starts)[1] - 1).astype(jnp.int32)
        elem_dt = f.dataType.elementType
        seg_out = seg if nseg != 1 else jnp.zeros(cap, jnp.int32)
        flat_idx = jnp.where(validity & (rank < ew),
                             seg_out.astype(jnp.int64) * ew + rank,
                             cap * ew).astype(jnp.int64)
        sdt = T.storage_dtype(elem_dt)
        data = jnp.zeros(cap * ew, sdt).at[flat_idx].set(
            c.data.astype(sdt), mode="drop")
        ev = jnp.zeros(cap * ew, jnp.bool_).at[flat_idx].set(
            True, mode="drop")
        lengths = jnp.clip(SEG.seg_count(validity, seg, nseg), 0, ew)
        out_rows = int(lengths.shape[0])
        return DeviceColumn(
            f.dataType, group_valid,
            data=data.reshape(cap, ew)[:out_rows],
            lengths=lengths.astype(jnp.int32),
            elem_valid=ev.reshape(cap, ew)[:out_rows])

    def _minmax_string(self, c: DeviceColumn, func, seg, validity, cap,
                       group_valid, f, nseg):
        """min/max on strings: argmin over packed key words per segment."""
        words = _column_key_words(c)
        # build a composite: use first word as primary ordering; resolve ties
        # via iterative refinement is complex — instead sort-based: rows are
        # already sorted by GROUP key, not value; do an argmin via two-pass
        # lexicographic reduction over words.
        n = c.capacity
        best = jnp.arange(n, dtype=jnp.int32)
        # iterative: compute rank by sorting (value words, index) within seg
        # null rows must sort after every valid row: a value-word sentinel
        # can collide with real key words, so nullness is its own sort key
        keyseq = [seg.astype(jnp.int64), (~validity).astype(jnp.int64)]
        for w in words:
            keyseq.append(w if func == "min" else ~w)
        perm2 = jax.lax.sort(tuple(keyseq) + (best,),
                             num_keys=len(keyseq), is_stable=True)[-1]
        # after sort by (seg, value): first row of each seg = min (or max)
        seg_sorted = seg[perm2]
        first = SEG.seg_first_index(seg_sorted, jnp.ones(n, jnp.bool_), nseg)
        take = perm2[jnp.clip(first, 0, n - 1)]
        g = _gather_col(c, take)
        has = jax.ops.segment_sum(validity.astype(jnp.int32), seg,
                                  num_segments=nseg) > 0
        return DeviceColumn(f.dataType, group_valid & has & g.validity,
                            chars=g.chars, lengths=g.lengths)

    # -- global (no grouping keys) -------------------------------------
    def _global_agg(self, ctx, batch, mask=None):
        """No grouping keys: a single-segment reduction (XLA lowers this to
        a plain tree-reduce; no sort, no scatter)."""
        if mask is None:
            mask = batch.row_mask
        perm = None  # no sort needed for a single segment
        seg = jnp.where(mask, 0, 1).astype(jnp.int32)  # padding dropped
        group_valid = jnp.ones(1, jnp.bool_)
        out_cols: List[DeviceColumn] = []
        for a, f in zip(self.aggregates, self._agg_fields()):
            out_cols.extend(self._eval_agg(a, f, ctx, perm, seg, mask,
                                           batch.capacity, group_valid,
                                           nseg=1))
        return tuple(out_cols), jnp.int32(1)


def _sum_input(c: DeviceColumn, out_dtype):
    if _is_float(c.dtype) or (out_dtype is not None and _is_float(out_dtype)):
        return c.data.astype(jnp.float64)
    return c.data.astype(jnp.int64)


def _sum_dec128(c: DeviceColumn, validity, seg, nseg, group_valid,
                dt: T.DecimalType) -> DeviceColumn:
    """sum over a decimal column into a >18-digit result: exact 128-bit limb
    sums; overflow past 10^precision yields NULL (Spark nullOnOverflow).

    Reference analog: GpuSum's DECIMAL128 buffer (GpuAggregateExec.scala) +
    decimal_utils.cu overflow checks."""
    from spark_rapids_tpu.expr import decimal128 as D

    hi, lo = D.column_limbs(c)
    ok, has, sh, sl = D.sum128_segments(hi, lo, validity, seg, nseg)
    ok = ok & D.in_bounds(sh, sl, dt.precision)
    data = D.pack(sh, sl) if dt.is_128 else sl
    return DeviceColumn(dt, group_valid & has & ok, data=data)


def _avg_div_dec128(scol: DeviceColumn, n, in_scale: int,
                    dt: T.DecimalType, group_valid) -> DeviceColumn:
    """Finalize decimal avg from a 128-bit sum buffer: sum/count with
    HALF_UP at the result scale (Spark Average.evaluateExpression).

    Exact integer path: q, r = divmod(|sum|, count); result =
    q*10^shift + round_half_up(r*10^shift / count).  The remainder term
    stays under 2^31 * 10^4 so it fits int64.  The long division's divisor
    contract is d < 2^31; FINAL-mode merged counts could exceed it, so such
    groups yield NULL rather than a silently wrong quotient."""
    from spark_rapids_tpu.expr import decimal128 as D

    sh, sl = D.column_limbs(scol)
    nz = n > 0
    n_ok = n < jnp.int64(2 ** 31)
    d = jnp.where(nz & n_ok, n, 1)
    neg = D.is_neg(sh, sl)
    uh, ul = D.abs128(sh, sl)
    qh, ql, rem = D.udivmod128_by_u32(uh, ul, d)
    shift = dt.scale - in_scale            # in [0, 4]
    over, qh, ql = D.mul128_pow10(qh, ql, shift)
    p10 = 10 ** max(shift, 0)
    num = rem * p10
    eq = num // d
    er = num - eq * d
    eq = eq + ((2 * er) >= d).astype(jnp.int64)
    qh, ql = D.add128(qh, ql, *D.from64(eq))
    ok = D.in_bounds(qh, ql, dt.precision) & ~over
    rh, rl = D.neg128(qh, ql)
    hi = jnp.where(neg, rh, qh)
    lo = jnp.where(neg, rl, ql)
    data = D.pack(hi, lo) if dt.is_128 else lo
    return DeviceColumn(dt, group_valid & nz & n_ok & ok & scol.validity,
                        data=data)


def _minmax_dec128(c: DeviceColumn, func, seg, validity, nseg,
                   group_valid, f) -> DeviceColumn:
    """min/max on decimal128: lexicographic two-word reduction.

    First reduce the high word; then reduce the low word among rows whose
    high word hit the optimum — two segment_min passes, no sort."""
    from spark_rapids_tpu.expr import decimal128 as D

    hi, lo = D.unpack(c.data)
    kh, kl = D.key_words(hi, lo)
    if func == "max":
        kh, kl = ~kh, ~kl
    big = jnp.int64(9223372036854775807)
    kh_m = jnp.where(validity, kh, big)
    mh = SEG._seg_min_raw(kh_m, seg, nseg)
    tie = validity & (kh_m == (mh[seg] if nseg > 1 else mh[0]))
    kl_m = jnp.where(tie, kl, big)
    ml = SEG._seg_min_raw(kl_m, seg, nseg)
    has = SEG._seg_isum(validity.astype(jnp.int32), seg, nseg) > 0
    if func == "max":
        mh, ml = ~mh, ~ml
    out_hi = mh
    out_lo = ml ^ jnp.int64(-0x8000000000000000)
    return DeviceColumn(f.dataType, group_valid & has,
                        data=D.pack(out_hi, out_lo))


def _chan_merge(cn: DeviceColumn, ca: DeviceColumn, cm: DeviceColumn,
                mask_sorted, seg, nseg):
    """Chan's parallel merge of (n, avg, m2) buffer rows per segment.

    -> (ntot, nonzero_mask, mean, m2) per group."""
    valid = cn.validity & mask_sorted & (cn.data > 0)
    n_r = jnp.where(valid, cn.data, 0.0)
    ntot, _ = SEG.seg_sum(n_r, valid, seg, nseg)
    wsum, _ = SEG.seg_sum(n_r * jnp.where(valid, ca.data, 0.0),
                          valid, seg, nseg)
    nz = ntot > 0
    mean = wsum / jnp.where(nz, ntot, 1.0)
    d = jnp.where(valid, ca.data, 0.0) - mean[seg]
    m2, _ = SEG.seg_sum(jnp.where(valid, cm.data + n_r * d * d, 0.0),
                        valid, seg, nseg)
    return ntot, nz, mean, m2


def _merge_moment_bufs(cs, mask_sorted, seg, nseg):
    """Merge (n, avg, m2, m3[, m4]) buffer columns per segment using the
    order-independent closed forms (Pébay's formulas reduced to segmented
    sums).  -> (ntot, nz, mean, m2, m3[, m4])."""
    cn, ca, cm2, cm3 = cs[:4]
    cm4 = cs[4] if len(cs) > 4 else None
    ok = cn.validity & mask_sorted
    ni = jnp.where(ok, cn.data, 0.0)
    ntot, _ = SEG.seg_sum(ni, ok, seg, nseg)
    nz = ntot > 0
    s, _ = SEG.seg_sum(ni * jnp.where(ok, ca.data, 0.0), ok, seg, nseg)
    mean = s / jnp.where(nz, ntot, 1.0)
    d = jnp.where(ok, ca.data - mean[seg], 0.0)
    m2i = jnp.where(ok, cm2.data, 0.0)
    m3i = jnp.where(ok, cm3.data, 0.0)
    m2, _ = SEG.seg_sum(m2i + ni * d * d, ok, seg, nseg)
    m3, _ = SEG.seg_sum(m3i + 3.0 * m2i * d + ni * d ** 3, ok, seg, nseg)
    if cm4 is None:
        return ntot, nz, mean, m2, m3
    m4i = jnp.where(ok, cm4.data, 0.0)
    m4, _ = SEG.seg_sum(
        m4i + 4.0 * m3i * d + 6.0 * m2i * d * d + ni * d ** 4, ok, seg,
        nseg)
    return ntot, nz, mean, m2, m3, m4


def _merge_cov_bufs(cs, mask_sorted, seg, nseg):
    """Merge (n, xavg, yavg, ck[, xm2, ym2]) covariance buffers per
    segment. -> (ntot, nz, xavg, yavg, ck[, xm2, ym2])."""
    cn, cx, cy, cc = cs[:4]
    ok = cn.validity & mask_sorted
    ni = jnp.where(ok, cn.data, 0.0)
    ntot, _ = SEG.seg_sum(ni, ok, seg, nseg)
    nz = ntot > 0
    sx, _ = SEG.seg_sum(ni * jnp.where(ok, cx.data, 0.0), ok, seg, nseg)
    sy, _ = SEG.seg_sum(ni * jnp.where(ok, cy.data, 0.0), ok, seg, nseg)
    xavg = sx / jnp.where(nz, ntot, 1.0)
    yavg = sy / jnp.where(nz, ntot, 1.0)
    dx = jnp.where(ok, cx.data - xavg[seg], 0.0)
    dy = jnp.where(ok, cy.data - yavg[seg], 0.0)
    cki = jnp.where(ok, cc.data, 0.0)
    ck, _ = SEG.seg_sum(cki + ni * dx * dy, ok, seg, nseg)
    if len(cs) <= 4:
        return ntot, nz, xavg, yavg, ck
    xm2, _ = SEG.seg_sum(jnp.where(ok, cs[4].data, 0.0) + ni * dx * dx,
                         ok, seg, nseg)
    ym2, _ = SEG.seg_sum(jnp.where(ok, cs[5].data, 0.0) + ni * dy * dy,
                         ok, seg, nseg)
    return ntot, nz, xavg, yavg, ck, xm2, ym2


def _seg_last_index(seg, row_mask, num_segments):
    n = seg.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    v = jnp.where(row_mask, iota, -1)
    return jax.ops.segment_max(v, seg, num_segments=num_segments)


def _key_at_rows(kc: DeviceColumn, sorted_keys, at: int, row_valid):
    """Key column ``kc`` in the sorted rows, read back from its sorted key
    words where they hold the value itself (integer, date, timestamp and
    64-bit decimal keys: no gather); None for the others, which
    ``_compact_ends`` gathers at the slots."""
    d = kc.data
    if (kc.chars is not None or kc.children is not None or d is None
            or d.ndim != 1 or not jnp.issubdtype(d.dtype, jnp.integer)):
        return None
    return DeviceColumn(kc.dtype, (sorted_keys[at] == 0) & row_valid,
                        data=sorted_keys[at + 1].astype(d.dtype))


def _compact_ends(cols, key_cols, seg, is_end, perm, ngroups):
    """The end-row form's output columns at their slots: ONE sort keyed by
    each end row's segment id, every other row after them, carries every
    output word, the validities packed 32 to a word.  Slot g < ngroups
    then holds group g; the slots past it hold rows that end no segment,
    whose validities hold ``is_end`` and so read False.  A key column that
    ``_key_at_rows`` could not read (None in ``cols``) is gathered at the
    slots through the sorted row index, carried beside them."""
    cap = seg.shape[0]
    flat = [c for c in cols if c is not None]
    words = [w for c in flat for w in _flat_sort_operands(c)[:-1]]
    packed = []
    for i in range(0, len(flat), 32):
        w = jnp.zeros(cap, jnp.uint32)
        for b, c in enumerate(flat[i:i + 32]):
            w = w | (c.validity.astype(jnp.uint32) << b)
        packed.append(w)
    extra = (perm,) if len(flat) < len(cols) else ()
    out = jax.lax.sort(
        (jnp.where(is_end, seg, cap),) + tuple(words) + tuple(packed)
        + extra, num_keys=1, is_stable=False)
    nw = len(words)
    words = list(out[1:1 + nw])
    packed = out[1 + nw:1 + nw + len(packed)]
    slot_valid = jnp.arange(cap) < ngroups
    out_cols, j = [], 0
    for i, c in enumerate(cols):
        if c is None:
            g = _gather_col(key_cols[i], out[-1])
            out_cols.append(DeviceColumn(
                g.dtype, g.validity & slot_valid, data=g.data,
                chars=g.chars, lengths=g.lengths))
            continue
        k = c.data.ndim        # one word, or a decimal128's two limbs
        v = ((packed[j // 32] >> (j % 32)) & 1).astype(jnp.bool_)
        out_cols.append(_rebuild_flat_col(c, words[:k] + [v]))
        del words[:k]
        j += 1
    return out_cols


def _gather_col(c: DeviceColumn, idx) -> DeviceColumn:
    return c.gather(idx)


def _flat_sort_operands(c: DeviceColumn):
    """1-D operand arrays of a flat (or dec128 two-limb) column for key
    co-sorting; None when the column needs the gather path (strings,
    arrays, structs)."""
    if c.chars is not None or c.children is not None \
            or c.elem_valid is not None or c.data is None:
        return None
    if c.data.ndim == 1:
        return [c.data, c.validity]
    if c.data.ndim == 2 and c.data.shape[1] == 2:     # decimal128 limbs
        return [c.data[:, 0], c.data[:, 1], c.validity]
    return None


def _rebuild_flat_col(c: DeviceColumn, arrs) -> DeviceColumn:
    """Inverse of _flat_sort_operands over the sorted operand slices."""
    if len(arrs) == 2:
        return DeviceColumn(c.dtype, arrs[1], data=arrs[0])
    return DeviceColumn(c.dtype, arrs[2],
                        data=jnp.stack([arrs[0], arrs[1]], axis=1))
