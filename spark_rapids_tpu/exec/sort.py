"""TpuSortExec / TpuTopNExec.

Reference analog: GpuSortExec + GpuOutOfCoreSortIterator + GpuTopN
(SURVEY.md §2.4).  In-core path: one lax.sort over packed key words per shape
bucket.  Out-of-core path (big inputs): each input batch is sorted in-core,
sorted runs are kept spillable, and an N-way merge re-sorts run heads in
memory-bounded windows — see mem/spill.py integration (round 1 keeps runs
device-resident; spill hooks land with the memory runtime).
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import jax
from spark_rapids_tpu.perfcounters import tpu_jit
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.expr.base import EvalContext, Expression
from spark_rapids_tpu.ops.sortkeys import SortSpec, sort_permutation


def _gather_batch(batch: ColumnarBatch, perm, num_rows,
                  schema) -> ColumnarBatch:
    cols = []
    for c in batch.columns:
        if c.is_string:
            cols.append(DeviceColumn(c.dtype, c.validity[perm],
                                     chars=c.chars[perm],
                                     lengths=c.lengths[perm]))
        else:
            cols.append(DeviceColumn(c.dtype, c.validity[perm],
                                     data=c.data[perm]))
    return ColumnarBatch(cols, num_rows, schema)


class TpuSortExec(TpuExec):
    # declared up front with reference levels (GpuSortExec metrics)
    EXTRA_METRICS = {"sortTime": "MODERATE"}

    def __init__(self, orders: List[Tuple[Expression, SortSpec]],
                 is_global: bool, child: TpuExec, ansi: bool = False,
                 ooc_bytes: int = 1 << 30, ooc_chunk_rows: int = 1024):
        super().__init__([child])
        self.orders = orders
        self.is_global = is_global
        self.ansi = ansi
        # out-of-core threshold + merge window chunk (GpuOutOfCoreSortIterator
        # analog: inputs beyond the goal sort as spillable runs + k-way merge)
        self.ooc_bytes = ooc_bytes
        self.ooc_chunk_rows = ooc_chunk_rows

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        o = ", ".join(f"{e.sql_string()} {'ASC' if s.ascending else 'DESC'}"
                      for e, s in self.orders)
        return f"TpuSort [{o}]"

    def _sort_program(self, schema):
        """(registry key parts, factory) — shared by the runtime path and
        the plan-time AOT enumeration."""
        from spark_rapids_tpu.compilecache.keys import (
            conf_fp,
            exprs_fp,
            schema_fp,
        )

        orders = self.orders
        ansi = self.ansi
        okeys = exprs_fp([e for e, _ in orders])
        key_parts = None if okeys is None else (
            "sort", schema_fp(schema), okeys,
            tuple((s.ascending, s.nulls_first) for _, s in orders),
            bool(ansi), conf_fp())

        def factory():
            def fn(cols, num_rows):
                batch = ColumnarBatch(list(cols), num_rows, schema)
                ctx = EvalContext(batch, ansi=ansi)
                key_cols = [e.eval_tpu(ctx) for e, _ in orders]
                specs = [s for _, s in orders]
                perm = sort_permutation(key_cols, specs, batch.row_mask)
                out = _gather_batch(batch, perm, num_rows, schema)
                return tuple(out.columns)

            return tpu_jit(fn, "sort"), None

        return key_parts, factory

    def _sort_fn(self, schema):
        if getattr(self, "_jitted", None) is not None:
            return self._jitted
        from spark_rapids_tpu.compilecache.registry import cached_program

        key_parts, factory = self._sort_program(schema)
        self._jitted = cached_program(key_parts, factory,
                                      label=self.describe()).jitted
        return self._jitted

    def aot_output_rows(self):
        # global sort concatenates the whole input into one batch
        rows = self.aot_input_rows()
        return None if rows is None else [sum(rows)]

    def aot_output_caps(self):
        caps = super().aot_output_caps()
        return caps if caps is not None else self.aot_input_concat_caps()

    def aot_emits_single_batch(self):
        return True

    def aot_programs(self):
        from spark_rapids_tpu.compilecache.aot import (
            AotProgram,
            dummy_batch_args,
        )

        caps = self.aot_input_concat_caps()
        if not caps:
            return []
        schema = self.children[0].output
        key_parts, factory = self._sort_program(schema)

        def args_factory():
            return [dummy_batch_args(schema, c) for c in caps]

        return [AotProgram(key_parts, factory, args_factory,
                           f"sort:{self.describe()[:48]}")]

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.memory.spill import get_spill_framework

        fw = get_spill_framework()
        spillables = []
        total_bytes = 0
        for b in self.children[0].execute_columnar():
            total_bytes += b.nbytes()
            spillables.append(fw.track(b))
        if not spillables:
            return
        if len(spillables) > 1 and total_bytes > self.ooc_bytes:
            yield from self._execute_out_of_core(spillables, fw)
            return
        with self.metric("sortTime").timed():
            def run():
                for s in spillables:
                    s.pin()
                try:
                    batches = [s.get_batch() for s in spillables]
                    batch = (batches[0] if len(batches) == 1
                             else ColumnarBatch.concat(batches))
                    fn = self._sort_fn(batch.schema)
                    cols = fn(tuple(batch.columns), jnp.int32(batch.num_rows))
                    return ColumnarBatch(list(cols), batch.num_rows,
                                         batch.schema)
                finally:
                    for s in spillables:
                        s.unpin()

            out = with_retry_no_split(run)
            for s in spillables:
                s.close()
        yield self._count_output(out)

    # -- out-of-core: sorted runs + k-way windowed merge -----------------
    def _execute_out_of_core(self, spillables, fw) -> Iterator[ColumnarBatch]:
        """GpuOutOfCoreSortIterator analog: sort each batch into a spillable
        run, then merge fixed-size chunk windows of all runs; rows are safe
        to emit once their key is <= the smallest last-loaded key of any
        non-exhausted run.  Peak device memory ~ one run + k * chunk."""
        from spark_rapids_tpu.memory.retry import with_retry_no_split

        schema = self.children[0].output
        C = self.ooc_chunk_rows
        sort_one = self._sort_fn(schema)

        runs = []            # (spillable sorted run, row count)
        with self.metric("sortTime").timed():
            for s in spillables:
                def mk(s=s):
                    s.pin()
                    try:
                        b = s.get_batch()
                        cols = sort_one(tuple(b.columns),
                                        jnp.int32(b.num_rows))
                        return ColumnarBatch(list(cols), b.num_rows, schema)
                    finally:
                        s.unpin()
                sorted_b = with_retry_no_split(mk)
                runs.append([fw.track(sorted_b), sorted_b.num_rows, 0])
                s.close()
        yield from self._merge_runs(runs, schema)

    def _merge_runs(self, runs, schema) -> Iterator[ColumnarBatch]:
        """Memory-bounded k-way merge of sorted spillable runs — shared by
        the single-chip out-of-core sort and the per-device emit of the
        distributed ICI sort (exec/ici.py)."""
        from spark_rapids_tpu.lifecycle.context import check_cancel

        C = self.ooc_chunk_rows
        k = len(runs)
        merge = self._merge_window_fn(schema, k)
        while any(off < n for _, n, off in runs):
            # cooperative cancellation per merge window: the k-way merge
            # can loop for many windows between yields
            check_cancel()
            chunks = []
            metas = []   # (nvalid, exhausted)
            for s, n, off in runs:
                remaining = n - off
                take = min(C, max(remaining, 0))
                if take > 0:
                    s.pin()
                    try:
                        full = s.get_batch()
                        chunk = full.slice_rows(off, C)
                    finally:
                        s.unpin()
                    # capacity C even when fewer rows remain
                    chunk = ColumnarBatch(
                        [c.slice_to(C) for c in chunk.columns], take, schema)
                else:
                    from spark_rapids_tpu.columnar.batch import empty_batch

                    chunk = empty_batch(schema, capacity=C)
                chunks.append(chunk)
                metas.append((take, remaining <= C))
            nvalid = jnp.asarray([m[0] for m in metas], jnp.int32)
            exhausted = jnp.asarray([m[1] for m in metas], jnp.bool_)
            with self.metric("sortTime").timed():
                out_cols, emit_cnt, consumed = merge(
                    tuple(tuple(c.columns) for c in chunks), nvalid,
                    exhausted)
                emit = int(emit_cnt)
                consumed_np = [int(x) for x in consumed]
            for i, used in enumerate(consumed_np):
                runs[i][2] += used
            if emit:
                yield self._count_output(
                    ColumnarBatch(list(out_cols), emit, schema))
        for s, _, _ in runs:
            s.close()

    def _merge_window_fn(self, schema, k: int):
        orders = self.orders
        ansi = self.ansi

        def keys_of(batch):
            ctx = EvalContext(batch, ansi=ansi)
            key_cols = [e.eval_tpu(ctx) for e, _ in orders]
            specs = [s for _, s in orders]
            from spark_rapids_tpu.ops.sortkeys import pack_sort_keys

            return pack_sort_keys(key_cols, specs, batch.row_mask)

        def le_bound(words, bound):
            """per row: key <= bound (lexicographic over packed words)."""
            lt = jnp.zeros(words[0].shape, jnp.bool_)
            eq = jnp.ones(words[0].shape, jnp.bool_)
            for w, b in zip(words, bound):
                lt = lt | (eq & (w < b))
                eq = eq & (w == b)
            return lt | eq

        def cat_columns(batches, C, k):
            """Static-shape concat of k C-capacity chunk batches."""
            out = []
            for ci in range(len(batches[0].columns)):
                cs = [b.columns[ci] for b in batches]
                validity = jnp.concatenate([c.validity for c in cs])
                if cs[0].is_string:
                    w = max(c.width for c in cs)
                    chars = jnp.concatenate([
                        jnp.pad(c.chars, ((0, 0), (0, w - c.width)))
                        for c in cs])
                    lengths = jnp.concatenate([c.lengths for c in cs])
                    out.append(DeviceColumn(cs[0].dtype, validity,
                                            chars=chars, lengths=lengths))
                else:
                    out.append(DeviceColumn(
                        cs[0].dtype, validity,
                        data=jnp.concatenate([c.data for c in cs])))
            return out

        def fn(chunk_cols, nvalid, exhausted):
            C = chunk_cols[0][0].capacity if chunk_cols else 0
            # normalize string widths across chunks: pack_sort_keys emits one
            # word per 8 chars, so differing widths would misalign the
            # word-by-word bound comparisons
            ncols = len(chunk_cols[0])
            widths = [max(cs[ci].width for cs in chunk_cols)
                      for ci in range(ncols)]
            from spark_rapids_tpu.expr.predicates import _pad_to

            norm = []
            for cs in chunk_cols:
                row = []
                for ci, c in enumerate(cs):
                    if c.is_string and c.width < widths[ci]:
                        row.append(DeviceColumn(
                            c.dtype, c.validity,
                            chars=_pad_to(c.chars, widths[ci]),
                            lengths=c.lengths))
                    else:
                        row.append(c)
                norm.append(row)
            chunk_cols = norm
            batches = [ColumnarBatch(list(cs), nvalid[i], schema)
                       for i, cs in enumerate(chunk_cols)]
            all_words = []
            bounds = []       # last valid key of each non-exhausted chunk
            big = jnp.int64(9223372036854775807)
            for i, b in enumerate(batches):
                mask = jnp.arange(C) < nvalid[i]
                words = keys_of(b)
                all_words.append((words, mask))
                last = jnp.clip(nvalid[i] - 1, 0, C - 1)
                # exhausted or empty runs impose no bound
                no_bound = exhausted[i] | (nvalid[i] == 0)
                bounds.append([jnp.where(no_bound, big, w[last])
                               for w in words])
            bound = bounds[0]
            for cand in bounds[1:]:
                lt = jnp.zeros((), jnp.bool_)
                eq = jnp.ones((), jnp.bool_)
                for a, c in zip(bound, cand):
                    lt = lt | (eq & (c < a))
                    eq = eq & (c == a)
                bound = [jnp.where(lt, c, a) for a, c in zip(bound, cand)]
            # consumed per chunk + total window sort
            consumed = []
            for words, mask in all_words:
                ok = le_bound(words, bound) & mask
                consumed.append(jnp.sum(ok.astype(jnp.int32)))
            mcols = cat_columns(batches, C, k)
            mmask = jnp.concatenate(
                [jnp.arange(C) < nvalid[i] for i in range(k)])
            merged = ColumnarBatch(mcols, C * k, schema)
            ctx = EvalContext(merged, ansi=ansi)
            key_cols = [e.eval_tpu(ctx) for e, _ in orders]
            specs = [s for _, s in orders]
            perm = sort_permutation(key_cols, specs, mmask)
            out = _gather_batch(merged, perm, C * k, schema)
            from spark_rapids_tpu.ops.sortkeys import pack_sort_keys

            mwords = [w[perm]
                      for w in pack_sort_keys(key_cols, specs, mmask)]
            emit = jnp.sum((le_bound(mwords, bound)
                            & mmask[perm]).astype(jnp.int32))
            return tuple(out.columns), emit, jnp.stack(consumed)

        return tpu_jit(fn, "sort_merge")


class TpuTopNExec(TpuExec):
    """sort + limit fused: keeps only n rows per batch then merges.

    Reference analog: GpuTopN in limit.scala — sort each batch, slice to n,
    concat + re-sort + slice; avoids materializing the full sort."""

    def __init__(self, n: int, orders: List[Tuple[Expression, SortSpec]],
                 child: TpuExec, ansi: bool = False):
        super().__init__([child])
        self.n = n
        self.orders = orders
        self.ansi = ansi

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        return f"TpuTopN {self.n}"

    def execute_columnar(self):
        sorter = TpuSortExec(self.orders, True, self.children[0], self.ansi)
        pending: List[ColumnarBatch] = []
        for b in self.children[0].execute_columnar():
            fn = sorter._sort_fn(b.schema)
            cols = fn(tuple(b.columns), jnp.int32(b.num_rows))
            sb = ColumnarBatch(list(cols), b.num_rows, b.schema)
            pending.append(sb.slice_rows(0, min(self.n, sb.num_rows)))
            if len(pending) > 8:
                pending = [self._merge(pending, sorter)]
        if not pending:
            return
        out = self._merge(pending, sorter)
        yield self._count_output(out)

    def _merge(self, batches, sorter):
        merged = (batches[0] if len(batches) == 1
                  else ColumnarBatch.concat(batches))
        fn = sorter._sort_fn(merged.schema)
        cols = fn(tuple(merged.columns), jnp.int32(merged.num_rows))
        sb = ColumnarBatch(list(cols), merged.num_rows, merged.schema)
        return sb.slice_rows(0, min(self.n, sb.num_rows))
