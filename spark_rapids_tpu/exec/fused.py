"""Join->Aggregate whole-stage fusion — the program-count killer.

Reference analog: none directly — the reference streams gather-map chunks
from GpuShuffledHashJoinExec into GpuHashAggregateExec as separate kernels
(SURVEY.md §2.4 Joins / hash aggregate); on a PCIe-local GPU the launch
boundary is ~10µs so fusing across it buys little.  On TPU every program
boundary materializes its output in HBM and usually syncs with the host,
so an aggregate directly above an equi-join is compiled INTO the join's
materialization program:

  * general path: [build] [probe: lo/counts/sizes] -> ONE host sync for the
    pair count -> [materialize+aggregate fused].  3 programs, 1 sync.
  * unique-build fast path: when the build side's keys are unique (the
    star-schema dim-table case — asked of the sorted build side by a
    sort-free program once a plan, before its first probe, and cached on
    the exec), pairs == matched probe rows, so the output
    capacity is the probe capacity: probe search, build gather, and the
    whole aggregation run in ONE program with NO size sync.  The unmatched
    probe rows of a LEFT join stay in place with null build columns; an
    INNER join masks them out via the aggregate's row-validity mask —
    filtered rows never move (no compaction scatter at all).

Falls back to the unfused pair (agg over join output) when the build side
exceeds the sub-partition threshold (out-of-core joins keep their own
machinery) — correctness is identical either way.
"""
from __future__ import annotations

import itertools
import threading
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    DEFAULT_ROW_BUCKETS,
    DeviceColumn,
    round_up_bucket,
)
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.exec.join import (
    _BaseTpuJoinExec,
    _key_words_of,
    _merge_lookup,
    _multiword_searchsorted,
    _SortedBuildSide,
    _takes_merge,
    arranged,
)
from spark_rapids_tpu.expr.base import EvalContext
from spark_rapids_tpu.ops import mxugather as MG
from spark_rapids_tpu.perfcounters import bump, span, sync_get, tpu_jit
from spark_rapids_tpu.plan.nodes import AggregateMode, JoinType


def _mask_col(c: DeviceColumn, keep) -> DeviceColumn:
    """AND a row mask into a column's validity (recursing into structs)."""
    if c.is_struct:
        return DeviceColumn(c.dtype, c.validity & keep,
                            children=tuple(_mask_col(k, keep)
                                           for k in c.children))
    return DeviceColumn(c.dtype, c.validity & keep, data=c.data,
                        chars=c.chars, lengths=c.lengths,
                        elem_valid=c.elem_valid)


def _has_dup_key(bwords, n_valid):
    """Traced: does any adjacent pair among the first ``n_valid`` sorted
    build keys compare equal (the build side's keys are not unique)?"""
    cap_b = bwords[0].shape[0]
    adj_eq = jnp.ones(cap_b - 1, jnp.bool_)
    for w in bwords:
        adj_eq = adj_eq & (w[:-1] == w[1:])
    in_valid = (jnp.arange(cap_b - 1) + 1) < n_valid
    return jnp.any(adj_eq & in_valid)


def _use_mxu(cap_b: int) -> bool:
    """The unique-build path's dimension lookup, chosen by the build
    side's CAPACITY: small tables ride the MXU one-hot contraction
    (ops/mxugather.py), larger ones the VPU gathers."""
    return cap_b <= MG.MAX_TABLE_ROWS


# process-unique tags for unfingerprintable agg variants (never reused,
# unlike id(), which the allocator recycles after GC); the lock makes
# the lazy pin-on-object init atomic — two concurrent collects sharing
# one agg must agree on the tag or the loser retraces forever
_PRIVATE_TAGS = itertools.count()
_PRIVATE_TAG_LOCK = threading.Lock()


class TpuJoinAggFusedExec(TpuExec):
    """agg(join(probe, build)) in (at most) three XLA programs."""

    EXTRA_METRICS = {"buildTime": "MODERATE"}

    def __init__(self, agg, join: _BaseTpuJoinExec):
        super().__init__(list(join.children))
        self.agg = agg
        self.join = join
        self._jit_cache = {}
        # None = unknown; True/False learned from the first build side and
        # reused across collects of the same plan (device-cached scans make
        # repeat execution the hot path)
        self._build_unique: Optional[bool] = None
        # what the last probe took ("path=... build_cap=N"), for describe()
        self._last_probe: Optional[str] = None

    @property
    def output(self):
        return self.agg.output

    def describe(self):
        took = "" if self._last_probe is None else " " + self._last_probe
        return (f"TpuJoinAggFused[{self.agg.describe()} <- "
                f"{self.join.describe()}]{took}")

    def _registry_scope(self):
        cached = getattr(self, "_reg_scope", False)
        if cached is not False:
            return cached
        join_scope = self.join._registry_scope()
        agg_fp = self.agg._program_fp()
        scope = None
        if join_scope is not None and agg_fp is not None:
            scope = ("joinagg",) + join_scope + (agg_fp,)
        self._reg_scope = scope
        return scope

    def _agg_tag(self, agg):
        """Stable registry identity for the agg variant a key closes over
        (self.agg or its PARTIAL/FINAL twins).  An unfingerprintable agg
        gets a process-unique tag PINNED on the object: an ``id()`` here
        could be reused after GC, silently aliasing two different aggs
        to one registry program — and the private marker also forces the
        key out of the shared registry (see ``_cached``)."""
        fpp = agg._program_fp()
        if fpp is not None:
            return fpp
        tag = getattr(agg, "_joinagg_private_tag", None)
        if tag is None:
            with _PRIVATE_TAG_LOCK:
                tag = getattr(agg, "_joinagg_private_tag", None)
                if tag is None:
                    tag = ("private", next(_PRIVATE_TAGS))
                    agg._joinagg_private_tag = tag
        return tag

    def _cached(self, key, builder):
        if key not in self._jit_cache:
            from spark_rapids_tpu.compilecache.registry import (
                cached_jit_program,
            )

            scope = self._registry_scope()
            # a private (unfingerprintable-agg) tag must not enter the
            # process-wide registry: the tag is meaningless in another
            # process (persisted AOT) and would pin a never-shareable
            # program in the shared LRU
            private = isinstance(key, tuple) and any(
                isinstance(p, tuple) and p[:1] == ("private",)
                for p in key)
            self._jit_cache[key] = cached_jit_program(
                None if scope is None or private else scope + (key,),
                builder,
                label=f"joinagg:{key if isinstance(key, str) else key[0]}")
        return self._jit_cache[key]

    def aot_programs(self):
        """The fused path reuses the join's build-sort program verbatim —
        including the broadcast-side stage-absorbed (pre_ops) variant —
        while the fused probe/materialize programs have data-dependent
        operand shapes (pair counts, uniqueness) and compile inline."""
        self.join.children = list(self.children)
        build_src, pre_ops, pre_schema = self._build_source()
        if pre_ops is None:
            return [p for p in self.join.aot_programs()
                    if p.label.startswith("join-build")]
        from spark_rapids_tpu.compilecache.aot import (
            AotProgram,
            concat_caps,
            dummy_batch_args,
        )
        from spark_rapids_tpu.compilecache.keys import (
            schema_fp,
            stage_ops_fp,
        )
        from spark_rapids_tpu.perfcounters import tpu_jit as _tj

        join = self.join
        scope = join._registry_scope()
        ops_fp = stage_ops_fp(pre_ops)
        caps = concat_caps(build_src)
        if scope is None or ops_fp is None or not caps:
            return []
        cap = caps[0]
        key = ("build_preops", ops_fp, schema_fp(pre_schema))
        fn = join._build_fn(pre_schema, join.right_keys, pre_ops)

        def args_factory(_schema=pre_schema, _cap=cap):
            return [dummy_batch_args(_schema, _cap)]

        return [AotProgram(scope + (key,),
                           lambda _fn=fn: (_tj(_fn), None), args_factory,
                           f"join-build-preops:{self.describe()[:36]}")]

    # ------------------------------------------------------------------
    def _fallback(self) -> Iterator[ColumnarBatch]:
        # the agg's child is still the join exec — the unfused pipeline
        yield from self.agg.execute_columnar()

    def _build_source(self):
        """(exec to drive, stage ops to fuse into the build program, input
        schema) — absorbs BroadcastExchange(Stage(x)) into the build."""
        from spark_rapids_tpu.exec.basic import TpuStageExec
        from spark_rapids_tpu.exec.exchange import TpuBroadcastExchangeExec

        child = self.join._build_child()
        if isinstance(child, TpuBroadcastExchangeExec):
            inner = child.children[0]
            if (isinstance(inner, TpuStageExec) and not inner.ansi
                    and not inner._has_host_kernels()):
                return inner.children[0], inner.ops, inner.children[0].output
        return child, None, None

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.spill import get_spill_framework

        join = self.join
        # later plan passes rewrite self.children in place; the join exec
        # must execute the rewritten subtrees, not its stale private copy
        join.children = list(self.children)
        fw = get_spill_framework()
        # broadcast-side stage absorption: drive the stage's CHILD and fuse
        # the project/filter ops into the build-sort program
        build_src, pre_ops, pre_schema = self._build_source()
        build_spill = []
        total_build_bytes = 0
        try:
            for b in build_src.execute_columnar():
                total_build_bytes += b.nbytes()
                build_spill.append(fw.track(b))
        except BaseException:
            for s in build_spill:
                s.close()
            raise
        if total_build_bytes > join.sub_partition_bytes:
            for s in build_spill:
                s.close()
            # out-of-core join path owns this size class; re-drive the
            # build child (scans re-stream; device cache makes it cheap)
            yield from self._fallback()
            return
        for s in build_spill:
            s.pin()
        try:
            build_batch = join._concat_or_empty(
                [s.get_batch() for s in build_spill],
                pre_schema if pre_schema is not None
                else join._build_child().output)
        finally:
            for s in build_spill:
                s.unpin()
                s.close()
        # timed on the FUSED exec's own metric: the inner join node is
        # not in this exec's children, so a metric written there would
        # never be harvested by collect_metrics / explain("analyze")
        with self.metric("buildTime").timed():
            build = join._prepare_build(build_batch, join.right_keys,
                                        pre_ops=pre_ops,
                                        in_schema=pre_schema)

        probe_it = join._probe_child().execute_columnar()
        first = next(probe_it, None)
        if first is None:
            from spark_rapids_tpu.columnar.batch import empty_batch

            if not self.agg.grouping:
                yield self.agg._global_agg_empty()
            else:
                yield empty_batch(self.agg._output)
            return
        from spark_rapids_tpu.memory.retry import (
            TpuSplitAndRetryOOM,
            with_retry,
            with_retry_no_split,
        )

        if self.agg.mode == AggregateMode.PARTIAL:
            # buffer-form output per probe batch; the surviving FINAL agg
            # above merges them (finalizing here would feed it avg-of-avgs)
            def feed_all():
                yield first
                yield from probe_it

            for probe in feed_all():
                with self.metrics["opTime"].timed():
                    for out in with_retry(
                            fw.track(probe),
                            lambda piece: self._probe_agg_one(
                                build, piece, self.agg)):
                        yield self._count_output(out)
            return

        second = next(probe_it, None)
        if second is None:
            try:
                with self.metrics["opTime"].timed():
                    out = with_retry_no_split(
                        lambda: self._probe_agg_one(build, first, self.agg))
                yield self._count_output(out)
                return
            except TpuSplitAndRetryOOM:
                # split the probe batch and continue on the two-phase path
                pass

        # multi-batch probe (or split-forced): per-batch PARTIAL buffers,
        # buffer merges, one FINAL finalize (the agg's COMPLETE twins)

        partial, final = self.agg._complete_twins()
        spillables = []

        def feed():
            yield first
            if second is not None:
                yield second
            yield from probe_it

        for probe in feed():
            with self.metrics["opTime"].timed():
                for out in with_retry(
                        fw.track(probe),
                        lambda piece: self._probe_agg_one(build, piece,
                                                          partial)):
                    spillables.append(fw.track(out))
        with self.metrics["opTime"].timed():
            while len(spillables) > 1:
                a, b2 = spillables.pop(0), spillables.pop(0)
                merged = with_retry_no_split(
                    lambda: final._merge_pair(a, b2))
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

    # ------------------------------------------------------------------
    def _probe_agg_one(self, build: _SortedBuildSide, probe: ColumnarBatch,
                       agg) -> ColumnarBatch:
        cap_b = build.words[0].shape[0]
        if self._build_unique is None:
            # asked of the sorted build side itself, once a plan, by a
            # sort-free program and a one-scalar sync: a star join's first
            # collect then takes the one-program path too, and never
            # compiles or runs the general path's two sort-bearing programs
            has_dup = self._cached("build_has_dup", _has_dup_key)(
                tuple(build.words), build.n_valid)
            self._build_unique = not bool(sync_get(has_dup))
        if self._build_unique:
            bump("joinagg_unique_probes")
            with span("srt.joinagg.unique"):
                return self._unique_probe_agg(build, probe, agg)
        bump("joinagg_general_probes")
        # the pair expansion gathers on the VPU whatever the build's size
        self._last_probe = f"path=general lookup=vpu build_cap={cap_b}"
        with span("srt.joinagg.probe_sizes"):
            lo, counts, unmatched, sizes = self._probe_sizes(build, probe)
            total, n_um = (int(x) for x in sync_get(sizes))
        with span("srt.joinagg.mat_agg"):
            return self._mat_agg(build, probe, lo, counts, unmatched,
                                 total, n_um, agg)

    def _probe_sizes(self, build: _SortedBuildSide, probe: ColumnarBatch):
        """Probe program: lo/counts plus ONE packed sizes vector
        [total_pairs, n_unmatched] so sizing costs a single host round
        trip."""
        join = self.join
        schema = probe.schema
        ansi, left_keys = join.ansi, join.left_keys   # locals only

        def fn(bwords, n_valid, cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            ctx = EvalContext(b, ansi=ansi)
            key_cols = [k.eval_tpu(ctx) for k in left_keys]
            valid = b.row_mask
            for kc in key_cols:
                valid = valid & kc.validity
            qwords = _key_words_of(key_cols)
            lo = _multiword_searchsorted(list(bwords), n_valid, qwords,
                                         "left")
            hi = _multiword_searchsorted(list(bwords), n_valid, qwords,
                                         "right")
            counts = jnp.where(valid, hi - lo, 0)
            total = jnp.sum(counts.astype(jnp.int64))
            unmatched = b.row_mask & (counts == 0)
            n_um = jnp.sum(unmatched.astype(jnp.int64))
            sizes = jnp.stack([total, n_um])
            return lo, counts, unmatched, sizes

        jitted = self._cached("probe_sizes", fn)
        return jitted(tuple(build.words), build.n_valid,
                      tuple(probe.columns), jnp.int32(probe.num_rows))

    # ------------------------------------------------------------------
    def _finish(self, agg, cols, nrows) -> ColumnarBatch:
        n = 1 if not agg.grouping else int(nrows)
        return ColumnarBatch(list(cols), n, agg._output)

    def _mat_agg(self, build, probe, lo, counts, unmatched, total: int,
                 n_um: int, agg) -> ColumnarBatch:
        """General path: materialize pairs + aggregate in ONE program."""
        join = self.join
        with_um = join.join_type == JoinType.LEFT_OUTER
        out_rows = total + (n_um if with_um else 0)
        out_cap = round_up_bucket(max(out_rows, 1), DEFAULT_ROW_BUCKETS)
        agg_fn = agg.detached_for_trace()._agg_fn   # no subtree capture

        slots = join._mat_slots

        def fn(row_index, b_cols, p_cols, lo, counts, unmatched, total,
               nrows):
            lcols, bcols = _BaseTpuJoinExec.materialize_pairs(
                row_index, b_cols, p_cols, lo, counts, unmatched, total,
                nrows, out_cap, with_um)
            joined = tuple(arranged(slots, lcols, bcols))
            return agg_fn(joined, nrows.astype(jnp.int32))

        jitted = self._cached(("mat_agg", out_cap, with_um,
                               self._agg_tag(agg)), fn)
        cols, nrows = jitted(
            build.row_index,
            tuple(build.batch.columns[i] for i in join._b_sel),
            tuple(probe.columns[i] for i in join._p_sel),
            lo, counts, unmatched,
            jnp.int64(total), jnp.int64(out_rows))
        return self._finish(agg, cols, nrows)

    def _unique_probe_agg(self, build, probe, agg) -> ColumnarBatch:
        """Unique-build fast path: probe search + build gather + aggregate
        in ONE program; no size sync (output capacity == probe capacity).
        The aggregate runs through its bounded-cardinality ladder
        (groups_cap) — the synced output row count is the overflow
        check.

        Past the binary search's sizes (``_takes_merge``) the probe's one
        merge sort says whether a probe row matched and at which SORTED
        build position (``_merge_lookup``): no key word is gathered to
        compare it.  The payload is then fetched by that position from
        build columns permuted into key order, where the permute is the
        smaller gather (build capacity <= probe capacity); else through
        ``row_index[loc]`` from the columns as they are."""
        join = self.join
        left_outer = join.join_type == JoinType.LEFT_OUTER
        schema = probe.schema
        ansi, left_keys = join.ansi, join.left_keys
        agg_fn = agg.detached_for_trace()._agg_fn   # no subtree capture
        slots, p_sel = join._mat_slots, join._p_sel
        # for the counters and describe() only: the registry shares ``fn``
        # among execs whose build sides differ in capacity, so the trace
        # asks the operand shapes itself and closes over nothing of them
        cap_b = build.words[0].shape[0]
        lookup = "mxu" if _use_mxu(cap_b) else "vpu"
        match = "merge" if _takes_merge(cap_b, probe.capacity) else "gather"
        self._last_probe = (f"path=unique lookup={lookup} match={match} "
                            f"build_cap={cap_b}")

        def mk(groups_cap):
            def fn(bwords, row_index, n_valid, b_cols, p_cols, num_rows):
                b = ColumnarBatch(list(p_cols), num_rows, schema)
                ctx = EvalContext(b, ansi=ansi)
                key_cols = [k.eval_tpu(ctx) for k in left_keys]
                valid = b.row_mask
                for kc in key_cols:
                    valid = valid & kc.validity
                qwords = _key_words_of(key_cols)
                cap_b, cap_p = bwords[0].shape[0], qwords[0].shape[0]
                # small build tables ride the MXU one-hot gather: a VPU
                # random gather costs ~300ms per column at 20M probe rows
                # while the fused one_hot@table contraction is ~5ms
                # (ops/mxugather.py)
                use_mxu = _use_mxu(cap_b)

                def at(table, idx):
                    return MG.mxu_gather(table, idx) if use_mxu \
                        else table[idx]

                merge = _takes_merge(cap_b, cap_p)
                if merge:
                    loc, matched = _merge_lookup(list(bwords), n_valid,
                                                 qwords)
                    found = valid & matched
                else:
                    lo = _multiword_searchsorted(list(bwords), n_valid,
                                                 qwords, "left")
                    loc = jnp.clip(lo, 0, cap_b - 1)
                    eq = jnp.ones(lo.shape, jnp.bool_)
                    for w, q in zip(bwords, qwords):
                        eq = eq & (at(w, loc) == q)
                    found = valid & (lo < n_valid) & eq
                if merge and cap_b <= cap_p:
                    # payload in key order: one build-sized gather a
                    # column, then ``loc`` indexes it directly
                    brow = jnp.where(found, loc, 0)
                    src = [c.gather(row_index) for c in b_cols]
                else:
                    brow = jnp.where(found, at(row_index, loc), 0)
                    src = b_cols
                bcols = []
                for c in src:
                    g = MG.mxu_gather_col(c, brow) if use_mxu else None
                    if g is None:
                        g = c.gather(brow)
                    bcols.append(_mask_col(g, found))
                joined = tuple(arranged(
                    slots, [p_cols[i] for i in p_sel], bcols))
                row_valid = b.row_mask if left_outer \
                    else (b.row_mask & found)
                return agg_fn(joined, num_rows, row_valid=row_valid,
                              groups_cap=groups_cap)

            return fn

        args = (tuple(build.words), build.row_index, build.n_valid,
                tuple(build.batch.columns[i] for i in join._b_sel),
                tuple(probe.columns), jnp.int32(probe.num_rows))
        cap = probe.capacity
        tag = self._agg_tag(agg)

        def run(groups_cap):
            # one bump each a call of the fused program, by the payload
            # lookup and the key match it took
            bump("join_lookups_" + lookup)
            bump("join_matches_" + match)
            return self._cached(("uniq_agg", tag, groups_cap),
                                mk(groups_cap))(*args)

        B = agg._bounded_groups_cap(cap)
        if B:
            cols, nrows = run(B)
            n = int(nrows)
            while n > B:
                B2 = min(max(1 << (n - 1).bit_length(), B * 2), cap)
                agg._groups_cap_hint = B2
                bump("agg_groups_cap_regrows")
                if B2 >= cap:
                    B2 = None
                cols, nrows = run(B2)
                n = int(nrows)
                if B2 is None:
                    break
                B = B2
            return self._finish(agg, cols, n)
        cols, nrows = run(None)
        return self._finish(agg, cols, nrows)


class TpuWindowChainFusedExec(TpuExec):
    """[COMPLETE agg ->] window [-> project/filter stage] as ONE program.

    The window already runs in a single jitted function of
    (columns, num_rows-scalar); a grouped aggregate feeding it produces
    (columns, ngroups-scalar) — so the whole chain composes into one XLA
    program with zero host syncs between operators.  Only the final row
    count syncs (to label the output batch).  The reference runs these as
    three separate stages with exchange boundaries (SURVEY.md §2.4 Window).
    """

    def __init__(self, window, pre_agg=None, post_ops=None,
                 post_schema=None):
        child = pre_agg.children[0] if pre_agg is not None \
            else window.children[0]
        super().__init__([child])
        self.window = window
        self.pre_agg = pre_agg
        self.post_ops = list(post_ops or [])
        self._post_schema = post_schema
        self._jit_cache = {}

    @property
    def output(self):
        return self._post_schema if self._post_schema is not None \
            else self.window.output

    def describe(self):
        parts = []
        if self.pre_agg is not None:
            parts.append(self.pre_agg.describe())
        parts.append(self.window.describe())
        if self.post_ops:
            parts.append("+".join(type(o).__name__.replace("Op", "")
                                  for o in self.post_ops))
        return "TpuWindowChainFused[" + " -> ".join(parts) + "]"

    def _registry_scope(self):
        cached = getattr(self, "_reg_scope", False)
        if cached is not False:
            return cached
        from spark_rapids_tpu.compilecache.keys import (
            schema_fp,
            stage_ops_fp,
        )

        wkey, _ = self.window._window_program()
        ops_fp = stage_ops_fp(self.post_ops)
        agg_fp = (self.pre_agg._program_fp()
                  if self.pre_agg is not None else ())
        scope = None
        if wkey is not None and ops_fp is not None and agg_fp is not None:
            scope = ("windowchain", wkey, agg_fp, ops_fp,
                     schema_fp(self.output))
        self._reg_scope = scope
        return scope

    def _cached(self, key, builder):
        if key not in self._jit_cache:
            from spark_rapids_tpu.compilecache.registry import (
                cached_jit_program,
            )

            scope = self._registry_scope()
            self._jit_cache[key] = cached_jit_program(
                None if scope is None else scope + (key,), builder,
                label=f"windowchain:{key}")
        return self._jit_cache[key]

    def aot_programs(self):
        from spark_rapids_tpu.compilecache.aot import (
            AotProgram,
            dummy_batch_args,
        )

        scope = self._registry_scope()
        if scope is None:
            return []
        with_agg = self.pre_agg is not None
        if with_agg and not self.aot_child_single_batch():
            # multi-batch + pre-agg runs through the two-phase twins, not
            # the fused chain program
            return []
        caps = self.aot_input_concat_caps()
        if not caps:
            return []
        schema = self.children[0].output
        out = []
        for cap in caps:
            B = (self.pre_agg._bounded_groups_cap(cap)
                 if with_agg else None)
            key = ("chain", with_agg, cap, B)

            def factory(_b=B):
                return tpu_jit(self._chain_fn(with_agg, _b)), None

            def args_factory(_cap=cap):
                return [dummy_batch_args(schema, _cap)]

            out.append(AotProgram(scope + (key,), factory, args_factory,
                                  f"windowchain:{self.describe()[:44]}"))
        return out

    def _chain_fn(self, with_agg: bool, groups_cap=None):
        # detached clones: the registry-shared closure must not pin the
        # live window/agg execs (and through them the input subtree)
        window = self.window.detached_for_trace()
        pre_agg = (self.pre_agg.detached_for_trace()
                   if with_agg and self.pre_agg is not None else None)
        post_ops = self.post_ops

        def fn(cols, num_rows):
            ngroups = jnp.asarray(0, jnp.int32)
            if pre_agg is not None:
                # bounded-cardinality agg: the window then runs over the
                # B-wide grouped result instead of input-capacity columns
                cols, ngroups = pre_agg._agg_fn(cols, num_rows,
                                                groups_cap=groups_cap)
                num_rows = ngroups.astype(jnp.int32)
            wcols = window._window_fn(tuple(cols), num_rows)
            batch = ColumnarBatch(list(wcols), num_rows, window.output)
            if post_ops:
                ctx = EvalContext(batch, ansi=False)
                for op in post_ops:
                    batch = op.apply(ctx, batch)
            # ngroups reported separately: post_ops may filter rows, so
            # the final count cannot double as the ladder overflow check
            return (tuple(batch.columns), jnp.asarray(batch.num_rows),
                    jnp.asarray(ngroups, jnp.int32))

        return fn

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory.retry import (
            TpuSplitAndRetryOOM,
            with_retry_no_split,
        )
        from spark_rapids_tpu.memory.spill import get_spill_framework

        # keep the owned execs pointing at the (possibly rewritten) child
        owner = self.pre_agg if self.pre_agg is not None else self.window
        owner.children = list(self.children)

        def run(b, with_agg):
            args = (tuple(b.columns), jnp.int32(b.num_rows))
            B = (self.pre_agg._bounded_groups_cap(b.capacity)
                 if with_agg else None)
            if B:
                cols, count, ng = self._cached(
                    ("chain", with_agg, b.capacity, B),
                    self._chain_fn(with_agg, B))(*args)
                # ONE host round trip for both scalars: the output row
                # count and the ladder's overflow check used to sync
                # separately, one extra trip on every qc_window run
                n, g = (int(x) for x in sync_get((count, ng)))
                while g > B:     # groups-cap ladder (see aggregate.py)
                    B2 = min(max(1 << (g - 1).bit_length(), B * 2),
                             b.capacity)
                    self.pre_agg._groups_cap_hint = B2
                    bump("agg_groups_cap_regrows")
                    if B2 >= b.capacity:
                        B2 = None
                    cols, count, ng = self._cached(
                        ("chain", with_agg, b.capacity, B2),
                        self._chain_fn(with_agg, B2))(*args)
                    n, g = (int(x) for x in sync_get((count, ng)))
                    if B2 is None:
                        break
                    B = B2
                return ColumnarBatch(list(cols), n, self.output)
            cols, count, _ = self._cached(
                ("chain", with_agg, b.capacity, None),
                self._chain_fn(with_agg))(*args)
            # int(count) is irreducible here: it is the only scalar this
            # path reads back (ng is statically irrelevant without the
            # groups-cap ladder)
            return ColumnarBatch(list(cols), int(count), self.output)

        fw = get_spill_framework()
        batches = list(self.children[0].execute_columnar())
        if not batches:
            if self.pre_agg is None:
                return
            # aggregate-of-empty semantics, then window[+stage] over it
            from spark_rapids_tpu.columnar.batch import empty_batch

            if not self.pre_agg.grouping:
                b = self.pre_agg._global_agg_empty()
            else:
                b = empty_batch(self.pre_agg._output)
            with self.metrics["opTime"].timed():
                out = with_retry_no_split(lambda: run(b, False))
            yield self._count_output(out)
            return

        def agg_then_window(batch_list):
            """Aggregate the already-materialized batches through the
            two-phase twins (no re-execution of the child subtree), then
            window the grouped result."""
            agg_out = list(self.pre_agg._complete_two_phase(
                iter(batch_list), fw, []))
            b = (agg_out[0] if len(agg_out) == 1
                 else ColumnarBatch.concat(agg_out))
            return with_retry_no_split(lambda: run(b, False))

        run_agg = self.pre_agg is not None
        with self.metrics["opTime"].timed():
            if run_agg and len(batches) > 1:
                out = agg_then_window(batches)
            else:
                batch = (batches[0] if len(batches) == 1
                         else ColumnarBatch.concat(batches))
                try:
                    out = with_retry_no_split(lambda: run(batch, run_agg))
                except TpuSplitAndRetryOOM:
                    if not run_agg:
                        raise
                    out = agg_then_window(batches)
        yield self._count_output(out)
