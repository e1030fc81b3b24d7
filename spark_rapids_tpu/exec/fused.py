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

A star of several dimensions (TPC-DS query 43: store_sales against
date_dim and store) is a chain of INNER broadcast joins, each the probe
child of the next; the fast path then looks the fact rows up in every
dimension in turn inside the same ONE program, each lookup choosing MXU
or VPU by its own build capacity, and no joined batch is written between
them.  What the aggregate reads of one dimension alone and through a
string (``d_day_name = 'Sunday'``, the group key ``(s_store_name,
s_store_id)``) is computed once per BUILD row in that program and carried
to the fact rows as a small payload (``_Hoist``); strings are gathered
only for the output groups.  A chain with a dimension whose keys repeat
runs unfused (every join materializes; the answer is the same).

Falls back to the unfused pair (agg over join output) when the build side
exceeds the sub-partition threshold (out-of-core joins keep their own
machinery) — correctness is identical either way.
"""
from __future__ import annotations

import copy
import dataclasses
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
    _has_dup_key,
    _key_words_of,
    _lookup,
    _mask_col,
    _multiword_searchsorted,
    _SortedBuildSide,
    _takes_merge,
    _use_mxu,
    arranged,
)
from spark_rapids_tpu.expr.base import (
    BoundReference,
    EvalContext,
    Expression,
)
from spark_rapids_tpu.ops.sortkeys import _column_key_words
from spark_rapids_tpu.perfcounters import bump, span, sync_get, tpu_jit
from spark_rapids_tpu.plan.nodes import AggregateMode, JoinType


def _group_code(key_cols) -> DeviceColumn:
    """Traced, over a dimension's build rows: for each row, the position
    of the first row whose tuple of ``key_cols`` equals its own, a null
    equal to a null (Spark's grouping).  One code a distinct tuple, and
    the row that decodes it; one small sort of the build capacity."""
    cap = key_cols[0].capacity
    words = []
    for kc in key_cols:
        words.append((~kc.validity).astype(jnp.int64))
        words.extend(jnp.where(kc.validity, w, 0)
                     for w in _column_key_words(kc))
    iota = jnp.arange(cap, dtype=jnp.int32)
    srt = jax.lax.sort(tuple(words) + (iota,), num_keys=len(words),
                       is_stable=True)
    perm = srt[-1]
    differs = jnp.zeros(cap - 1, jnp.bool_)
    for w in srt[:-1]:
        differs = differs | (w[1:] != w[:-1])
    new_run = jnp.concatenate([jnp.ones(1, jnp.bool_), differs])
    # a stable sort puts a run's lowest position first: a real row, never
    # the padding after it.  lax.cummax, never associative_scan
    head = jax.lax.cummax(jnp.where(new_run, iota, 0))
    code = jnp.zeros(cap, jnp.int32).at[perm].set(perm[head])
    return DeviceColumn(T.INT, jnp.ones(cap, jnp.bool_), data=code)


_FLAGS_A_WORD = 16


def _pack(cols) -> list:
    """The payload a lookup carries for ``cols``: the booleans packed two
    bits each (value, validity) into int32 words, one gather a word where
    each boolean would cost two; the others as they are."""
    flags = [c for c in cols if isinstance(c.dtype, T.BooleanType)]
    words = []
    for s in range(0, len(flags), _FLAGS_A_WORD):
        w = jnp.zeros(flags[s].capacity, jnp.int32)
        for i, c in enumerate(flags[s:s + _FLAGS_A_WORD]):
            w = (w | (c.data.astype(jnp.int32) << (2 * i))
                 | (c.validity.astype(jnp.int32) << (2 * i + 1)))
        words.append(DeviceColumn(T.INT, jnp.ones(w.shape, jnp.bool_),
                                  data=w))
    return words + [c for c in cols
                    if not isinstance(c.dtype, T.BooleanType)]


class _Flag(Expression):
    """Boolean ``bit`` of a word ``_pack`` made, read where the aggregate
    reads it: the word travels in place of its booleans, through the
    lookup (one gather) and the aggregate's sort (one payload where each
    boolean input would be two); a word's validity (the lookup's match)
    masks each of its booleans."""

    def __init__(self, word: Expression, bit: int):
        super().__init__([word])
        self.bit = bit
        self._dataType = T.BOOLEAN
        self.resolved = True

    def sql_string(self):
        return f"flag({self.children[0].sql_string()}, {self.bit})"

    def do_columnar_eval(self, ctx, cols):
        w = cols[0]
        return DeviceColumn(
            T.BOOLEAN, w.validity & (((w.data >> (2 * self.bit + 1)) & 1) == 1),
            data=((w.data >> (2 * self.bit)) & 1) == 1)


@dataclasses.dataclass
class _Hoist:
    """What the fused program computes once per build row instead of once
    per fact row (plan time, one an aggregate variant).  ``agg`` reads the
    top join's columns followed by ``extra``: per dimension, in lookup
    order, the carried values of its ``derived`` expressions (``_pack``:
    the booleans' words, which ``agg`` reads through ``_Flag``, then the
    others) and then, where it has ``coded`` group keys, their code
    (``_group_code``).  Both lists
    are bound to that dimension's build schema.  ``keys`` says where each
    of the original aggregate's group keys comes from in ``agg``'s output:
    ``("key", i)``, or ``("code", i, dim, k)``: ``coded[k]`` of ``dim``
    at the row the code in key ``i`` names."""
    agg: object
    derived: list
    coded: list
    keys: list


def _hoist_plan(agg, joins, probe_width: int) -> Optional[_Hoist]:
    """The ``_Hoist`` of ``agg`` above the chain ``joins`` (innermost
    first), or None where nothing is worth computing per build row.

    Hoisted: a group key of string type read from one dimension alone (a
    code per dimension stands for the tuple of them), and a largest
    sub-expression of a key or an aggregate input that reads one
    dimension alone, reads a string of it, and is not a string (``d_day_name
    = 'Sunday'``).  Not under ANSI (a build row no fact row matches must
    not raise) nor with fused stage ops, which read the joined columns."""
    from spark_rapids_tpu.plan.pruning import _refs, rebind

    if agg.ansi or agg.pre_ops:
        return None
    # where every column of each join's output comes from: ("p", i) a
    # column of the fact side, (j, i) column i of dimension j's build side
    src = [("p", i) for i in range(probe_width)]
    for j, join in enumerate(joins):
        nl = len(src)
        out = join.emit if join.emit is not None else range(
            nl + len(join._build_child().output.fields))
        src = [src[o] if o < nl else (j, o - nl) for o in out]
    top = joins[-1].output.fields
    derived = [[] for _ in joins]
    derived_refs = [[] for _ in joins]      # the non-boolean ones
    word_refs = [[] for _ in joins]         # one a _FLAGS_A_WORD booleans
    n_flags = [0 for _ in joins]
    coded = [[] for _ in joins]

    def one_dim(e):
        """(dim, {top ordinal: build ordinal}) of an expression that
        reads one dimension of an INNER join alone and one of its
        strings, else None (a LEFT join's unmatched row reads nulls, and
        an expression of nulls need not be null)."""
        refs = _refs([e])
        if not refs or any(src[o][0] == "p" for o in refs):
            return None
        dims = {src[o][0] for o in refs}
        if len(dims) != 1 or not any(
                isinstance(top[o].dataType, T.StringType) for o in refs):
            return None
        dim = dims.pop()
        if joins[dim].join_type != JoinType.INNER:
            return None
        return dim, {o: src[o][1] for o in refs}

    def hoist(e):
        if e is None or isinstance(e, BoundReference):
            return e
        found = None if isinstance(e.dataType, T.StringType) else one_dim(e)
        if found is not None:
            dim, m = found
            name = f"__build{dim}_{len(derived[dim])}"
            derived[dim].append(rebind(e, m))
            if not isinstance(e.dataType, T.BooleanType):
                derived_refs[dim].append(BoundReference(-1, e.dataType, True,
                                                        name))
                return derived_refs[dim][-1]
            word, bit = divmod(n_flags[dim], _FLAGS_A_WORD)
            n_flags[dim] += 1
            if bit == 0:
                word_refs[dim].append(BoundReference(
                    -1, T.INT, True, f"__flags{dim}_{word}"))
            return _Flag(word_refs[dim][word], bit)
        kids = [hoist(c) for c in e.children]
        if all(a is b for a, b in zip(kids, e.children)):
            return e
        out = copy.copy(e)
        out.children = kids
        return out

    out_fields = agg.output.fields
    code_refs = {}
    grouping, key_fields, keys = [], [], []
    for p, g in enumerate(agg.grouping):
        found = one_dim(g) if isinstance(g.dataType, T.StringType) else None
        if found is None:
            keys.append(("key", len(grouping)))
            grouping.append(hoist(g))
            key_fields.append(out_fields[p])
            continue
        dim, m = found
        if dim not in code_refs:
            code_refs[dim] = (len(grouping), BoundReference(
                -1, T.INT, True, f"__code{dim}"))
            grouping.append(code_refs[dim][1])
            key_fields.append(T.StructField(f"__code{dim}", T.INT, True))
        keys.append(("code", code_refs[dim][0], dim, len(coded[dim])))
        coded[dim].append(rebind(g, m))
    aggs = [dataclasses.replace(a, child=hoist(a.child),
                                child2=hoist(a.child2))
            for a in agg.aggregates]
    if not any(derived) and not code_refs:
        return None
    # the extra columns' ordinals: per dimension, as _pack carries its
    # derived values (the words, then the others), then the code
    extra = []
    for dim in range(len(joins)):
        for ref in word_refs[dim] + derived_refs[dim]:
            ref.ordinal = len(top) + len(extra)
            extra.append(T.StructField(ref.sql_string(), ref.dataType, True))
        if dim in code_refs:
            code_refs[dim][1].ordinal = len(top) + len(extra)
            extra.append(key_fields[code_refs[dim][0]])
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec

    rewritten = TpuHashAggregateExec(
        grouping, aggs, agg.mode, agg.children[0],
        T.StructType(list(top) + extra),
        T.StructType(key_fields + list(out_fields[len(agg.grouping):])),
        agg.ansi)
    return _Hoist(rewritten, derived, coded, keys)


# process-unique tags for unfingerprintable agg variants (never reused,
# unlike id(), which the allocator recycles after GC); the lock makes
# the lazy pin-on-object init atomic — two concurrent collects sharing
# one agg must agree on the tag or the loser retraces forever
_PRIVATE_TAGS = itertools.count()
_PRIVATE_TAG_LOCK = threading.Lock()


class TpuJoinAggFusedExec(TpuExec):
    """agg(join(probe, build)) in (at most) three XLA programs;
    agg(join_k(... join_1(probe, build_1) ..., build_k)) of INNER joins
    in one."""

    EXTRA_METRICS = {"buildTime": "MODERATE"}

    def __init__(self, agg, join: _BaseTpuJoinExec, inner=()):
        """``join`` is the aggregate's child; ``inner`` the INNER
        broadcast joins beneath it, each the probe child of the one
        before, outermost first."""
        # the lookups' order: innermost first, the aggregate's child last
        self.joins = [*reversed(inner), join]
        super().__init__([self.joins[0].children[0]]
                         + [j.children[1] for j in self.joins])
        self.agg = agg
        self.join = join
        self._jit_cache = {}
        # None = unknown; True/False learned from the first build sides
        # and reused across collects of the same plan (device-cached scans
        # make repeat execution the hot path)
        self._build_unique: Optional[bool] = None
        # what the last probe took ("path=... build_cap=N"), for describe()
        self._last_probe: Optional[str] = None
        self._hoists = {}       # id(agg variant) -> (variant, _Hoist)

    @property
    def output(self):
        return self.agg.output

    def describe(self):
        took = "" if self._last_probe is None else " " + self._last_probe
        joins = " <- ".join(j.describe() for j in reversed(self.joins))
        return f"TpuJoinAggFused[{self.agg.describe()} <- {joins}]{took}"

    def _link(self):
        """Point the joins at the executed children: later plan passes
        rewrite ``self.children`` in place, and the unfused fallback and
        the build programs must run the rewritten subtrees, not the
        joins' stale private copies."""
        probe = self.children[0]
        for join, build in zip(self.joins, self.children[1:]):
            join.children = [probe, build]
            probe = join

    def _registry_scope(self):
        cached = getattr(self, "_reg_scope", False)
        if cached is not False:
            return cached
        join_scopes = [j._registry_scope() for j in reversed(self.joins)]
        agg_fp = self.agg._program_fp()
        scope = None
        if None not in join_scopes and agg_fp is not None:
            scope = (("joinagg",) + tuple(itertools.chain(*join_scopes))
                     + (agg_fp,))
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
        """The fused path reuses each join's build-sort program verbatim —
        including the broadcast-side stage-absorbed (pre_ops) variant —
        while the fused probe/materialize programs have data-dependent
        operand shapes (pair counts, uniqueness) and compile inline."""
        self._link()
        out = []
        for join in self.joins:
            out.extend(self._build_aot(join))
        return out

    def _build_aot(self, join):
        build_src, pre_ops, pre_schema = self._build_source(join)
        if pre_ops is None:
            return [p for p in join.aot_programs()
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

    @staticmethod
    def _build_source(join):
        """(exec to drive, stage ops to fuse into the build program, input
        schema) — absorbs BroadcastExchange(Stage(x)) into the build."""
        from spark_rapids_tpu.exec.basic import TpuStageExec
        from spark_rapids_tpu.exec.exchange import TpuBroadcastExchangeExec

        child = join._build_child()
        if isinstance(child, TpuBroadcastExchangeExec):
            inner = child.children[0]
            if (isinstance(inner, TpuStageExec) and not inner.ansi
                    and not inner._has_host_kernels()):
                return inner.children[0], inner.ops, inner.children[0].output
        return child, None, None

    def _build(self, join) -> Optional[_SortedBuildSide]:
        """One dimension's build side, sorted; None when it is too large
        for the fused path (the out-of-core join owns that size class)."""
        from spark_rapids_tpu.memory.spill import get_spill_framework

        fw = get_spill_framework()
        # broadcast-side stage absorption: drive the stage's CHILD and fuse
        # the project/filter ops into the build-sort program
        build_src, pre_ops, pre_schema = self._build_source(join)
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
            return None
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
            return join._prepare_build(build_batch, join.right_keys,
                                       pre_ops=pre_ops,
                                       in_schema=pre_schema)

    def _unique(self, builds) -> bool:
        """Are every dimension's build keys unique?  Asked of the sorted
        build sides themselves, once a plan, by a sort-free program each
        and ONE sync: a star join's first collect then takes the
        one-program path too, and never compiles or runs the general
        path's two sort-bearing programs."""
        if self._build_unique is None:
            dup = [self._cached("build_has_dup", _has_dup_key)(
                tuple(b.words), b.n_valid) for b in builds]
            got = sync_get(dup[0]) if len(dup) == 1 else sync_get(tuple(dup))
            self._build_unique = not any(bool(d) for d in
                                         ([got] if len(dup) == 1 else got))
        return self._build_unique

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        self._link()
        builds = []
        for join in self.joins:
            build = self._build(join)
            if build is None:
                # re-drive the build children (scans re-stream; device
                # cache makes it cheap)
                yield from self._fallback()
                return
            builds.append(build)

        probe_it = self.joins[0]._probe_child().execute_columnar()
        first = next(probe_it, None)
        if first is None:
            from spark_rapids_tpu.columnar.batch import empty_batch

            if not self.agg.grouping:
                yield self.agg._global_agg_empty()
            else:
                yield empty_batch(self.agg._output)
            return
        if not self._unique(builds) and len(builds) > 1:
            # a dimension's keys repeat: the pair expansion of each join,
            # unfused, for this plan
            probe_it.close()
            yield from self._fallback()
            return
        from spark_rapids_tpu.memory.retry import (
            TpuSplitAndRetryOOM,
            with_retry,
            with_retry_no_split,
        )
        from spark_rapids_tpu.memory.spill import get_spill_framework

        fw = get_spill_framework()
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
                                builds, piece, self.agg)):
                        yield self._count_output(out)
            return

        second = next(probe_it, None)
        if second is None:
            try:
                with self.metrics["opTime"].timed():
                    out = with_retry_no_split(
                        lambda: self._probe_agg_one(builds, first, self.agg))
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
                        lambda piece: self._probe_agg_one(builds, piece,
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
    def _probe_agg_one(self, builds, probe: ColumnarBatch,
                       agg) -> ColumnarBatch:
        if self._unique(builds):
            bump("joinagg_unique_probes")
            with span("srt.joinagg.unique"):
                return self._unique_probe_agg(builds, probe, agg)
        # one join (a chain with repeated keys ran unfused)
        build, = builds
        cap_b = build.words[0].shape[0]
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
        agg._launch_full_width(out_cap)
        cols, nrows = jitted(
            build.row_index,
            tuple(build.batch.columns[i] for i in join._b_sel),
            tuple(probe.columns[i] for i in join._p_sel),
            lo, counts, unmatched,
            jnp.int64(total), jnp.int64(out_rows))
        return self._finish(agg, cols, nrows)

    def _hoist(self, agg) -> Optional[_Hoist]:
        hit = self._hoists.get(id(agg))
        if hit is None:
            hit = self._hoists[id(agg)] = (agg, _hoist_plan(
                agg, self.joins, len(self.children[0].output.fields)))
        return hit[1]

    def _unique_probe_agg(self, builds, probe, agg) -> ColumnarBatch:
        """Unique-build fast path: every dimension's lookup (``_lookup``)
        + the aggregate in ONE program; no size sync (output capacity ==
        probe capacity).  The aggregate runs through its
        bounded-cardinality ladder (groups_cap) — the synced output row
        count is the overflow check."""
        schema = probe.schema
        left_outer = self.join.join_type == JoinType.LEFT_OUTER
        ansi = self.join.ansi
        hoist = self._hoist(agg)
        inner = agg if hoist is None else hoist.agg
        agg_fn = inner.detached_for_trace()._agg_fn   # no subtree capture
        # locals only: the registry keeps ``fn`` alive across queries
        links = [(j.left_keys, j._mat_slots, j._p_sel, j.output,
                  j._build_child().output) for j in self.joins]
        derived = [[] for _ in links] if hoist is None else hoist.derived
        coded = [[] for _ in links] if hoist is None else hoist.coded
        keys = None if hoist is None else hoist.keys
        # for the counters and describe() only: the registry shares ``fn``
        # among execs whose build sides differ in capacity, so the trace
        # asks the operand shapes itself and closes over nothing of them
        caps = [b.words[0].shape[0] for b in builds]
        lookups = ["mxu" if _use_mxu(c) else "vpu" for c in caps]
        matches = ["merge" if _takes_merge(c, probe.capacity) else "gather"
                   for c in caps]
        self._last_probe = (f"path=unique lookup={','.join(lookups)} "
                            f"match={','.join(matches)} "
                            f"build_cap={','.join(map(str, caps))}")

        def mk(groups_cap):
            def fn(bwords, row_index, n_valid, b_cols, p_cols, num_rows,
                   whole=(), more=()):
                # the first dimension's operands lead, under the names the
                # one-dimension program has always given them
                dims = ((bwords, row_index, n_valid, b_cols, whole),) + more
                b = ColumnarBatch(list(p_cols), num_rows, schema)
                cols, at_schema = list(p_cols), schema
                row_valid = b.row_mask
                extra, per_dim = [], []
                for (left_keys, slots, p_sel, out_schema, b_schema), \
                        (bwords, row_index, n_valid, b_cols, whole), \
                        der, cod in zip(links, dims, derived, coded):
                    ctx = EvalContext(
                        ColumnarBatch(cols, num_rows, at_schema), ansi=ansi)
                    key_cols = [k.eval_tpu(ctx) for k in left_keys]
                    valid = b.row_mask
                    for kc in key_cols:
                        valid = valid & kc.validity
                    qwords = _key_words_of(key_cols)
                    flags, codes, vals = [], [], []
                    if whole:
                        # once per build row: what the aggregate reads of
                        # this dimension alone
                        bctx = EvalContext(ColumnarBatch(
                            list(whole[0]), whole[1], b_schema), ansi=False)
                        flags = _pack([e.eval_tpu(bctx) for e in der])
                        vals = [e.eval_tpu(bctx) for e in cod]
                        codes = [_group_code(vals)] if vals else []
                    per_dim.append(vals)
                    found, bcols = _lookup(
                        bwords, row_index, n_valid,
                        tuple(b_cols) + tuple(flags) + tuple(codes),
                        qwords, valid)
                    n = len(b_cols)
                    cols = arranged(slots, [cols[i] for i in p_sel],
                                    bcols[:n])
                    extra.extend(bcols[n:])
                    at_schema = out_schema
                    if not left_outer:
                        row_valid = row_valid & found
                out, ngroups = agg_fn(tuple(cols) + tuple(extra), num_rows,
                                      row_valid=row_valid,
                                      groups_cap=groups_cap)
                if keys is None:
                    return out, ngroups
                # the group keys a code stands for, gathered for the
                # output groups alone from the rows the codes name
                nk = len(inner.grouping)
                decoded = []
                for how in keys:
                    if how[0] == "key":
                        decoded.append(out[how[1]])
                        continue
                    _, i, dim, k = how
                    code = out[i]
                    v = per_dim[dim][k]
                    g = v.gather(jnp.clip(code.data, 0, v.capacity - 1))
                    decoded.append(_mask_col(g, code.validity))
                return tuple(decoded) + tuple(out[nk:]), ngroups

            return fn

        dims = []
        for j, b in enumerate(builds):
            join = self.joins[j]
            whole = ()
            if derived[j] or coded[j]:
                whole = (tuple(b.batch.columns), jnp.int32(b.batch.num_rows))
            dims.append((tuple(b.words), b.row_index, b.n_valid,
                         tuple(b.batch.columns[i] for i in join._b_sel),
                         whole))
        args = (*dims[0][:4], tuple(probe.columns), jnp.int32(probe.num_rows),
                dims[0][4], tuple(dims[1:]))
        cap = probe.capacity
        tag = self._agg_tag(agg)

        def run(groups_cap):
            # one bump each a call of the fused program and a lookup in
            # it, by the payload lookup and the key match it took
            bump("joinagg_fused_lookups", len(builds))
            for lookup, match in zip(lookups, matches):
                bump("join_lookups_" + lookup)
                bump("join_matches_" + match)
            if groups_cap is None:
                inner._launch_full_width(cap)
            return self._cached(("uniq_agg", tag, groups_cap),
                                mk(groups_cap))(*args)

        B = inner._bounded_groups_cap(cap)
        if B:
            cols, nrows = run(B)
            n = int(nrows)
            while n > B:
                B2 = min(max(1 << (n - 1).bit_length(), B * 2), cap)
                inner._groups_cap_hint = B2
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
                        self.pre_agg._launch_full_width(b.capacity)
                    cols, count, ng = self._cached(
                        ("chain", with_agg, b.capacity, B2),
                        self._chain_fn(with_agg, B2))(*args)
                    n, g = (int(x) for x in sync_get((count, ng)))
                    if B2 is None:
                        break
                    B = B2
                return ColumnarBatch(list(cols), n, self.output)
            if with_agg:
                self.pre_agg._launch_full_width(b.capacity)
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
