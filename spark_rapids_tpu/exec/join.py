"""TPU joins — sort-based gather-map equi-joins.

Reference analog (SURVEY.md §2.4 Joins): GpuHashJoin / GpuShuffledHashJoinExec
/ GpuBroadcastHashJoinExec / JoinGatherer / AbstractGpuJoinIterator, where
cuDF produces gather maps that are materialized in size-bounded chunks.

TPU-first redesign: the build side is compacted (valid keys only) and sorted
by packed key words; probes binary-search it (vectorized multiword
searchsorted — log2(n) lexicographic compare rounds, all rows in parallel).
The gather-map materialization is the same two-index expansion cuDF uses
(probe index from searchsorted over the pair-count prefix sum, build index
by offset within the match run).  Everything is jitted; only the total pair
count syncs to host (to pick the output capacity bucket) — the exact analog
of the reference's JoinGatherer.getTotalRows sizing step.

A LEFT OUTER join whose valid build keys are unique (asked of each sorted
build side by a sort-free program) skips the expansion: its output is the
probe batch, columns passed through, beside the build columns looked up
at every probe row from one merge sort (``_lookup``, shared with the
fused star join).

Sort-merge join at the plan level is converted to this shuffled-sort join —
mirroring GpuSortMergeJoinMeta, which converts SMJ to shuffled-hash on GPU.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax
from spark_rapids_tpu.perfcounters import bump, span, sync_get, tpu_jit
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    DEFAULT_ROW_BUCKETS,
    DeviceColumn,
    round_up_bucket,
)
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.expr.base import (
    BoundReference,
    EvalContext,
    Expression,
)
from spark_rapids_tpu.ops import mxugather as MG
from spark_rapids_tpu.ops.filterops import compact_columns, gather_columns
from spark_rapids_tpu.ops.sortkeys import _column_key_words
from spark_rapids_tpu.plan.nodes import (
    JoinType,
    describe_emit,
    join_full_output,
)


# what jax calls a join program in a trace, by the first part of its
# registry key (the key itself stays as it is: it is fingerprinted)
_PROGRAM_NAMES = {"mat": "materialize"}


def _program_name(key) -> str:
    kind = key if isinstance(key, str) else key[0]
    return "join_" + _PROGRAM_NAMES.get(kind, kind)


def _lex_less(a_words: List[jax.Array], b_words: List[jax.Array],
              or_equal: bool) -> jax.Array:
    lt = jnp.zeros(a_words[0].shape, jnp.bool_)
    eq = jnp.ones(a_words[0].shape, jnp.bool_)
    for a, b in zip(a_words, b_words):
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return lt | eq if or_equal else lt


def _takes_merge(n: int, nq: int) -> bool:
    """Static: a build side of capacity ``n`` probed by ``nq`` rows takes
    the shared sort (``_merge_rank``, ``_merge_lookup``); smaller inputs
    the binary-search gather loop."""
    return n >= (1 << 14) or nq >= (1 << 14)


def _multiword_searchsorted(sorted_words: List[jax.Array], n_valid,
                            query_words: List[jax.Array],
                            side: str) -> jax.Array:
    """For each query row, the insertion point into the sorted build keys.

    Two strategies (perf-critical — the probe of every hash join):

    * merge-rank for large inputs: concat build+query words, ONE
      lax.sort, exclusive cumsum of build flags at query positions.
      lax.sort is a fused sorting network on TPU (~the cost of a few
      elementwise passes) while each binary-search step is a full-width
      gather; at 2M probe rows the gather loop measured ~800ms device
      time vs ~100ms for the shared sort (round-4 microbench).
    * the O(log n) gather loop for small inputs, where the sort's
      fixed cost would dominate.
    """
    n = sorted_words[0].shape[0]
    nq = query_words[0].shape[0]
    if _takes_merge(n, nq):
        return _merge_rank(sorted_words, n_valid, query_words, side)
    lo = jnp.zeros(nq, jnp.int32)
    hi = jnp.broadcast_to(n_valid.astype(jnp.int32), (nq,))
    steps = max(1, int(n).bit_length())
    for _ in range(steps):
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, n - 1)
        mid_words = [w[midc] for w in sorted_words]
        if side == "left":
            go_right = _lex_less(mid_words, query_words, or_equal=False)
        else:
            go_right = _lex_less(mid_words, query_words, or_equal=True)
        go_right = go_right & (mid < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


def _merge_rank(sorted_words: List[jax.Array], n_valid,
                query_words: List[jax.Array], side: str) -> jax.Array:
    """searchsorted via one shared sort: rank of each query among the
    valid sorted build keys.  Key layout per row:

      (invalid, word_0..word_k, tie) + iota payload

    where ``invalid`` pushes the build tail (rows >= n_valid) after every
    query and valid build row so they are never counted, and ``tie``
    orders a query before equal build keys for side=left (strict rank)
    or after them for side=right (inclusive rank)."""
    n = sorted_words[0].shape[0]
    nq = query_words[0].shape[0]
    b_inv = (jnp.arange(n, dtype=jnp.int32)
             >= n_valid.astype(jnp.int32)).astype(jnp.int32)
    q_inv = jnp.zeros(nq, jnp.int32)
    tie_b = jnp.full(n, 0 if side == "right" else 1, jnp.int32)
    tie_q = jnp.full(nq, 1 if side == "right" else 0, jnp.int32)
    words = [jnp.concatenate([b_inv, q_inv])]
    for sw, qw in zip(sorted_words, query_words):
        words.append(jnp.concatenate([sw, qw]))
    words.append(jnp.concatenate([tie_b, tie_q]))
    iota = jnp.arange(n + nq, dtype=jnp.int32)
    srt = jax.lax.sort(tuple(words) + (iota,), num_keys=len(words),
                       is_stable=False)
    pos = srt[-1]
    is_build = (pos < n).astype(jnp.int32)
    nb_before = jnp.cumsum(is_build) - is_build
    qpos = jnp.where(is_build == 1, nq, pos - n)
    return jnp.zeros(nq, jnp.int32).at[qpos].set(nb_before, mode="drop")


def _merge_lookup(sorted_words: List[jax.Array], n_valid,
                  query_words: List[jax.Array]
                  ) -> Tuple[jax.Array, jax.Array]:
    """(loc, matched) per query row from the shared sort alone: whether a
    valid sorted build key equals the query key, and that key's sorted
    position.  ``_merge_rank``'s sort and operands, with the tie
    ordering a build key BEFORE equal query keys, so in sorted order

      * a run of equal keys (``invalid`` included) holds a build key iff
        its FIRST element is one: one running max over the run starts
        carries that bit to every query of the run;
      * the number of build keys at or before a query, less one, is the
        position of the last build key <= it: the equal one where there
        is one (the last of them where the build keys repeat).

    No gather from the build side: ``words[loc] == query`` costs a
    full-width random gather a 32-bit word (~36 ms at 2^22 rows on a
    v5e, PERF.md), the compare of neighbours and the scan are sequential
    passes.  The build tail (rows >= n_valid: filtered and null keys)
    sorts after every query and never matches.  ``loc`` is meaningful
    only where ``matched``; both ride back to query order in the ONE
    scatter ``_merge_rank`` makes too."""
    n = sorted_words[0].shape[0]
    nq = query_words[0].shape[0]
    assert n + nq < (1 << 30)     # position and match bit share an int32
    b_inv = (jnp.arange(n, dtype=jnp.int32)
             >= n_valid.astype(jnp.int32)).astype(jnp.int32)
    words = [jnp.concatenate([b_inv, jnp.zeros(nq, jnp.int32)])]
    for sw, qw in zip(sorted_words, query_words):
        words.append(jnp.concatenate([sw, qw]))
    words.append(jnp.concatenate([jnp.zeros(n, jnp.int32),
                                  jnp.ones(nq, jnp.int32)]))
    iota = jnp.arange(n + nq, dtype=jnp.int32)
    srt = jax.lax.sort(tuple(words) + (iota,), num_keys=len(words),
                       is_stable=False)
    pos = srt[-1]
    is_build = (pos < n).astype(jnp.int32)
    differs = jnp.zeros(n + nq - 1, jnp.bool_)
    for k in srt[:-2]:            # invalid and the key words, not the tie
        differs = differs | (k[1:] != k[:-1])
    new_run = jnp.concatenate([jnp.ones(1, jnp.bool_), differs])
    # lax.cummax, never associative_scan (see _slots_to_probe_rows)
    head = jax.lax.cummax(jnp.where(new_run, 2 * iota + is_build, 0))
    loc = jnp.maximum(jnp.cumsum(is_build) - 1, 0)
    qpos = jnp.where(is_build == 1, nq, pos - n)
    packed = jnp.zeros(nq, jnp.int32).at[qpos].set(
        2 * loc + (head & 1), mode="drop")
    return packed >> 1, (packed & 1) == 1


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
    """The unique-build lookup, chosen by the build side's CAPACITY:
    small tables ride the MXU one-hot contraction (ops/mxugather.py),
    larger ones the VPU gathers."""
    return cap_b <= MG.MAX_TABLE_ROWS


def _lookup(bwords, row_index, n_valid, b_cols, qwords, valid):
    """Traced, one build side of unique keys: (found, the payload
    ``b_cols`` at every probe row, null where nothing matched).

    Past the binary search's sizes (``_takes_merge``) the probe's one
    merge sort says whether a probe row matched and at which SORTED
    build position (``_merge_lookup``): no key word is gathered to
    compare it.  The payload is then fetched by that position from build
    columns permuted into key order, where the permute is the smaller
    gather (build capacity <= probe capacity); else through
    ``row_index[loc]`` from the columns as they are."""
    cap_b, cap_p = bwords[0].shape[0], qwords[0].shape[0]
    # small build tables ride the MXU one-hot gather: a VPU random
    # gather costs ~300ms per column at 20M probe rows while the fused
    # one_hot@table contraction is ~5ms (ops/mxugather.py)
    use_mxu = _use_mxu(cap_b)

    def at(table, idx):
        return MG.mxu_gather(table, idx) if use_mxu else table[idx]

    merge = _takes_merge(cap_b, cap_p)
    if merge:
        loc, matched = _merge_lookup(list(bwords), n_valid, qwords)
        found = valid & matched
    else:
        lo = _multiword_searchsorted(list(bwords), n_valid, qwords, "left")
        loc = jnp.clip(lo, 0, cap_b - 1)
        eq = jnp.ones(lo.shape, jnp.bool_)
        for w, q in zip(bwords, qwords):
            eq = eq & (at(w, loc) == q)
        found = valid & (lo < n_valid) & eq
    if merge and cap_b <= cap_p:
        # payload in key order: one build-sized gather a column, then
        # ``loc`` indexes it directly
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
    return found, bcols


def _slots_to_probe_rows(excl, counts, out_cap: int) -> jax.Array:
    """probe_row[j] for every output pair slot j: scatter each matched
    probe row's index at its first slot, then a running-max scan.
    Replaces jnp.searchsorted(offsets, j) — the binary-search gather loop
    measured ~700ms device time at 2M rows while scatter+scan is ~80ms
    (round-4 microbench); scans and sorts are near-free on TPU."""
    n = counts.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # excl is strictly increasing over count>0 rows -> distinct slots
    scatter_idx = jnp.where(counts > 0, excl, out_cap).astype(jnp.int64)
    m = jnp.full(out_cap, -1, jnp.int32).at[scatter_idx].set(
        iota, mode="drop")
    # lax.cummax lowers to a compact reduce-window; the generic
    # associative_scan's unrolled log-depth graph took ~100s of XLA
    # compile time at 1M rows on TPU (round-4 hang)
    pr = jax.lax.cummax(m)
    return jnp.clip(pr, 0, jnp.int32(max(n - 1, 0)))


def _key_words_of(key_cols: List[DeviceColumn]) -> List[jax.Array]:
    words: List[jax.Array] = []
    for kc in key_cols:
        words.extend(_column_key_words(kc))
    return words


class _SortedBuildSide:
    """Build-side state: valid-key rows sorted by key words."""

    def __init__(self, words, row_index, n_valid, batch):
        self.words = words            # sorted key words (capacity,)
        self.row_index = row_index    # original row per sorted pos
        self.n_valid = n_valid        # device scalar
        self.batch = batch            # the materialized build batch


_SUB_PARTITION_SEED = 100407   # decorrelated from exchange partitioning


class _MaterializedExec(TpuExec):
    """Leaf exec replaying already-materialized spillable batches (the
    per-bucket children of a sub-partitioned join)."""

    def __init__(self, spillables, schema: T.StructType):
        super().__init__([])
        self._spillables = spillables
        self._schema = schema

    @property
    def output(self):
        return self._schema

    def execute_columnar(self):
        for s in self._spillables:
            s.pin()
            try:
                b = s.get_batch()
            finally:
                s.unpin()
            yield b


class _EmitLayout:
    """What a join gathers and where it lands (plan/pruning.py): ``emit``
    names the ordinals of ``left ++ right`` the parent reads, in output
    order (None = all).  Only those are gathered, plus what an INNER
    join's condition reads, which is dropped again once it is applied
    (``_apply_condition``).  Keys are read from the child batches by the
    build and probe programs and are gathered only if emitted."""

    def _set_emit(self, left, right, join_type, condition, emit,
                  output_schema):
        from spark_rapids_tpu.plan.pruning import rebind

        nl = len(left.output.fields)
        self.emit = None if emit is None else list(emit)
        self._full_output = output_schema if emit is None else \
            join_full_output(left.output, right.output, join_type)
        out = list(range(len(self._full_output.fields))) if emit is None \
            else self.emit
        mat = list(out)
        self._mat_condition = condition
        if condition is not None and emit is not None \
                and join_type == JoinType.INNER:
            reads = {r.ordinal for r in condition.collect(
                lambda x: isinstance(x, BoundReference))}
            mat += sorted(reads - set(out))
            self._mat_condition = rebind(
                condition, {o: i for i, o in enumerate(mat)})
        self._n_emit = len(out)
        # child ordinals gathered, ascending, and where each materialized
        # column sits in ``probe gathers ++ build gathers``
        self._p_sel = sorted({o for o in mat if o < nl})
        self._b_sel = sorted({o - nl for o in mat if o >= nl})
        slot = {o: i for i, o in enumerate(self._p_sel)}
        slot.update({nl + o: len(self._p_sel) + i
                     for i, o in enumerate(self._b_sel)})
        self._mat_slots = [slot[o] for o in mat]
        self._mat_schema = output_schema if len(mat) == len(out) else \
            T.StructType([self._full_output.fields[o] for o in mat])



def arranged(slots, lcols, bcols) -> list:
    """Gathered probe and build columns in materialized order."""
    cols = list(lcols) + list(bcols)
    return [cols[s] for s in slots]


class _BaseTpuJoinExec(_EmitLayout, TpuExec):
    # GpuShuffledHashJoinExec metric set: build + stream/probe time
    EXTRA_METRICS = {"buildTime": "MODERATE",
                     "joinTime": "MODERATE"}

    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: List[Expression], right_keys: List[Expression],
                 join_type: JoinType, condition: Optional[Expression],
                 output_schema: T.StructType, ansi: bool = False,
                 sub_partition_bytes: int = 1 << 30,
                 emit: Optional[List[int]] = None):
        super().__init__([left, right])
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.join_type = join_type
        self.condition = condition
        self._output = output_schema
        self.ansi = ansi
        self.sub_partition_bytes = sub_partition_bytes
        self._jit_cache = {}
        # what the last probe batch took ("lookup" or "pairs"), for
        # describe()
        self._path: Optional[str] = None
        self._set_emit(left, right, join_type, condition, emit,
                       output_schema)

    def with_full_output(self) -> "_BaseTpuJoinExec":
        """This join emitting all of ``left ++ right`` (for a consumer
        that takes no emit list and selects columns itself)."""
        if self.emit is None:
            return self
        return type(self)(
            self.children[0], self.children[1], self.left_keys,
            self.right_keys, self.join_type, self.condition,
            self._full_output, self.ansi,
            sub_partition_bytes=self.sub_partition_bytes)

    def _registry_scope(self):
        """Fingerprint prefix identifying this join's program family (the
        compilecache registry shares programs across exec instances with
        identical scope + local key), or None when an expression is not
        safely fingerprintable."""
        cached = getattr(self, "_reg_scope", False)
        if cached is not False:
            return cached
        from spark_rapids_tpu.compilecache.keys import (
            conf_fp,
            exprs_fp,
            schema_fp,
        )

        lk = exprs_fp(self.left_keys)
        rk = exprs_fp(self.right_keys)
        cond = exprs_fp(
            [self.condition] if self.condition is not None else [])
        scope = None
        if lk is not None and rk is not None and cond is not None:
            scope = ("join", type(self).__name__, self.join_type.value,
                     lk, rk, cond,
                     schema_fp(self.children[0].output),
                     schema_fp(self.children[1].output),
                     schema_fp(self._output),
                     None if self.emit is None else tuple(self.emit),
                     bool(self.ansi), conf_fp())
        self._reg_scope = scope
        return scope

    def _cached_jit(self, key, builder, unsafe=False, **jit_kw):
        if key not in self._jit_cache:
            from spark_rapids_tpu.compilecache.registry import (
                cached_jit_program,
            )

            scope = None if unsafe else self._registry_scope()
            self._jit_cache[key] = cached_jit_program(
                None if scope is None else scope + (key,), builder,
                label=f"{type(self).__name__}:{key}",
                name=_program_name(key), **jit_kw)
        return self._jit_cache[key]

    @property
    def output(self):
        return self._output

    def describe(self):
        keys = ", ".join(f"{l.sql_string()}={r.sql_string()}"
                         for l, r in zip(self.left_keys, self.right_keys))
        took = "" if self._path is None else f" path={self._path}"
        return (f"{self.node_name} {self.join_type.value} [{keys}]"
                + describe_emit(self.emit, self._full_output) + took)

    # -- build side -----------------------------------------------------
    def _prepare_build(self, batch: ColumnarBatch, keys: List[Expression],
                       pre_ops=None, in_schema=None) -> _SortedBuildSide:
        """Sort the build side by packed key words (ONE program).

        ``pre_ops`` fuses a broadcast-side project/filter stage into this
        program in selection-mask mode: filtered rows sort to the invalid
        tail and are never probed — the stage costs no extra launch and no
        compaction scatter."""
        with span("srt.join.build"):
            schema = in_schema or batch.schema
            fn = self._build_fn(schema, keys, pre_ops)
            if pre_ops is None:
                jitted = self._cached_jit(self._build_key(schema), fn)
                words, row_index, n_valid = jitted(tuple(batch.columns),
                                                   jnp.int32(batch.num_rows))
                return _SortedBuildSide(words, row_index, n_valid, batch)
            from spark_rapids_tpu.compilecache.keys import (
                schema_fp,
                stage_ops_fp,
            )

            ops_fp = stage_ops_fp(pre_ops)
            jitted = self._cached_jit(
                ("build_preops", ops_fp, schema_fp(schema)), fn,
                unsafe=ops_fp is None)
            words, row_index, n_valid, bcols = jitted(
                tuple(batch.columns), jnp.int32(batch.num_rows))
            out_batch = ColumnarBatch(list(bcols), batch.num_rows,
                                      self._build_child().output)
            return _SortedBuildSide(words, row_index, n_valid, out_batch)

    def _build_key(self, schema):
        from spark_rapids_tpu.compilecache.keys import schema_fp

        return ("build", schema_fp(schema))

    def _build_fn(self, schema, keys, pre_ops=None):
        """The build-sort program body — shared by runtime and AOT.
        Captures only locals (never ``self``): the registry keeps these
        closures alive across queries and a self-reference would pin the
        whole exec subtree."""
        key_cols_src = keys
        ansi = self.ansi

        def fn(cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            ctx = EvalContext(b, ansi=ansi)
            mask = b.row_mask
            for op in (pre_ops or []):
                b, mask = op.apply_masked(ctx, b, mask)
            ctx.batch = b
            key_cols = [k.eval_tpu(ctx) for k in key_cols_src]
            valid = mask
            for kc in key_cols:
                valid = valid & kc.validity
            words = _key_words_of(key_cols)
            # sort valid rows first by (is_invalid, words...)
            inv = (~valid).astype(jnp.int64)
            iota = jnp.arange(b.capacity, dtype=jnp.int32)
            out = jax.lax.sort(tuple([inv] + words + [iota]),
                               num_keys=1 + len(words), is_stable=True)
            sorted_words = list(out[1:-1])
            row_index = out[-1]
            n_valid = jnp.sum(valid.astype(jnp.int32))
            if pre_ops is None:
                return sorted_words, row_index, n_valid
            return sorted_words, row_index, n_valid, tuple(b.columns)

        return fn

    # -- probe ----------------------------------------------------------
    def _probe_fn(self, schema):
        """The probe-search program body — shared by runtime and AOT.
        Locals only; no ``self`` capture (see _build_fn)."""
        left_keys = self.left_keys
        ansi = self.ansi

        def fn(bwords, n_valid, cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            ctx = EvalContext(b, ansi=ansi)
            key_cols = [k.eval_tpu(ctx) for k in left_keys]
            valid = b.row_mask
            for kc in key_cols:
                valid = valid & kc.validity
            qwords = _key_words_of(key_cols)
            lo = _multiword_searchsorted(list(bwords), n_valid, qwords, "left")
            hi = _multiword_searchsorted(list(bwords), n_valid, qwords, "right")
            counts = jnp.where(valid, hi - lo, 0)
            total = jnp.sum(counts.astype(jnp.int64))
            unmatched = b.row_mask & (counts == 0)
            n_unmatched = jnp.sum(unmatched.astype(jnp.int64))
            return lo, counts, total, unmatched, n_unmatched

        return fn

    def _probe_key(self, schema):
        from spark_rapids_tpu.compilecache.keys import schema_fp

        return ("probe", schema_fp(schema))

    def _probe_counts(self, build: _SortedBuildSide, batch: ColumnarBatch):
        with span("srt.join.probe"):
            jitted = self._cached_jit(self._probe_key(batch.schema),
                                      self._probe_fn(batch.schema))
            return jitted(tuple(build.words), build.n_valid,
                          tuple(batch.columns), jnp.int32(batch.num_rows))

    # -- unique build side: lookup instead of pairs ----------------------
    def _unique_build(self, build: _SortedBuildSide) -> bool:
        """Are the valid build keys unique?  Asked of every build side
        afresh (this node rebuilds each collect) by a sort-free program
        and ONE sync; null and filtered rows sort past ``n_valid`` and
        never count."""
        def has_dup(bwords, n_valid):
            # a function of this program's own: tpu_jit names it in
            # place, and the fused join's program is ``_has_dup_key``
            return _has_dup_key(bwords, n_valid)

        with span("srt.join.unique"):
            dup = self._cached_jit("has_dup", has_dup)(
                tuple(build.words), build.n_valid)
            return not bool(sync_get(dup))

    def _lookup_fn(self, schema):
        """The lookup program body: the build columns of ``_b_sel`` at
        every probe row, null where its key matched nothing.  Locals
        only; no ``self`` capture (see _build_fn)."""
        left_keys = self.left_keys
        ansi = self.ansi

        def fn(bwords, row_index, n_valid, b_cols, cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            ctx = EvalContext(b, ansi=ansi)
            key_cols = [k.eval_tpu(ctx) for k in left_keys]
            valid = b.row_mask
            for kc in key_cols:
                valid = valid & kc.validity
            _, bcols = _lookup(bwords, row_index, n_valid, b_cols,
                               _key_words_of(key_cols), valid)
            return tuple(bcols)

        return fn

    def _lookup_unique(self, build: _SortedBuildSide,
                       probe: ColumnarBatch) -> ColumnarBatch:
        """LEFT OUTER against unique build keys: the output is the probe
        batch itself, its columns passed through ungathered, beside the
        build's columns looked up at every probe row.  One program, and
        no size sync: the row count is the probe's."""
        from spark_rapids_tpu.compilecache.keys import schema_fp

        with span("srt.join.lookup"):
            bump("join_lookups_unique")
            jitted = self._cached_jit(("lookup", schema_fp(probe.schema)),
                                      self._lookup_fn(probe.schema))
            bcols = jitted(tuple(build.words), build.row_index,
                           build.n_valid,
                           tuple(build.batch.columns[i] for i in self._b_sel),
                           tuple(probe.columns), jnp.int32(probe.num_rows))
        lcols = [probe.columns[i] for i in self._p_sel]
        return ColumnarBatch(arranged(self._mat_slots, lcols, bcols),
                             probe.num_rows, self._mat_schema)

    # -- materialization (gather maps -> output batch) -------------------
    @staticmethod
    def materialize_pairs(bwords_row_index, b_cols, p_cols, lo, counts,
                          unmatched, total, nrows, out_cap: int,
                          with_unmatched_probe: bool):
        """Traced gather-map expansion: (probe, build) pair columns for the
        matched pairs [+ null-extended unmatched probe rows].  Pure function
        of device operands + static (out_cap, with_unmatched_probe) so it
        can be inlined into a consumer's program (join->agg fusion)."""
        n = counts.shape[0]
        offsets = jnp.cumsum(counts.astype(jnp.int64))
        excl = offsets - counts.astype(jnp.int64)
        j = jnp.arange(out_cap, dtype=jnp.int64)
        probe_row = _slots_to_probe_rows(excl, counts, out_cap)
        k = j - excl[probe_row]
        build_pos = lo[probe_row].astype(jnp.int64) + k
        build_cap = bwords_row_index.shape[0]
        build_row = bwords_row_index[
            jnp.clip(build_pos, 0, build_cap - 1).astype(jnp.int32)]
        in_pairs = j < total
        probe_idx = jnp.where(in_pairs, probe_row, 0)
        if with_unmatched_probe:
            # unmatched probe rows appended after the pairs
            um_positions = jnp.cumsum(unmatched.astype(jnp.int64)) - 1
            um_slot = total + um_positions
            scatter_to = jnp.where(unmatched, um_slot,
                                   out_cap).astype(jnp.int64)
            probe_idx_full = jnp.zeros(out_cap, jnp.int32).at[
                jnp.clip(scatter_to, 0, out_cap)].set(
                jnp.arange(n, dtype=jnp.int32), mode="drop")
            probe_idx = jnp.where(in_pairs, probe_row, probe_idx_full)
        row_valid = j < nrows
        lcols = gather_columns(probe_idx, row_valid, list(p_cols))
        bcols = gather_columns(
            jnp.where(in_pairs, build_row, 0), row_valid & in_pairs,
            list(b_cols))
        return lcols, bcols

    def _materialize(self, build: _SortedBuildSide, probe: ColumnarBatch,
                     lo, counts, total_host: int, unmatched,
                     with_unmatched_probe: bool, unmatched_host: int):
        with span("srt.join.materialize"):
            out_rows = total_host + (
                unmatched_host if with_unmatched_probe else 0)
            out_cap = round_up_bucket(max(out_rows, 1), DEFAULT_ROW_BUCKETS)

            def fn(bwords_row_index, b_cols, p_cols, lo, counts, unmatched,
                   total, nrows):
                return _BaseTpuJoinExec.materialize_pairs(
                    bwords_row_index, b_cols, p_cols, lo, counts, unmatched,
                    total, nrows, out_cap, with_unmatched_probe)

            bump("join_rows_materialized", out_rows)
            jitted = self._cached_jit(
                ("mat", out_cap, with_unmatched_probe), fn)
            lcols, bcols = jitted(
                build.row_index,
                tuple(build.batch.columns[i] for i in self._b_sel),
                tuple(probe.columns[i] for i in self._p_sel),
                lo, counts, unmatched,
                jnp.int64(total_host), jnp.int64(out_rows))
            return lcols, bcols, out_rows

    def _semi_anti(self, probe: ColumnarBatch, counts, anti: bool):
        schema = probe.schema   # never capture the device batch itself
        p_sel = self._p_sel

        def fn(cols, counts, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            keep = (counts == 0) if anti else (counts > 0)
            keep = keep & b.row_mask
            out, cnt = compact_columns(keep, [b.columns[i] for i in p_sel])
            return tuple(out), cnt

        from spark_rapids_tpu.compilecache.keys import schema_fp

        jitted = self._cached_jit(("semi", anti, schema_fp(probe.schema)),
                                  fn)
        out, cnt = jitted(tuple(probe.columns), counts,
                          jnp.int32(probe.num_rows))
        # int(cnt) is irreducible: the compacted row count labels the
        # output batch and nothing else in this path syncs to fold it into
        return ColumnarBatch(arranged(self._mat_slots, out, []), int(cnt),
                             self._output)

    # -- driver ----------------------------------------------------------
    @staticmethod
    def _concat_or_empty(batches, schema) -> ColumnarBatch:
        if not batches:
            from spark_rapids_tpu.columnar.batch import empty_batch

            return empty_batch(schema)
        return (batches[0] if len(batches) == 1
                else ColumnarBatch.concat(batches))

    def _build_child(self) -> TpuExec:
        return self.children[1]

    def _probe_child(self) -> TpuExec:
        return self.children[0]

    # -- plan-time AOT enumeration (compilecache/aot.py) -----------------
    def aot_programs(self):
        """Build-sort program (always enumerable when the build side's
        shape is static) and the probe-search program (enumerable when
        every key packs to one sort-key word, so the build-words operand
        shape is predictable).  The pair-materialization program is NOT
        enumerable: its output capacity is the runtime pair count."""
        from spark_rapids_tpu.compilecache.aot import (
            AotProgram,
            batch_caps,
            concat_caps,
            dummy_batch_args,
            dummy_columns,
            single_word_keys,
        )
        from spark_rapids_tpu.compilecache.registry import registry_enabled

        scope = self._registry_scope()
        if scope is None or not registry_enabled():
            return []
        out = []
        bchild, pchild = self._build_child(), self._probe_child()
        bschema = bchild.output
        bcaps = concat_caps(bchild)  # build side concats whole
        bcap = bcaps[0] if bcaps else None
        if bcap is not None:
            key = self._build_key(bschema)
            fn = self._build_fn(bschema, self.right_keys)

            def b_args(_cap=bcap, _schema=bschema):
                return [dummy_batch_args(_schema, _cap)]

            out.append(AotProgram(
                scope + (key,),
                lambda _fn=fn, _name=_program_name(key): (
                    tpu_jit(_fn, _name), None),
                b_args, f"join-build:{self.describe()[:40]}"))
        pcaps = batch_caps(pchild)
        if bcap is not None and pcaps \
                and single_word_keys(self.right_keys):
            pschema = pchild.output
            key = self._probe_key(pschema)
            fn = self._probe_fn(pschema)
            nwords = len(self.right_keys)

            def p_args(_bcap=bcap, _n=nwords, _schema=pschema,
                       _caps=tuple(pcaps)):
                import jax.numpy as jnp

                from spark_rapids_tpu.compilecache.aot import (
                    abstract_array,
                    abstract_scalar,
                )

                sets = []
                for c in _caps:
                    cols = dummy_columns(_schema, c)
                    if cols is None:
                        continue
                    bwords = tuple(abstract_array((_bcap,), jnp.int64)
                                   for _ in range(_n))
                    sets.append((bwords, abstract_scalar(jnp.int32),
                                 cols, abstract_scalar(jnp.int32)))
                return sets

            out.append(AotProgram(
                scope + (key,),
                lambda _fn=fn, _name=_program_name(key): (
                    tpu_jit(_fn, _name), None),
                p_args, f"join-probe:{self.describe()[:40]}"))
        return out

    # -- sub-partitioning (GpuSubPartitionHashJoin analog) ----------------
    def _sub_partition(self, spillables, keys, n_parts: int, side: str,
                       schema, fw):
        """Hash-bucket rows of spillable ``spillables`` into n_parts
        spillable lists.  Partition ids are computed ONCE per batch; the
        per-bucket compactions reuse them."""
        from spark_rapids_tpu.ops.hashing import spark_partition_ids

        ansi = self.ansi   # locals only: closures outlive the exec

        def ids_fn(cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            ctx = EvalContext(b, ansi=ansi)
            key_cols = [k.eval_tpu(ctx) for k in keys]
            return spark_partition_ids(key_cols, n_parts,
                                       seed=_SUB_PARTITION_SEED)

        def slice_fn(cols, ids, num_rows, pid):
            b = ColumnarBatch(list(cols), num_rows, schema)
            keep = (ids == pid) & b.row_mask
            out, cnt = compact_columns(keep, b.columns)
            return tuple(out), cnt

        # side in the cache key: build and probe close over different key
        # expressions and schemas
        from spark_rapids_tpu.compilecache.keys import schema_fp

        sfp = schema_fp(schema)
        ids_j = self._cached_jit(("subpart_ids", n_parts, side, sfp),
                                 ids_fn)
        slice_j = self._cached_jit(("subpart_slice", n_parts, side, sfp),
                                   slice_fn)
        buckets = [[] for _ in range(n_parts)]
        for s in spillables:
            s.pin()
            try:
                b = s.get_batch()
                ids = ids_j(tuple(b.columns), jnp.int32(b.num_rows))
                for pid in range(n_parts):
                    cols, cnt = slice_j(tuple(b.columns), ids,
                                        jnp.int32(b.num_rows),
                                        jnp.int32(pid))
                    n = int(cnt)
                    if n:
                        buckets[pid].append(
                            fw.track(ColumnarBatch(list(cols), n, schema)))
            finally:
                s.unpin()
            s.close()
        return buckets

    def _execute_sub_partitioned(self, build_spillables,
                                 total_bytes: int) -> Iterator[ColumnarBatch]:
        """Build side exceeds the goal: hash both sides into buckets and
        join bucket-by-bucket so only ~1/P of the build is live at once."""
        from spark_rapids_tpu.memory.spill import get_spill_framework

        fw = get_spill_framework()
        n_parts = 1
        while n_parts * self.sub_partition_bytes < total_bytes:
            n_parts <<= 1
        n_parts = max(2, n_parts)
        bschema = self._build_child().output
        pschema = self._probe_child().output
        build_buckets = self._sub_partition(build_spillables,
                                            self.right_keys, n_parts,
                                            "build", bschema, fw)
        del build_spillables
        probe_buckets = self._sub_partition(
            [fw.track(b) for b in self._probe_child().execute_columnar()],
            self.left_keys, n_parts, "probe", pschema, fw)
        try:
            for pid in range(n_parts):
                if not build_buckets[pid] and not probe_buckets[pid]:
                    continue
                sub = TpuShuffledSymmetricHashJoinExec(
                    _MaterializedExec(probe_buckets[pid], pschema),
                    _MaterializedExec(build_buckets[pid], bschema),
                    self.left_keys, self.right_keys, self.join_type,
                    self.condition, self._output, self.ansi,
                    sub_partition_bytes=1 << 62,  # buckets never re-partition
                    emit=self.emit)
                for out in sub.execute_columnar():
                    self._path = sub._path
                    yield self._count_output(out)
                for s in build_buckets[pid] + probe_buckets[pid]:
                    s.close()
                build_buckets[pid] = []
                probe_buckets[pid] = []
        finally:
            # an abandoned generator (limit above the join) must not leave
            # tracked handles registered for the session
            for pid in range(n_parts):
                for s in build_buckets[pid] + probe_buckets[pid]:
                    s.close()

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        jt = self.join_type
        if jt == JoinType.RIGHT_OUTER:
            yield from self._execute_right_outer()
            return
        from spark_rapids_tpu.memory.retry import with_retry
        from spark_rapids_tpu.memory.spill import get_spill_framework

        fw0 = get_spill_framework()
        # track build batches as they stream in so the spill framework can
        # shed them during ingest (the oversized-build case is exactly when
        # that matters)
        build_spill = []
        total_build_bytes = 0
        try:
            for b in self._build_child().execute_columnar():
                total_build_bytes += b.nbytes()
                build_spill.append(fw0.track(b))
        except BaseException:
            for s in build_spill:
                s.close()
            raise
        if (total_build_bytes > self.sub_partition_bytes and self.left_keys
                and jt != JoinType.CROSS):
            yield from self._execute_sub_partitioned(build_spill,
                                                     total_build_bytes)
            return
        for s in build_spill:
            s.pin()
        try:
            build_batch = self._concat_or_empty(
                [s.get_batch() for s in build_spill],
                self._build_child().output)
        finally:
            for s in build_spill:
                s.unpin()
                s.close()
        del build_spill
        with self.metric("buildTime").timed():
            build = self._prepare_build(build_batch, self.right_keys)
        # LEFT OUTER (RIGHT OUTER arrives swapped) against unique build
        # keys outputs exactly the probe rows: a lookup, no pairs
        lookup = (jt == JoinType.LEFT_OUTER and self.condition is None
                  and self._unique_build(build))
        matched_build_any = None
        if jt == JoinType.FULL_OUTER:
            matched_build_any = jnp.zeros(build_batch.capacity, jnp.bool_)
        fw = get_spill_framework()

        def probe_one(probe: ColumnarBatch):
            """Per-probe-batch join; re-runnable and probe-splittable (the
            reference splits the stream side on SplitAndRetryOOM; FULL
            OUTER's coverage update is an idempotent OR)."""
            nonlocal matched_build_any
            self._path = "lookup" if lookup else "pairs"
            if lookup:
                return (self._lookup_unique(build, probe)
                        if probe.num_rows else None)
            lo, counts, total, unmatched, n_um = self._probe_counts(
                build, probe)
            if jt == JoinType.LEFT_SEMI:
                return self._semi_anti(probe, counts, anti=False)
            if jt == JoinType.LEFT_ANTI:
                return self._semi_anti(probe, counts, anti=True)
            with_um = jt in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER)
            # ONE host round trip for both sizing scalars (the seed synced
            # total and n_um separately, an extra round trip on every
            # probe batch of qb_left_join); semi/anti
            # return above without paying the total sync at all
            from spark_rapids_tpu.perfcounters import sync_get

            total_host, um_host = (int(x)
                                   for x in sync_get((total, n_um)))
            if not with_um:
                um_host = 0
            if jt == JoinType.FULL_OUTER:
                matched_build_any = matched_build_any | \
                    self._covered_build_rows(build, lo, counts)
            if total_host + um_host == 0:
                return None
            lcols, bcols, nrows = self._materialize(
                build, probe, lo, counts, total_host, unmatched,
                with_um, um_host)
            out = ColumnarBatch(arranged(self._mat_slots, lcols, bcols), nrows,
                                self._mat_schema)
            return self._apply_condition(out)

        for probe in self._probe_child().execute_columnar():
            with self.metric("joinTime").timed():
                outs = list(with_retry(fw.track(probe), probe_one))
            for out in outs:
                if out is not None:
                    yield self._count_output(out)
        if jt == JoinType.FULL_OUTER:
            tail = self._unmatched_build_tail(build_batch, build,
                                              matched_build_any)
            if tail is not None:
                yield self._count_output(tail)

    def _covered_build_rows(self, build: _SortedBuildSide, lo, counts):
        """bool per original build row: appeared in some pair (diff-array)."""
        def fn(row_index, lo, counts):
            n = row_index.shape[0]
            diff = jnp.zeros(n + 1, jnp.int32)
            has = counts > 0
            start = jnp.where(has, lo, n)
            end = jnp.where(has, lo + counts, n)
            diff = diff.at[start].add(1, mode="drop")
            diff = diff.at[end].add(-1, mode="drop")
            covered_sorted = jnp.cumsum(diff[:-1]) > 0
            out = jnp.zeros(n, jnp.bool_).at[row_index].set(
                covered_sorted, mode="drop")
            return out

        return self._cached_jit("covered", fn)(build.row_index, lo, counts)

    def _unmatched_build_tail(self, build_batch, build, matched_any):
        schema = build_batch.schema   # never capture the device batch
        b_sel = self._b_sel

        def fn(cols, matched, num_rows):
            b = ColumnarBatch(list(cols), num_rows, schema)
            keep = b.row_mask & ~matched
            out, cnt = compact_columns(keep, [b.columns[i] for i in b_sel])
            return tuple(out), cnt

        out, cnt = self._cached_jit("build_tail", fn)(
            tuple(build_batch.columns), matched_any,
            jnp.int32(build_batch.num_rows))
        n = int(cnt)
        if n == 0:
            return None
        # null left side
        pfields = self._probe_child().output.fields
        lfields = [pfields[i] for i in self._p_sel]
        lcols = []
        cap = build_batch.capacity
        for f in lfields:
            if isinstance(f.dataType, T.StringType):
                lcols.append(DeviceColumn(f.dataType,
                                          jnp.zeros(cap, jnp.bool_),
                                          chars=jnp.zeros((cap, 8), jnp.uint8),
                                          lengths=jnp.zeros(cap, jnp.int32)))
            else:
                lcols.append(DeviceColumn(
                    f.dataType, jnp.zeros(cap, jnp.bool_),
                    data=jnp.zeros(cap, T.storage_dtype(f.dataType))))
        return ColumnarBatch(arranged(self._mat_slots, lcols, out), n,
                             self._output)

    def _execute_right_outer(self):
        """RIGHT OUTER = LEFT OUTER with sides swapped, emitting this
        join's columns in this join's order (right cols as they are, left
        cols nullable): the swap costs no reordering of its own."""
        nl = len(self.children[0].output.fields)
        nr = len(self.children[1].output.fields)
        out = range(nl + nr) if self.emit is None else self.emit
        swapped = TpuShuffledSymmetricHashJoinExec(
            self.children[1], self.children[0],
            self.right_keys, self.left_keys,
            JoinType.LEFT_OUTER, self.condition,
            self._output, self.ansi,
            sub_partition_bytes=self.sub_partition_bytes,
            emit=[nr + o if o < nl else o - nl for o in out])
        for b in swapped.execute_columnar():
            self._path = swapped._path
            yield self._count_output(b)

    def _apply_condition(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Filter materialized pairs by an INNER join's condition; the
        columns gathered for the condition alone are not compacted."""
        if self.condition is None or self.join_type != JoinType.INNER:
            return batch
        mat_schema, cond, ansi = (self._mat_schema, self._mat_condition,
                                  self.ansi)
        n_emit = self._n_emit

        def fn(cols, num_rows):
            b = ColumnarBatch(list(cols), num_rows, mat_schema)
            ctx = EvalContext(b, ansi=ansi)
            pred = cond.eval_tpu(ctx)
            keep = pred.data & pred.validity & b.row_mask
            out, cnt = compact_columns(keep, b.columns[:n_emit])
            return tuple(out), cnt

        jitted = self._cached_jit("cond", fn)
        out, cnt = jitted(tuple(batch.columns), jnp.int32(batch.num_rows))
        return ColumnarBatch(list(out), int(cnt), self._output)


class TpuShuffledSymmetricHashJoinExec(_BaseTpuJoinExec):
    """Shuffled join (post-exchange).  Name mirrors the reference's newer
    GpuShuffledSymmetricHashJoinExec; algorithm is the sorted-build probe."""


class TpuBroadcastHashJoinExec(_BaseTpuJoinExec):
    """Join against a broadcast build side (small table).  Single-process:
    the build child is materialized whole, exactly like the broadcast table
    the reference collects; on a mesh the build batch is replicated to every
    device (parallel/bcast)."""


class TpuCartesianProductExec(_EmitLayout, TpuExec):
    """CROSS join: index-arithmetic expansion (GpuCartesianProductExec)."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 output_schema: T.StructType,
                 condition: Optional[Expression] = None, ansi: bool = False,
                 emit: Optional[List[int]] = None):
        super().__init__([left, right])
        self._output = output_schema
        self.condition = condition
        self.join_type = JoinType.INNER  # for _apply_condition reuse
        self.ansi = ansi
        self._jit_cache = {}
        self._set_emit(left, right, JoinType.INNER, condition, emit,
                       output_schema)

    _cached_jit = _BaseTpuJoinExec._cached_jit
    _apply_condition = _BaseTpuJoinExec._apply_condition

    def _registry_scope(self):
        cached = getattr(self, "_reg_scope", False)
        if cached is not False:
            return cached
        from spark_rapids_tpu.compilecache.keys import (
            conf_fp,
            exprs_fp,
            schema_fp,
        )

        cond = exprs_fp(
            [self.condition] if self.condition is not None else [])
        scope = None
        if cond is not None:
            scope = ("cartesian", cond,
                     schema_fp(self.children[0].output),
                     schema_fp(self.children[1].output),
                     schema_fp(self._output),
                     None if self.emit is None else tuple(self.emit),
                     bool(self.ansi), conf_fp())
        self._reg_scope = scope
        return scope

    @property
    def output(self):
        return self._output

    def describe(self):
        return self.node_name + describe_emit(self.emit, self._full_output)

    def execute_columnar(self):
        right_batches = list(self.children[1].execute_columnar())
        if not right_batches:
            return
        rbatch = (right_batches[0] if len(right_batches) == 1
                  else ColumnarBatch.concat(right_batches))
        for lb in self.children[0].execute_columnar():
            total = lb.num_rows * rbatch.num_rows
            if total == 0:
                continue
            out_cap = round_up_bucket(total, DEFAULT_ROW_BUCKETS)

            def fn(lcols, rcols, nright, total):
                j = jnp.arange(out_cap, dtype=jnp.int64)
                li = (j // nright).astype(jnp.int32)
                ri = (j % nright).astype(jnp.int32)
                valid = j < total
                lo = gather_columns(li, valid, list(lcols))
                ro = gather_columns(ri, valid, list(rcols))
                return tuple(lo + ro)

            jitted = self._cached_jit(("cart", out_cap), fn)
            nlc = len(self._p_sel)
            cols = jitted(tuple(lb.columns[i] for i in self._p_sel),
                          tuple(rbatch.columns[i] for i in self._b_sel),
                          jnp.int64(rbatch.num_rows), jnp.int64(total))
            out = ColumnarBatch(
                arranged(self._mat_slots, cols[:nlc], cols[nlc:]), total,
                self._mat_schema)
            yield self._count_output(self._apply_condition(out))


class _ReplayExec(TpuExec):
    """Re-emits batches already materialized by the adaptive planner.

    Batches arrive as SPILLABLE handles (tracked while the runtime
    decision was pending, so an oversized build side can shed to host/disk
    instead of pinning HBM) and are closed once replayed."""

    def __init__(self, handles, output_schema):
        super().__init__([])
        self._handles = handles
        self._output = output_schema

    @property
    def output(self):
        return self._output

    def describe(self):
        return f"Replay[{len(self._handles)} batches]"

    def execute_columnar(self):
        for h in self._handles:
            yield h.get_batch()
            h.close()
        self._handles = []


def _logical_bytes(batches) -> int:
    """Row-weighted bytes (padding capacity excluded)."""
    total = 0
    for b in batches:
        cap = max(b.capacity, 1)
        total += int(sum(c.nbytes() for c in b.columns)
                     * (b.num_rows / cap))
    return total


class TpuAdaptiveJoinExec(TpuExec):
    """AQE runtime join-strategy switch (GpuCustomShuffleReaderExec /
    AQE re-optimization analog, SURVEY.md §2.2).

    Wraps a planned shuffled join whose children are exchanges.  At
    EXECUTION time the build side below its exchange materializes first;
    if its measured bytes fall under spark.sql.autoBroadcastJoinThreshold
    the join re-plans itself as a broadcast join with BOTH exchanges
    elided (runtime statistics beating the static planner — the point of
    AQE); otherwise the shuffled plan runs with the materialized batches
    replayed into its exchange, so nothing is computed twice."""

    def __init__(self, shuffled: "TpuShuffledSymmetricHashJoinExec",
                 threshold: int):
        super().__init__(list(shuffled.children))
        self.shuffled = shuffled
        self.threshold = threshold
        self.decision: Optional[str] = None

    @property
    def output(self):
        return self.shuffled.output

    def inner_execs(self):
        return (self.shuffled,)

    def describe(self):
        d = f" decided={self.decision}" if self.decision else ""
        return (f"TpuAdaptiveJoin(threshold={self.threshold})"
                f"[{self.shuffled.describe()}]{d}")

    def execute_columnar(self):
        from spark_rapids_tpu.memory.spill import get_spill_framework

        left_ex, right_ex = self.shuffled.children
        build_inner = right_ex.children[0]
        fw = get_spill_framework()
        handles = []
        size = 0
        for b in build_inner.execute_columnar():
            size += _logical_bytes([b])
            handles.append(fw.track(b))
        if 0 <= self.threshold and size <= self.threshold:
            self.decision = f"broadcast({size}B)"
            bj = TpuBroadcastHashJoinExec(
                left_ex.children[0], _ReplayExec(handles,
                                                 build_inner.output),
                self.shuffled.left_keys, self.shuffled.right_keys,
                self.shuffled.join_type, self.shuffled.condition,
                self.shuffled.output, self.shuffled.ansi,
                sub_partition_bytes=self.shuffled.sub_partition_bytes,
                emit=self.shuffled.emit)
            self.metrics.update(bj.metrics)
            yield from bj.execute_columnar()
            return
        self.decision = f"shuffled({size}B)"
        # the inner join is the operator that runs: its metrics are this
        # node's, as the broadcast branch's are
        self.metrics.update(self.shuffled.metrics)
        # the replay child is single-shot (handles close as they re-emit):
        # restore the real build subtree afterwards so a REPEATED execute
        # of this plan re-materializes instead of replaying closed handles
        # (round-5 on-chip finding: the second collect of a 20M-row qb
        # joined an EMPTY build side and silently dropped every match)
        right_ex.children[0] = _ReplayExec(handles, build_inner.output)
        try:
            yield from self.shuffled.execute_columnar()
        finally:
            right_ex.children[0] = build_inner
