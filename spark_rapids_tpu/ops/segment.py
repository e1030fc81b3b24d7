"""Segmented reductions/scans — the TPU groupby/window primitive.

Reference analog: cuDF's hash `Table.groupBy().aggregate()` kernels
(SURVEY.md §2.10 item 2).  TPU-first: group-by is sort-based — rows sorted by
key, equal-key runs become segments, and `jax.ops.segment_*` performs the
reduction in one pass.  Null/NaN semantics follow Spark SQL:

  * aggregates skip nulls; a group with zero valid inputs yields null
    (except count, which yields 0);
  * float min/max treat NaN as the greatest value (Spark total order).
"""
from __future__ import annotations

import threading
from typing import Tuple

import jax
import jax.numpy as jnp


def _starts_of(seg_ids):
    return jnp.concatenate([jnp.ones(1, jnp.bool_),
                            seg_ids[1:] != seg_ids[:-1]])


class SegBounds:
    """Boundary form of a SORTED segment-id array, bounded to B segments:
    ``starts[g]``/``ends[g]`` delimit segment g's row range.  B*log(cap)
    tiny gathers (binary search) replace every full-width scatter in the
    bounded aggregation path — on the v5e, a cap-wide scatter-add costs
    ~1.7s at 20M rows while cumsum+B-gathers cost ~90ms (round-5
    calibration)."""

    __slots__ = ("starts", "ends", "num")

    def __init__(self, seg_ids, num: int):
        gids = jnp.arange(num, dtype=seg_ids.dtype)
        self.starts = jnp.searchsorted(seg_ids, gids, side="left")
        self.ends = jnp.searchsorted(seg_ids, gids, side="right")
        self.num = num

    def gather_last(self, arr, fill):
        """arr value at each segment's last row (fill for empty)."""
        cap = arr.shape[0]
        idx = jnp.clip(self.ends - 1, 0, cap - 1)
        return jnp.where(self.ends > self.starts, arr[idx],
                         jnp.asarray(fill, arr.dtype))

    def gather_first(self, arr, fill):
        cap = arr.shape[0]
        idx = jnp.clip(self.starts, 0, cap - 1)
        return jnp.where(self.ends > self.starts, arr[idx],
                         jnp.asarray(fill, arr.dtype))

    def csum_diff(self, contrib):
        """Per-segment sum of contrib via one cumsum + 2B gathers.
        Exact for integers (wrap cancels); callers keep floats on the
        scatter path (global-magnitude cancellation)."""
        cs = jnp.cumsum(contrib)
        cap = contrib.shape[0]
        hi = cs[jnp.clip(self.ends - 1, 0, cap - 1)]
        lo_idx = self.starts - 1
        lo = jnp.where(lo_idx >= 0, cs[jnp.clip(lo_idx, 0, cap - 1)],
                       jnp.zeros((), cs.dtype))
        return jnp.where(self.ends > self.starts, hi - lo,
                         jnp.zeros((), cs.dtype))

    def counts(self, validity):
        return self.csum_diff(validity.astype(jnp.int64))


_M32 = 0xFFFFFFFF


class SegEnds:
    """End-row form of a SORTED segment-id array whose valid rows lead
    (``row_mask``): each segment's value is formed at its LAST row by
    scans alone, a cumsum less the cumsum at the previous segment's end,
    with no scatter (an int64 scatter-add over 2^24 rows costs ~2.2 s on
    the v5e).  A row that ends no segment holds a partial value; the
    caller moves every end row to its segment's slot with ONE sort over
    all of its outputs (exec/aggregate.py ``_compact_ends``).  Installed
    through ``bounds_scope`` with ``num`` = the row capacity: integer
    sums and counts take this form; min/max, first/last and folds have
    none (it lacks ``SegBounds``' gathers), nor have float sums."""

    __slots__ = ("is_end", "num")

    def __init__(self, seg_ids, row_mask):
        same_next = (seg_ids[1:] == seg_ids[:-1]) & row_mask[1:]
        self.is_end = row_mask & jnp.concatenate(
            [~same_next, jnp.ones(1, jnp.bool_)])
        self.num = seg_ids.shape[0]

    def nonneg_sum(self, x):
        """Per-segment sum of NON-NEGATIVE x, at each end row: its cumsum
        never decreases, so the exclusive cummax of the end rows' cumsums
        is the cumsum at the previous segment's end."""
        cs = jnp.cumsum(x)
        at_ends = jax.lax.cummax(jnp.where(self.is_end, cs,
                                           jnp.zeros((), cs.dtype)))
        return cs - jnp.concatenate([jnp.zeros(1, cs.dtype), at_ends[:-1]])

    def csum_diff(self, contrib):
        """Per-segment sum of any integer contrib, equal modulo 2^64 to a
        wrapping segment_sum: the sums of its two unsigned 32-bit halves,
        each below 2^63 for fewer than 2^31 rows."""
        if jnp.issubdtype(contrib.dtype, jnp.floating):
            raise NotImplementedError("a float sum has no end-row form")
        x = contrib.astype(jnp.int64)
        lo = self.nonneg_sum(x & _M32)
        hi = self.nonneg_sum((x >> 32) & _M32)
        return (lo + (hi << 32)).astype(contrib.dtype)

    def counts(self, validity):
        # int32 scans: exact below 2^31 rows, and native on the v5e
        return self.nonneg_sum(validity.astype(jnp.int32)).astype(jnp.int64)


def active_ends(num_segments: int):
    """The end-row form installed for ``num_segments``, else None."""
    b = _active_bounds(num_segments, None)
    return b if isinstance(b, SegEnds) else None


_BOUNDS_TLS = threading.local()


def _bounds_stack() -> list:
    st = getattr(_BOUNDS_TLS, "stack", None)
    if st is None:
        st = _BOUNDS_TLS.stack = []
    return st


class bounds_scope:
    """Trace-scoped segments mode: inside the scope, every segment
    primitive called with ``num_segments == bounds.num`` takes the
    boundary form (``SegBounds``) or the end-row form (``SegEnds``)
    instead of a full-width scatter.  Installed by the aggregate's
    ``_agg_fn`` around its evaluation so the ~40
    SEG call sites need no signature change.  The ambient stack is
    PER-THREAD: tracing is synchronous on its own thread, but concurrent
    collects and the AOT compile pool trace on different threads at the
    same time, and one query's bounds must never leak into another's
    trace (found by tpulint's module-state rule, ISSUE 9)."""

    def __init__(self, b: "SegBounds"):
        self.b = b

    def __enter__(self):
        _bounds_stack().append(self.b)
        return self.b

    def __exit__(self, *a):
        _bounds_stack().pop()


def _active_bounds(num_segments: int, bounds):
    if bounds is not None:
        return bounds
    st = _bounds_stack()
    if st and st[-1].num == num_segments:
        return st[-1]
    return None


def _scatter_at(rows_mask, seg_ids, values, num_segments: int, fill):
    """values at flagged rows -> their segment's slot (one scatter-set;
    flagged rows are one-per-segment so indices are distinct)."""
    idx = jnp.where(rows_mask, seg_ids, num_segments).astype(jnp.int32)
    return jnp.full(num_segments, fill, values.dtype).at[idx].set(
        values, mode="drop")


def seg_sum(values, validity, seg_ids, num_segments: int, bounds=None):
    bounds = _active_bounds(num_segments, bounds)
    contrib = jnp.where(validity, values, jnp.zeros_like(values))
    if num_segments == 1:
        # global reduction: plain tree-reduce, no scatter
        return (jnp.sum(contrib, keepdims=True),
                jnp.sum(validity.astype(jnp.int64), keepdims=True) > 0)
    if bounds is not None and (isinstance(bounds, SegEnds)
                               or not jnp.issubdtype(values.dtype,
                                                     jnp.floating)):
        # integer/decimal: cumsum-diff is exact (wrap cancels); floats
        # keep the scatter (cumsum-diff cancels across segments), which
        # the end-row form refuses
        return bounds.csum_diff(contrib), bounds.counts(validity) > 0
    s = jax.ops.segment_sum(contrib, seg_ids, num_segments=num_segments)
    cnt = jax.ops.segment_sum(validity.astype(jnp.int64), seg_ids,
                              num_segments=num_segments)
    return s, cnt > 0


def seg_count(validity, seg_ids, num_segments: int, bounds=None):
    bounds = _active_bounds(num_segments, bounds)
    if num_segments == 1:
        return jnp.sum(validity.astype(jnp.int64), keepdims=True)
    if bounds is not None:
        return bounds.counts(validity)
    return jax.ops.segment_sum(validity.astype(jnp.int64), seg_ids,
                               num_segments=num_segments)


def _seg_min_raw(v, seg_ids, num_segments: int, bounds=None):
    """Sorted-run min: re-sort within segments by value, pick segment
    starts, scatter to slots.  segment_min's scatter measured ~480ms at
    2M on TPU while sorts are near-free; associative_scan alternatives
    cost ~20s of XLA compile EACH (the round-4 compile hang), so this is
    the compile-cheap AND runtime-cheap form.  With bounds, the end
    scatter becomes B gathers at segment starts."""
    bounds = _active_bounds(num_segments, bounds)
    if num_segments == 1:
        return jnp.min(v, keepdims=True)
    fill = (jnp.asarray(jnp.inf, v.dtype)
            if jnp.issubdtype(v.dtype, jnp.floating)
            else jnp.asarray(jnp.iinfo(v.dtype).max, v.dtype))
    sv = jax.lax.sort((seg_ids, v), num_keys=2)[1]
    if bounds is not None:
        return bounds.gather_first(sv, fill)
    return _scatter_at(_starts_of(seg_ids), seg_ids, sv, num_segments,
                       fill)


def _seg_max_raw(v, seg_ids, num_segments: int, bounds=None):
    bounds = _active_bounds(num_segments, bounds)
    if num_segments == 1:
        return jnp.max(v, keepdims=True)
    fill = (jnp.asarray(-jnp.inf, v.dtype)
            if jnp.issubdtype(v.dtype, jnp.floating)
            else jnp.asarray(jnp.iinfo(v.dtype).min, v.dtype))
    sv = jax.lax.sort((seg_ids, v), num_keys=2)[1]
    if bounds is not None:
        return bounds.gather_last(sv, fill)
    starts = _starts_of(seg_ids)
    is_end = jnp.concatenate([starts[1:], jnp.ones(1, jnp.bool_)])
    return _scatter_at(is_end, seg_ids, sv, num_segments, fill)


def _seg_isum(v, seg_ids, num_segments: int, bounds=None):
    bounds = _active_bounds(num_segments, bounds)
    if num_segments == 1:
        return jnp.sum(v, keepdims=True)
    if bounds is not None:
        return bounds.csum_diff(v.astype(jnp.int64)).astype(v.dtype)
    return jax.ops.segment_sum(v, seg_ids, num_segments=num_segments)


def seg_min(values, validity, seg_ids, num_segments: int, is_float: bool,
            bounds=None):
    if is_float:
        nan = jnp.isnan(values)
        big = jnp.asarray(jnp.inf, values.dtype)
        v = jnp.where(validity & ~nan, values, big)
        m = _seg_min_raw(v, seg_ids, num_segments, bounds)
        valid_nonnan = _seg_isum(
            (validity & ~nan).astype(jnp.int32), seg_ids, num_segments,
            bounds) > 0
        any_valid = _seg_isum(
            validity.astype(jnp.int32), seg_ids, num_segments, bounds) > 0
        # all-NaN group -> NaN (NaN is greatest, min falls back to NaN
        # only when nothing else exists)
        m = jnp.where(valid_nonnan, m, jnp.asarray(jnp.nan, values.dtype))
        return m, any_valid
    if values.dtype == jnp.bool_:
        v = jnp.where(validity, values, True)
        m = _seg_min_raw(v.astype(jnp.int32), seg_ids,
                         num_segments, bounds).astype(jnp.bool_)
    else:
        big = jnp.asarray(jnp.iinfo(values.dtype).max, values.dtype)
        v = jnp.where(validity, values, big)
        m = _seg_min_raw(v, seg_ids, num_segments, bounds)
    any_valid = _seg_isum(validity.astype(jnp.int32), seg_ids,
                          num_segments, bounds) > 0
    return m, any_valid


def seg_max(values, validity, seg_ids, num_segments: int, is_float: bool,
            bounds=None):
    if is_float:
        nan = jnp.isnan(values)
        small = jnp.asarray(-jnp.inf, values.dtype)
        v = jnp.where(validity & ~nan, values, small)
        m = _seg_max_raw(v, seg_ids, num_segments, bounds)
        has_nan = _seg_isum(
            (validity & nan).astype(jnp.int32), seg_ids, num_segments,
            bounds) > 0
        any_valid = _seg_isum(
            validity.astype(jnp.int32), seg_ids, num_segments, bounds) > 0
        m = jnp.where(has_nan, jnp.asarray(jnp.nan, values.dtype), m)
        return m, any_valid
    if values.dtype == jnp.bool_:
        v = jnp.where(validity, values, False)
        m = _seg_max_raw(v.astype(jnp.int32), seg_ids,
                         num_segments, bounds).astype(jnp.bool_)
    else:
        small = jnp.asarray(jnp.iinfo(values.dtype).min, values.dtype)
        v = jnp.where(validity, values, small)
        m = _seg_max_raw(v, seg_ids, num_segments, bounds)
    any_valid = _seg_isum(validity.astype(jnp.int32), seg_ids,
                          num_segments, bounds) > 0
    return m, any_valid


def seg_first_index(seg_ids, row_mask, num_segments: int, bounds=None):
    """Index of the first row of each segment (for group-key extraction):
    rows are in segment order already, so the first VALID row index is
    the value at each segment start after a (seg, ~valid, iota) sort."""
    bounds = _active_bounds(num_segments, bounds)
    n = seg_ids.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    big = jnp.int32(n)
    _, inv_s, iota_s = jax.lax.sort(
        (seg_ids, (~row_mask).astype(jnp.int32), iota), num_keys=3)
    # a segment whose first sorted row is invalid has NO valid rows
    vals = jnp.where(inv_s == 0, iota_s, big)
    if bounds is not None:
        return bounds.gather_first(vals, big)
    return _scatter_at(_starts_of(seg_ids), seg_ids, vals,
                       num_segments, big)


# -- segmented scans (window running frames) --------------------------------

def _seg_scan(values, starts, combine):
    """Inclusive segmented scan: resets at rows where ``starts`` is True."""

    def op(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, combine(va, vb)), fa | fb

    out, _ = jax.lax.associative_scan(op, (values, starts))
    return out


def seg_scan_sum(values, validity, starts):
    """Segmented inclusive running sum via global cumsum minus the
    segment-base (cumsum/cummax lower to compact reduce-windows; a
    generic associative_scan costs ~20s of XLA compile per instance on
    TPU — round-4 finding).  Integer wrap cancels exactly in the
    subtraction; float running sums lose at most the usual cancellation
    (tests compare approximately)."""
    n = values.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    first = jax.lax.cummax(jnp.where(starts, iota, 0))

    def seg_csum(x):
        cs = jnp.cumsum(x)
        return cs - (cs[first] - x[first])

    contrib = jnp.where(validity, values, jnp.zeros_like(values))
    if jnp.issubdtype(contrib.dtype, jnp.floating):
        # the cumsum-difference cancels catastrophically when another
        # segment holds huge values; floats keep the exact segmented scan
        total = _seg_scan(contrib, starts, lambda a, b: a + b)
    else:
        total = seg_csum(contrib)   # integer wrap cancels exactly
    return total, seg_csum(validity.astype(jnp.int64))


def seg_scan_min(values, validity, starts, is_float: bool):
    if is_float:
        ident = jnp.asarray(jnp.inf, values.dtype)
        nan = jnp.isnan(values)
        v = jnp.where(validity & ~nan, values, ident)
        m = _seg_scan(v, starts, jnp.minimum)
        seen_nonnan = _seg_scan((validity & ~nan).astype(jnp.int32), starts,
                                lambda a, b: a + b) > 0
        m = jnp.where(seen_nonnan, m, jnp.asarray(jnp.nan, values.dtype))
        seen = _seg_scan(validity.astype(jnp.int32), starts,
                         lambda a, b: a + b) > 0
        return m, seen
    ident = jnp.asarray(jnp.iinfo(values.dtype).max, values.dtype)
    v = jnp.where(validity, values, ident)
    m = _seg_scan(v, starts, jnp.minimum)
    seen = _seg_scan(validity.astype(jnp.int32), starts,
                     lambda a, b: a + b) > 0
    return m, seen


def seg_scan_max(values, validity, starts, is_float: bool):
    if is_float:
        ident = jnp.asarray(-jnp.inf, values.dtype)
        nan = jnp.isnan(values)
        v = jnp.where(validity & ~nan, values, ident)
        m = _seg_scan(v, starts, jnp.maximum)
        seen_nan = _seg_scan((validity & nan).astype(jnp.int32), starts,
                             lambda a, b: a + b) > 0
        m = jnp.where(seen_nan, jnp.asarray(jnp.nan, values.dtype), m)
        seen = _seg_scan(validity.astype(jnp.int32), starts,
                         lambda a, b: a + b) > 0
        return m, seen
    ident = jnp.asarray(jnp.iinfo(values.dtype).min, values.dtype)
    v = jnp.where(validity, values, ident)
    m = _seg_scan(v, starts, jnp.maximum)
    seen = _seg_scan(validity.astype(jnp.int32), starts,
                     lambda a, b: a + b) > 0
    return m, seen


def seg_fold(values, validity, seg_ids, num_segments: int, op, identity,
             bounds=None):
    """Segmented fold for non-min/max/sum combines (bit_and/or/xor): the
    pair-scan segmented fold + one end scatter.  associative_scan costs
    ~20s of XLA compile per instance on TPU, acceptable for these rare
    aggregates."""
    bounds = _active_bounds(num_segments, bounds)
    v = jnp.where(validity, values, jnp.asarray(identity, values.dtype))
    starts = _starts_of(seg_ids)
    run = _seg_scan(v, starts, op)
    if bounds is not None:
        out = bounds.gather_last(run, identity)
        has = bounds.counts(validity) > 0
        return out, has
    is_end = jnp.concatenate([starts[1:], jnp.ones(1, jnp.bool_)])
    out = _scatter_at(is_end, seg_ids, run, num_segments,
                      jnp.asarray(identity, values.dtype))
    has = jax.ops.segment_sum(validity.astype(jnp.int32), seg_ids,
                              num_segments=num_segments) > 0
    return out, has
