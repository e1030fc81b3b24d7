"""Program-key fingerprints — the cache-key anatomy.

A registry key must capture EVERYTHING a stage function's trace depends on:
a collision returns another stage's executable and silently corrupts
results, so keys err on the side of including too much (a spurious
difference only costs a hit).  Every key is built from:

  * the expression list (SQL string + result type per node — literals print
    their values, so constant-folding differences key apart),
  * the input/output schemas (name, type, nullability per field),
  * static mode flags (ansi, aggregate mode, join type, frame, ...) passed
    by the call site,
  * the ambient conf fingerprint (sorted settings) — conf knobs are read at
    trace time (ansi, groups-cap, ...), so two sessions with different
    settings never share an executable.

Expressions that close over arbitrary Python state (UDFs, host-kernel
callbacks) are NOT fingerprintable: two different lambdas can print the
same SQL.  ``exprs_fp`` returns None for those and the call site falls
back to per-instance jit caching (correct, just not shared).
"""
from __future__ import annotations

import hashlib
from typing import Iterable, Optional

from spark_rapids_tpu import types as T


def fingerprint(*parts) -> str:
    """Stable digest of an arbitrary (repr-able) part tuple."""
    h = hashlib.sha1(repr(parts).encode("utf-8", "replace"))
    return h.hexdigest()


def schema_fp(schema: Optional[T.StructType]):
    """Schema fingerprint: (name, type) per field.  Nullability is
    deliberately EXCLUDED: materialized batches upgrade plan-declared
    nullable=False fields to True, traced programs never read the flag
    (validity vectors always exist; output nullability comes from the
    expressions), and keying on it would make every plan-time AOT key
    miss its runtime twin for non-nullable inputs."""
    if schema is None:
        return None
    return tuple((f.name, str(f.dataType)) for f in schema.fields)


# expressions whose trace bakes ambient per-instance/per-batch state that
# sql_string() cannot capture (row_offset, global current-file, ...)
_UNSAFE_EXPR_CLASSES = frozenset({
    "MonotonicallyIncreasingID", "SparkPartitionID", "InputFileName",
    "InputFileBlockStart", "InputFileBlockLength", "Rand", "Uuid",
})


def _expr_unsafe(e) -> bool:
    """True when the expression's trace depends on Python state its SQL
    string cannot capture: python UDF callables, host-kernel callbacks
    (jax.pure_callback closures), seeded nondeterministic streams
    (rand/uuid bake their seed and row offset at trace time), and
    ambient-state readers (monotonically_increasing_id, input_file_name)."""
    if callable(getattr(e, "fn", None)):
        return True
    if getattr(e, "is_host_kernel", False):
        return True
    if type(e).__name__ in _UNSAFE_EXPR_CLASSES:
        return True
    if hasattr(e, "captured_micros"):
        # current_date()/current_timestamp() capture the wall clock at
        # construction and bake it into the trace as a constant; sharing
        # the executable would freeze the first query's clock
        return True
    for c in getattr(e, "children", []) or []:
        if _expr_unsafe(c):
            return True
    return False


def exprs_fp(exprs: Optional[Iterable]):
    """Fingerprint parts for an expression list, or None when any
    expression is not safely fingerprintable (caller must then keep a
    per-instance jit instead of sharing through the registry)."""
    parts = []
    for e in exprs or []:
        if e is None:
            parts.append(None)
            continue
        if _expr_unsafe(e):
            return None
        try:
            sql = e.sql_string()
        except Exception:
            return None
        try:
            dt = str(e.dataType)
        except Exception:
            dt = type(e).__name__
        # deterministic numeric parameters that sql_string may not print
        # (hash seeds, anywhere in the tree) are part of the identity
        parts.append((type(e).__name__, sql, dt, _nested_seeds(e)))
    return tuple(parts)


def _nested_seeds(e, acc=None):
    acc = acc if acc is not None else []
    seed = getattr(e, "seed", None)
    if isinstance(seed, int):
        acc.append((type(e).__name__, seed))
    for c in getattr(e, "children", []) or []:
        _nested_seeds(c, acc)
    return tuple(acc)


def conf_fp() -> str:
    """Fingerprint of the ambient execution conf (config.get_conf()) —
    trace-time conf reads (ansi, smallGroupsCap, ...) make the
    settings part of the program identity."""
    from spark_rapids_tpu.config import get_conf

    settings = get_conf().settings
    return fingerprint(tuple(sorted((str(k), str(v))
                                    for k, v in settings.items())))


def window_fns_fp(functions) -> Optional[tuple]:
    """Fingerprint parts for a WindowFunction list (plan/nodes.py)."""
    parts = []
    for wf in functions or []:
        child_fp = exprs_fp([wf.child] if wf.child is not None else [])
        if child_fp is None and wf.child is not None:
            return None
        parts.append((wf.func,
                      child_fp,
                      getattr(wf, "result_name", None),
                      str(getattr(wf, "result_type", None)),
                      getattr(wf, "offset", None),
                      repr(getattr(wf, "default", None)),
                      getattr(wf, "buckets", None),
                      bool(getattr(wf, "ignore_nulls", False))))
    return tuple(parts)


def aggs_fp(aggregates) -> Optional[tuple]:
    """Fingerprint parts for an AggregateExpression list."""
    parts = []
    for a in aggregates or []:
        kids = [a.child] if a.child is not None else []
        if getattr(a, "child2", None) is not None:
            kids.append(a.child2)
        kfp = exprs_fp(kids)
        if kfp is None and kids:
            return None
        parts.append((a.func, kfp, a.result_name,
                      str(getattr(a, "result_type", None)),
                      tuple(getattr(a, "args", ()) or ())))
    return tuple(parts)


def stage_ops_fp(ops) -> Optional[tuple]:
    """Fingerprint parts for a _StageOp list (exec/basic.py)."""
    parts = []
    for op in ops or []:
        efp = exprs_fp(list(getattr(op, "exprs", []) or [])
                       + ([op.condition]
                          if getattr(op, "condition", None) is not None
                          else []))
        if efp is None:
            return None
        parts.append((type(op).__name__, efp))
    return tuple(parts)
