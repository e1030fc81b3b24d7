"""Column pruning at plan time — Catalyst's ``ColumnPruning`` for an engine
that is its own front end.

Reference analog: upstream, the plugin is handed Catalyst's physical plan
AFTER ColumnPruning, so a join only ever sees the columns its query reads.
``DataFrame`` here builds physical nodes directly with references bound by
ordinal, so the rule is this engine's to run: one top-down walk computes
the set of columns each node's parent reads, and the nodes where a column
costs device work honour it — scans emit only the read columns, joins
gather only the read columns (``_BaseJoin.emit``), and below an exchange,
sort or window whose child can do neither a projection of bare references
is inserted (executed by selecting column objects, no program).

Contract of the walk: ``_prune(node, required)`` returns the narrowed node
and a map from the node's OLD output ordinals to the new ones, whose keys
hold at least ``required``.  With every column required the map is the
identity, which is what lets a node kind the walk does not know (Generate,
Expand, Union, writes, anything new) simply require all of its children's
columns: correct by default, never by enumeration.  Nothing of the input is
mutated: a changed node is a copy (``with_new_children`` / the narrowing
constructors) and a rebound expression is copied along the path to the
reference, so the user's ``DataFrame.plan`` stays whole for its next query
and for the CPU oracle, which runs it unpruned.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
from typing import Dict, FrozenSet, Iterable, List, Optional, Tuple

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.base import BoundReference, Expression
from spark_rapids_tpu.plan import nodes as PN

_Map = Dict[int, int]


def prune_columns(plan: PN.SparkPlan) -> PN.SparkPlan:
    """``plan`` with every column nobody reads dropped where it is made.
    A plan that reads all of its columns comes back as the same object."""
    dropped = [0]
    out, _ = _prune(plan, frozenset(range(len(plan.output.fields))), dropped)
    if dropped[0]:
        PC.bump("plan_columns_pruned", dropped[0])
    return out


# -- expressions ---------------------------------------------------------

def _opaque(e: Expression) -> bool:
    """True when ``e`` holds sub-expressions outside ``children`` (a lambda
    body bound against an extended schema) or reads ambient state: its
    references cannot be listed, so its node reads every column."""
    from spark_rapids_tpu.compilecache.keys import _expr_unsafe

    if _expr_unsafe(e):
        return True

    def walk(x) -> bool:
        for k, v in vars(x).items():
            if k == "children":
                continue
            vs = v if isinstance(v, (list, tuple)) else (v,)
            if any(isinstance(i, Expression) for i in vs):
                return True
        return any(walk(c) for c in x.children)

    return walk(e)


def _refs(exprs: Iterable[Optional[Expression]]) -> Optional[FrozenSet[int]]:
    """The ordinals the expressions read, or None when one is opaque."""
    out = set()
    for e in exprs:
        if e is None:
            continue
        if _opaque(e):
            return None
        out.update(r.ordinal for r in
                   e.collect(lambda x: isinstance(x, BoundReference)))
    return frozenset(out)


def rebind(e: Optional[Expression], m: _Map) -> Optional[Expression]:
    """``e`` with every reference moved to ``m[ordinal]``: copies along the
    paths that change, ``e`` itself where none does."""
    if e is None:
        return None
    if isinstance(e, BoundReference):
        if m[e.ordinal] == e.ordinal:
            return e
        out = copy.copy(e)
        out.ordinal = m[e.ordinal]
        return out
    kids = [rebind(c, m) for c in e.children]
    if all(a is b for a, b in zip(kids, e.children)):
        return e
    out = copy.copy(e)
    out.children = kids
    return out


def _rebind_all(exprs, m: _Map):
    return [rebind(e, m) for e in exprs]


def _rebind_orders(orders, m: _Map):
    return [(rebind(e, m), spec) for e, spec in orders]


def _same(new: list, old: list) -> bool:
    return len(new) == len(old) and all(a is b for a, b in zip(new, old))


# -- helpers ---------------------------------------------------------------

def _identity(n: int) -> _Map:
    return {i: i for i in range(n)}


def _width(dt: T.DataType) -> int:
    try:
        return T.storage_dtype(dt).itemsize
    except TypeError:
        return 1 << 20      # strings, arrays, structs: never the narrowest


def _keep(required: FrozenSet[int], fields) -> List[int]:
    """The ordinals to keep, ascending; a node of which nothing is read
    keeps its narrowest column, so a batch still carries its row count."""
    if required:
        return sorted(required)
    return [min(range(len(fields)), key=lambda i: _width(fields[i].dataType))]


def _select(child: PN.SparkPlan, keep: List[int]) -> PN.Project:
    fields = child.output.fields
    return PN.Project([BoundReference(i, fields[i].dataType,
                                      fields[i].nullable,
                                      name=fields[i].name) for i in keep],
                      child)


def _prune_child(child, required, dropped, force: bool = False):
    """Prune ``child``; with ``force`` (the parent moves every column it is
    given: exchange, sort, window) what the child could not drop itself is
    dropped by a projection of bare references above it."""
    new, m = _prune(child, required, dropped)
    n_out = len(new.output.fields)
    if force and n_out:
        keep = _keep(frozenset(m[o] for o in required), new.output.fields)
        if len(keep) < n_out:
            dropped[0] += n_out - len(keep)
            pos = {o: i for i, o in enumerate(keep)}
            return _select(new, keep), {o: pos[n] for o, n in m.items()
                                        if n in pos}
    return new, m


def _all_of(node) -> FrozenSet[int]:
    return frozenset(range(len(node.output.fields)))


# -- the walk ------------------------------------------------------------

def _prune(node: PN.SparkPlan, required: FrozenSet[int],
           dropped) -> Tuple[PN.SparkPlan, _Map]:
    for kind, fn in _RULES:
        if isinstance(node, kind):
            return fn(node, required, dropped)
    return _prune_unknown(node, required, dropped)


def _prune_unknown(node, required, dropped):
    """A node kind the walk does not know reads all of its children."""
    kids = [_prune(c, _all_of(c), dropped)[0] for c in node.children]
    if not _same(kids, node.children):
        node = node.with_new_children(kids)
    return node, _identity(len(node.output.fields))


def _prune_scan(node, required, dropped):
    fields = node.output.fields
    if isinstance(node, PN.FileSourceScan) \
            and node.fmt not in ("parquet", "orc"):
        # text formats parse by position or by the whole record
        return node, _identity(len(fields))
    keep = _keep(required, fields)
    if len(keep) == len(fields):
        return node, _identity(len(fields))
    dropped[0] += len(fields) - len(keep)
    return node.narrowed(keep), {o: i for i, o in enumerate(keep)}


def _prune_project(node: PN.Project, required, dropped):
    n = len(node.exprs)
    # an expression nobody reads is dropped, as Catalyst drops it, unless
    # it is non-deterministic
    keep = sorted(set(required)
                  | {i for i, e in enumerate(node.exprs) if _opaque(e)})
    if not keep:
        keep = _keep(frozenset(), node.output.fields)
    exprs = [node.exprs[i] for i in keep]
    refs = _refs(exprs)
    if refs is None:
        child, m = _prune(node.child, _all_of(node.child), dropped)
    else:
        child, m = _prune_child(node.child, refs, dropped)
        exprs = _rebind_all(exprs, m)
    if child is node.child and _same(exprs, node.exprs):
        return node, _identity(n)
    dropped[0] += n - len(keep)
    return PN.Project(exprs, child), {o: i for i, o in enumerate(keep)}


def _prune_filter(node: PN.Filter, required, dropped):
    refs = _refs([node.condition])
    if refs is None:
        return _prune_unknown(node, required, dropped)
    child, m = _prune_child(node.child, required | refs, dropped)
    if child is node.child:
        return node, m
    return PN.Filter(rebind(node.condition, m), child), m


def _prune_aggregate(node: PN.HashAggregate, required, dropped):
    inputs = list(node.grouping)
    for a in node.aggregates:
        inputs += [a.child, a.child2]
    refs = _refs(inputs)
    # a FINAL aggregate reads its child's buffer columns by position, and
    # its aggregates' expressions are the PARTIAL's, bound to another schema
    if refs is None or node.mode == PN.AggregateMode.FINAL:
        return _prune_unknown(node, required, dropped)
    child, m = _prune_child(node.child, refs, dropped)
    if child is not node.child:
        aggs = [dataclasses.replace(a, child=rebind(a.child, m),
                                    child2=rebind(a.child2, m))
                for a in node.aggregates]
        node = PN.HashAggregate(_rebind_all(node.grouping, m), aggs,
                                node.mode, child)
    return node, _identity(len(node.output.fields))


def _prune_join(node: PN._BaseJoin, required, dropped):
    """Children keep what the parent reads plus keys and condition; the
    join emits what the parent reads.  The nested-loop join takes no emit
    list: its children are narrowed and its output is theirs."""
    nl = len(node.left.output.fields)
    takes_emit = not isinstance(node, PN.BroadcastNestedLoopJoin)
    lrefs, rrefs = _refs(node.left_keys), _refs(node.right_keys)
    crefs = _refs([node.condition])
    if lrefs is None or rrefs is None or crefs is None \
            or node.emit is not None:
        return _prune_unknown(node, required, dropped)
    n_out = len(node.output.fields)
    req = frozenset(_keep(required, node.output.fields))
    read = req | crefs
    left, lm = _prune_child(
        node.left, lrefs | {o for o in read if o < nl}, dropped)
    right, rm = _prune_child(
        node.right, rrefs | {o - nl for o in read if o >= nl}, dropped)
    nl2 = len(left.output.fields)
    both = dict(lm)
    both.update({nl + o: nl2 + n for o, n in rm.items()})
    new = copy.copy(node)
    new.children = [left, right]
    new.left_keys = _rebind_all(node.left_keys, lm)
    new.right_keys = _rebind_all(node.right_keys, rm)
    new.condition = rebind(node.condition, both)
    n_full = len(new.full_output.fields)
    if takes_emit:
        emit = [both[o] for o in sorted(req)]
        if emit != list(range(n_full)):
            new.emit = emit
            dropped[0] += n_full - len(emit)
        out_map = {o: i for i, o in enumerate(sorted(req))}
    else:
        out_map = {o: n for o, n in both.items() if o < n_out}
    if new.emit is None and left is node.left and right is node.right:
        return node, _identity(n_out)
    return new, out_map


def _prune_passthrough(node, required, dropped, exprs=(), force=False,
                       rebuild=None):
    """A node whose output is its child's (filter-like: sort, exchange,
    limit, sample): the child keeps what the parent and the node read."""
    refs = _refs(exprs)
    if refs is None:
        return _prune_unknown(node, required, dropped)
    child, m = _prune_child(node.children[0], required | refs, dropped,
                            force=force)
    if child is node.children[0]:
        return node, m
    new = node.with_new_children([child])
    if rebuild is not None:
        rebuild(new, m)
    return new, m


def _prune_sort(node: PN.Sort, required, dropped):
    def rebuild(new, m):
        new.orders = _rebind_orders(node.orders, m)

    return _prune_passthrough(node, required, dropped,
                              [e for e, _ in node.orders], True, rebuild)


def _prune_exchange(node: PN.Exchange, required, dropped):
    part = node.partitioning
    if isinstance(part, PN.HashPartitioning):
        exprs = list(part.keys)

        def moved(m):
            return dataclasses.replace(part, keys=_rebind_all(part.keys, m))
    elif isinstance(part, PN.RangePartitioning):
        exprs = [e for e, _ in part.orders]

        def moved(m):
            return dataclasses.replace(
                part, orders=_rebind_orders(part.orders, m))
    elif isinstance(part, (PN.SinglePartitioning,
                           PN.RoundRobinPartitioning)):
        exprs = []

        def moved(m):
            return part
    else:
        return _prune_unknown(node, required, dropped)

    def rebuild(new, m):
        new.partitioning = moved(m)

    return _prune_passthrough(node, required, dropped, exprs, True, rebuild)


def _prune_window(node: PN.Window, required, dropped):
    """Output = the child's columns, then one per function: every function
    stays, the child keeps what the parent and the specs read."""
    nc = len(node.child.output.fields)
    refs = _refs(list(node.partition_by) + [e for e, _ in node.order_by]
                 + [f.child for f in node.functions])
    if refs is None:
        return _prune_unknown(node, required, dropped)
    child, m = _prune_child(
        node.child, refs | {o for o in required if o < nc}, dropped,
        force=True)
    if child is node.child:
        return node, _identity(nc + len(node.functions))
    fns = [dataclasses.replace(f, child=rebind(f.child, m))
           for f in node.functions]
    new = PN.Window(fns, _rebind_all(node.partition_by, m),
                    _rebind_orders(node.order_by, m), child, node.frame)
    nc2 = len(child.output.fields)
    out = dict(m)
    out.update({nc + i: nc2 + i for i in range(len(fns))})
    return new, out


_RULES = (
    ((PN.LocalTableScan, PN.FileSourceScan), _prune_scan),
    (PN.Project, _prune_project),
    (PN.Filter, _prune_filter),
    (PN.HashAggregate, _prune_aggregate),
    (PN._BaseJoin, _prune_join),
    (PN.Sort, _prune_sort),
    (PN.Exchange, _prune_exchange),
    (PN.BroadcastExchange, functools.partial(_prune_passthrough, force=True)),
    ((PN.LocalLimit, PN.Sample), _prune_passthrough),
    (PN.Window, _prune_window),
)
