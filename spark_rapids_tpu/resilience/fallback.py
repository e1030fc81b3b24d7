"""Runtime per-stage CPU fallback — synthesize the failing operator's
plan-node twin over its materialized TPU inputs.

Reference analog: plan-time ``willNotWorkOnTpu`` tagging routes a stage to
CPU Spark *before* execution; this module is the mid-query analog.  When a
stage fails deterministically at runtime, we rebuild the equivalent
``plan.nodes`` subtree with every TPU child wrapped in
``TpuMaterializedScan`` (the existing columnar->row boundary, which
re-drives the child's — still healthy — TPU iterator), execute it through
``cpu/oracle.py``, upload the result, and let the rest of the query
continue on TPU.

Synthesis is per-exec-class: post-conversion rewrites (whole-stage fusion,
complete-agg collapse, TopN) replaced the original plan nodes, so the twin
is rebuilt from the exec's own attributes rather than a stale pointer.
Operators with no synthesis (shuffle internals, mesh collectives) return
None — their failure propagates to the parent domain, which falls back at
its own (coarser) granularity, and ultimately to the session's whole-query
oracle fallback."""
from __future__ import annotations

from typing import Iterator, List, Optional

from spark_rapids_tpu import types as T


def _mat(child):
    """A CPU scan node over one TPU child (fresh execution)."""
    from spark_rapids_tpu.overrides.transitions import TpuMaterializedScan

    return TpuMaterializedScan(child)


def _ops_to_plan(ops, base):
    """Rebuild the PN.Project/PN.Filter chain a fused stage absorbed."""
    from spark_rapids_tpu.exec.basic import (
        FilterOp,
        FilterProjectOp,
        ProjectOp,
    )
    from spark_rapids_tpu.plan import nodes as PN

    plan = base
    for op in ops:
        if isinstance(op, FilterProjectOp):
            plan = PN.Project(op.exprs, PN.Filter(op.condition, plan))
        elif isinstance(op, ProjectOp):
            plan = PN.Project(op.exprs, plan)
        elif isinstance(op, FilterOp):
            plan = PN.Filter(op.condition, plan)
        else:
            return None
    return plan


def _agg_plan(agg, base):
    from spark_rapids_tpu.plan import nodes as PN

    if agg.pre_ops:
        base = _ops_to_plan(agg.pre_ops, base)
        if base is None:
            return None
    return PN.HashAggregate(agg.grouping, agg.aggregates, agg.mode, base)


def build_cpu_subplan(op) -> Optional[object]:
    """The oracle-executable twin of one TPU exec, or None."""
    from spark_rapids_tpu.exec import aggregate as XA
    from spark_rapids_tpu.exec import basic as XB
    from spark_rapids_tpu.exec import generate as XG
    from spark_rapids_tpu.exec import join as XJ
    from spark_rapids_tpu.exec import limit as XL
    from spark_rapids_tpu.exec import sort as XS
    from spark_rapids_tpu.exec import window as XW
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.exec.fused import (
        TpuJoinAggFusedExec,
        TpuWindowChainFusedExec,
    )
    from spark_rapids_tpu.exec.transitions import TpuRowToColumnarExec
    from spark_rapids_tpu.plan import nodes as PN

    if isinstance(op, XB.TpuStageExec):
        return _ops_to_plan(op.ops, _mat(op.children[0]))
    if isinstance(op, XA.TpuHashAggregateExec):
        return _agg_plan(op, _mat(op.children[0]))
    if isinstance(op, XJ.TpuAdaptiveJoinExec):
        sh = op.shuffled
        return PN.SortMergeJoin(_mat(op.children[0]), _mat(op.children[1]),
                                sh.left_keys, sh.right_keys, sh.join_type,
                                sh.condition, emit=sh.emit)
    if isinstance(op, XJ._BaseTpuJoinExec):
        return PN.SortMergeJoin(_mat(op.children[0]), _mat(op.children[1]),
                                op.left_keys, op.right_keys, op.join_type,
                                op.condition, emit=op.emit)
    if isinstance(op, XJ.TpuCartesianProductExec):
        return PN.SortMergeJoin(_mat(op.children[0]), _mat(op.children[1]),
                                [], [], PN.JoinType.CROSS, op.condition,
                                emit=op.emit)
    if isinstance(op, TpuJoinAggFusedExec):
        # the agg kept the join as its child; materialize the join's TPU
        # output and aggregate it on CPU
        return _agg_plan(op.agg, _mat(op.join))
    if isinstance(op, TpuWindowChainFusedExec):
        base = _mat(op.children[0])
        if op.pre_agg is not None:
            base = _agg_plan(op.pre_agg, base)
            if base is None:
                return None
        w = op.window
        plan = PN.Window(w.functions, w.partition_by, w.order_by, base,
                         w.frame)
        if op.post_ops:
            plan = _ops_to_plan(op.post_ops, plan)
        return plan
    if isinstance(op, XS.TpuTopNExec):
        return PN.GlobalLimit(op.n, PN.Sort(op.orders, True,
                                            _mat(op.children[0])))
    if isinstance(op, XS.TpuSortExec):
        return PN.Sort(op.orders, op.is_global, _mat(op.children[0]))
    if isinstance(op, XW.TpuWindowExec):
        return PN.Window(op.functions, op.partition_by, op.order_by,
                         _mat(op.children[0]), op.frame)
    if isinstance(op, XG.TpuGenerateExec):
        return PN.Generate(op.gen_expr, _mat(op.children[0]),
                           position=op.position, outer=op.outer,
                           out_name=op.out_name)
    if isinstance(op, XG.TpuExpandExec):
        return PN.Expand(op.projections, op.output, _mat(op.children[0]))
    if isinstance(op, XG.TpuBroadcastNestedLoopJoinExec):
        return PN.BroadcastNestedLoopJoin(
            _mat(op.children[0]), _mat(op.children[1]), op.join_type,
            op.condition)
    if isinstance(op, XL.TpuGlobalLimitExec):
        return PN.GlobalLimit(op.n, _mat(op.children[0]))
    if isinstance(op, XL.TpuLocalLimitExec):
        return PN.LocalLimit(op.n, _mat(op.children[0]))
    if isinstance(op, XB.TpuUnionExec):
        return PN.Union([_mat(c) for c in op.children])
    if isinstance(op, TpuRowToColumnarExec):
        # the wrapped subtree already is a CPU plan
        return op.cpu_plan
    origin = getattr(op, "_origin_plan", None)
    if origin is not None:
        tpu_children = [c for c in op.children if isinstance(c, TpuExec)]
        if not origin.children and not tpu_children:
            return origin          # leaf scans execute natively on CPU
        if len(origin.children) == len(tpu_children):
            return origin.with_new_children(
                [_mat(c) for c in tpu_children])
    return None


def op_breaker_key(op):
    """The breaker key for one exec, via its plan twin (so the key matches
    what plan-time tagging computes); None when no twin exists."""
    from spark_rapids_tpu.resilience.breaker import plan_key

    origin = getattr(op, "_origin_plan", None)
    if origin is not None:
        return plan_key(origin)
    twin = build_cpu_subplan(op)
    if twin is None:
        return None
    return plan_key(twin)


def execute_fallback(op, ansi: bool) -> Iterator[object]:
    """Run the operator's CPU twin through the oracle and yield ONE device
    batch with its full result (device<->host transitions included).
    Raises whatever the oracle raises — the caller keeps the original TPU
    exception as primary if the oracle fails too."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.cpu.oracle import execute_cpu_plan

    twin = build_cpu_subplan(op)
    if twin is None:
        raise LookupError(
            f"no CPU fallback synthesis for {op.node_name}")
    cols, n = execute_cpu_plan(twin, ansi=ansi)
    host = [c.to_host() for c in cols]
    names = op.output.field_names()
    yield ColumnarBatch.from_host_columns(host, names)


def has_fallback(op) -> bool:
    try:
        return build_cpu_subplan(op) is not None
    # tpulint: disable=cancel-swallow (plan-construction probe — builds
    # no batches and observes no token; False just means no CPU twin)
    except Exception:
        return False
