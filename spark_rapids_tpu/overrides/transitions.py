"""TpuTransitionOverrides — post-conversion plan fixups.

Reference analog: com/nvidia/spark/rapids/GpuTransitionOverrides.scala:
inserts transitions at CPU<->GPU boundaries, adds GpuCoalesceBatches /
GpuShuffleCoalesceExec after shuffles, and validates the final plan.  Here
the boundary transitions are inserted during conversion (overrides.py); this
pass adds:

  * TpuCoalesceBatchesExec after every shuffle exchange (the
    GpuShuffleCoalesceExec role: concat per-partition slices to the goal
    size — and on TPU, re-bucket shapes to bound recompiles);
  * Sort+Limit -> TpuTopNExec rewrite (GpuTopN);
  * whole-stage fusion of adjacent project/filter stages (TPU-specific).
"""
from __future__ import annotations

from typing import List

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import BATCH_SIZE_BYTES, TPU_WHOLESTAGE_FUSION, TpuConf
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.exec.basic import TpuStageExec, fuse_stages
from spark_rapids_tpu.exec.coalesce import CoalesceGoal, TpuCoalesceBatchesExec
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.exec.limit import TpuGlobalLimitExec, TpuLocalLimitExec
from spark_rapids_tpu.exec.sort import TpuSortExec, TpuTopNExec
from spark_rapids_tpu.plan.nodes import SparkPlan


class TpuMaterializedScan(SparkPlan):
    """CPU plan node backed by a TPU subtree: the columnar->row boundary.

    Reference analog: GpuColumnarToRowExec feeding a CPU operator."""

    def __init__(self, tpu_child: TpuExec):
        super().__init__([])
        self.tpu_child = tpu_child

    @property
    def output(self):
        return self.tpu_child.output

    def describe(self):
        return f"ColumnarToRow <- {self.tpu_child.describe()}"

    def materialize_cpu(self):
        from spark_rapids_tpu.cpu.oracle import CpuCol
        from spark_rapids_tpu.exec.transitions import TpuColumnarToRowExec

        c2r = TpuColumnarToRowExec(self.tpu_child)
        host = c2r.collect_host()
        cols = [CpuCol.from_host(h) for h in host]
        n = cols[0].n if cols else 0
        return cols, n


def _mesh_stage_on(conf: TpuConf, switch) -> bool:
    """The shared 4-condition guard of every ICI stage rewrite: mesh mode
    on, the per-stage kill switch on, shuffle mode ICI, >1 device."""
    return _mesh_stage_reason(conf, switch) is None


def _mesh_stage_reason(conf: TpuConf, switch):
    """None when the mesh stage may install; otherwise the fallback reason
    (which of the 4 guard conditions failed), for explain parity."""
    import jax

    from spark_rapids_tpu.config import MESH_ENABLED, SHUFFLE_MODE

    if not conf.get(MESH_ENABLED):
        return f"{MESH_ENABLED.key} is false"
    if not conf.get(switch):
        return f"{switch.key} is false"
    if str(conf.get(SHUFFLE_MODE)).upper() != "ICI":
        return (f"{SHUFFLE_MODE.key}={conf.get(SHUFFLE_MODE)} "
                "(mesh stages need ICI)")
    if len(jax.devices()) <= 1:
        return "single device (no mesh to distribute over)"
    return None


# ---------------------------------------------------------------------------
# Stage rules: the taggable registry of transition-installed execs
#.  The reference registers every exec in
# GpuOverrides.execs with per-exec explain/fallback; the collective (ICI)
# and fused stages here are installed by plan REWRITE rather than node
# conversion, so they get their own registry + per-apply decision ledger
# that the explain output and docs generator read.
# ---------------------------------------------------------------------------

import dataclasses as _dc
import threading as _threading


@_dc.dataclass(frozen=True)
class StageRule:
    name: str           # installed exec class name
    conf_key: str       # kill-switch conf
    desc: str           # what the stage collapses / replaces


def _stage_rules():
    from spark_rapids_tpu import config as C

    return {r.name: r for r in [
        StageRule("TpuIciShuffleAggExec", C.MESH_AGG_ENABLED.key,
                  "Final<-Exchange<-Partial aggregate as one SPMD "
                  "collective program (all-to-all over ICI)"),
        StageRule("TpuIciShuffleJoinExec", C.MESH_JOIN_ENABLED.key,
                  "shuffled equi-join as mesh all-to-all both sides + "
                  "per-device sorted probe"),
        StageRule("TpuIciSortExec", C.MESH_SORT_ENABLED.key,
                  "global sort as sampled range exchange + per-device "
                  "sort + ordered emit"),
        StageRule("TpuIciWindowExec", C.MESH_WINDOW_ENABLED.key,
                  "partitioned window as hash all-to-all on PARTITION BY "
                  "+ per-device window"),
        StageRule("TpuIciRepartitionExec", C.MESH_REPARTITION_ENABLED.key,
                  "remaining hash/round-robin exchanges as the generic "
                  "mesh all-to-all"),
        StageRule("TpuJoinAggFusedExec", C.JOIN_AGG_FUSION.key,
                  "aggregate over unconditioned INNER/LEFT broadcast "
                  "equi-join fused into one program"),
        StageRule("TpuWindowChainFusedExec", C.WINDOW_CHAIN_FUSION.key,
                  "window over complete-agg (and trailing stage ops) "
                  "fused into one program"),
        StageRule("TpuAdaptiveShuffleReaderExec",
                  C.ADAPTIVE_ENABLED.key,
                  "stats-driven shuffle-read partition coalescing "
                  "(GpuCustomShuffleReaderExec analog)"),
        StageRule("TpuFusedPipelineExec", C.FUSION_ENABLED.key,
                  "maximal pipeline-able operator chains (stage/expand) "
                  "compiled as ONE jitted program, split at predicted-"
                  "oversized HBM boundaries (manifest ∩ cost model)"),
    ]}


STAGE_RULES = None      # populated lazily (config import cycle)


def stage_rules():
    global STAGE_RULES
    if STAGE_RULES is None:
        STAGE_RULES = _stage_rules()
    return STAGE_RULES


_STAGE_LOG = _threading.local()


def _stage_log_reset() -> None:
    _STAGE_LOG.entries = []


def stage_decisions():
    """[(exec_name, installed: bool, reason: Optional[str])] for the most
    recent TpuTransitionOverrides.apply on this thread."""
    return list(getattr(_STAGE_LOG, "entries", []))


def _record(name: str, installed: bool, reason=None) -> None:
    entries = getattr(_STAGE_LOG, "entries", None)
    if entries is not None:
        entries.append((name, installed, reason))


class TpuTransitionOverrides:
    @staticmethod
    def apply(root: TpuExec, conf: TpuConf) -> TpuExec:
        from spark_rapids_tpu.exec.partition_sizing import (
            size_exchange_partitions,
        )

        _stage_log_reset()
        # size-aware partition counts FIRST (ISSUE 10): exchanges whose
        # estimated input exceeds the per-partition pool budget grow
        # their counts and become exempt from the single-device collapse
        # (out-of-core schedule, not parallelism)
        root = size_exchange_partitions(root, conf)
        root = TpuTransitionOverrides._coalesce_single_device_shuffle(
            root, conf)
        root = TpuTransitionOverrides._insert_coalesce(root, conf)
        root = TpuTransitionOverrides._collapse_complete_agg(root, conf)
        root = TpuTransitionOverrides._rewrite_topn(root)
        if conf.get(TPU_WHOLESTAGE_FUSION):
            root = fuse_stages(root)
        # after stage fusion so Agg(Stage(Join)) has become Agg(Join) with
        # the stage ops absorbed as the aggregate's pre_ops
        root = TpuTransitionOverrides._fuse_join_agg(root, conf)
        root = TpuTransitionOverrides._fuse_window_chain(root, conf)
        # whole-plan pipeline fusion (ISSUE 17) after the specialized
        # join-agg / window-chain fusions so they keep first claim on
        # their patterns; remaining stage/expand chains compile into one
        # program each, split at predicted-oversized HBM boundaries
        from spark_rapids_tpu.exec.fusion import fuse_pipelines

        root = fuse_pipelines(root, conf)
        root = TpuTransitionOverrides._rewrite_ici_agg(root, conf)
        root = TpuTransitionOverrides._rewrite_ici_join(root, conf)
        root = TpuTransitionOverrides._rewrite_ici_sort(root, conf)
        root = TpuTransitionOverrides._rewrite_ici_window(root, conf)
        root = TpuTransitionOverrides._rewrite_ici_repartition(root, conf)
        return root

    @staticmethod
    def _collapse_complete_agg(node: TpuExec, conf: TpuConf) -> TpuExec:
        """Single-device exchange elision for two-phase aggregates:
        Final <- [Coalesce] <- Exchange <- Partial  =>  Complete.

        The exchange exists to co-locate keys across devices; with one
        device (or the mesh path disabled) it only adds program launches.
        The COMPLETE aggregate runs ONE fused XLA program for a
        single-batch input and falls back to the exact two-phase pipeline
        (buffer-form merges) for multi-batch — see
        TpuHashAggregateExec._execute_complete.  Reference analog: AQE's
        single-partition shuffle elision (SURVEY.md §2.2)."""
        import jax

        from spark_rapids_tpu.config import (
            COMPLETE_AGG_COLLAPSE,
            MESH_AGG_ENABLED,
            MESH_ENABLED,
            SHUFFLE_MODE,
        )
        from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.plan.nodes import AggregateMode

        node.children = [
            TpuTransitionOverrides._collapse_complete_agg(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        if not conf.get(COMPLETE_AGG_COLLAPSE):
            return node
        if _mesh_stage_on(conf, MESH_AGG_ENABLED):
            return node  # the ICI collective rewrite owns this pattern
        if not (isinstance(node, TpuHashAggregateExec)
                and node.mode == AggregateMode.FINAL):
            return node
        from spark_rapids_tpu.exec.exchange import (
            TpuAdaptiveShuffleReaderExec,
        )

        mid = node.children[0]
        if isinstance(mid, (TpuCoalesceBatchesExec,
                            TpuAdaptiveShuffleReaderExec)):
            mid = mid.children[0]
        if not isinstance(mid, TpuShuffleExchangeExec):
            return node
        partial = mid.children[0]
        if not (isinstance(partial, TpuHashAggregateExec)
                and partial.mode == AggregateMode.PARTIAL):
            return node
        comp = TpuHashAggregateExec(
            partial.grouping, partial.aggregates, AggregateMode.COMPLETE,
            partial.children[0], partial.child_schema, node.output,
            node.ansi)
        comp.pre_ops = partial.pre_ops
        comp.input_schema = partial.input_schema
        return comp

    @staticmethod
    def _fuse_join_agg(node: TpuExec, conf: TpuConf) -> TpuExec:
        """Aggregate directly above an unconditioned INNER/LEFT equi-join
        fuses into TpuJoinAggFusedExec (exec/fused.py)."""
        from spark_rapids_tpu.config import JOIN_AGG_FUSION
        from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec
        from spark_rapids_tpu.exec.join import TpuBroadcastHashJoinExec
        from spark_rapids_tpu.plan.nodes import AggregateMode, JoinType

        node.children = [
            TpuTransitionOverrides._fuse_join_agg(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        if not (isinstance(node, TpuHashAggregateExec)
                and node.mode in (AggregateMode.COMPLETE,
                                  AggregateMode.PARTIAL)
                and not node._has_collect):
            return node
        join = node.children[0]
        if not (isinstance(join, TpuBroadcastHashJoinExec)
                and join.condition is None
                and join.join_type in (JoinType.INNER, JoinType.LEFT_OUTER)
                and join.left_keys):
            return node
        if not conf.get(JOIN_AGG_FUSION):
            _record("TpuJoinAggFusedExec", False,
                    f"{JOIN_AGG_FUSION.key} is false")
            return node
        _record("TpuJoinAggFusedExec", True)
        # the agg keeps the join as its child (used by the oversized-build
        # fallback); the fused exec replaces it in the surrounding tree
        return TpuJoinAggFusedExec(node, join)

    @staticmethod
    def _fuse_window_chain(node: TpuExec, conf: TpuConf) -> TpuExec:
        """[Stage(]Window([CompleteAgg(x)])[)] -> TpuWindowChainFusedExec.

        Non-ANSI only (the fused program carries no error-flag channel);
        ANSI chains keep their per-operator programs."""
        from spark_rapids_tpu.config import WINDOW_CHAIN_FUSION
        from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.exec.basic import TpuStageExec
        from spark_rapids_tpu.exec.fused import TpuWindowChainFusedExec
        from spark_rapids_tpu.exec.window import TpuWindowExec
        from spark_rapids_tpu.plan.nodes import AggregateMode

        from spark_rapids_tpu.config import MESH_WINDOW_ENABLED

        mesh_claims = _mesh_stage_on(conf, MESH_WINDOW_ENABLED)
        # match TOP-DOWN so the longest chain (stage+window+agg) wins over
        # the inner window+agg pair, then recurse into the result
        post_ops, post_schema = None, None
        window = node
        if isinstance(node, TpuStageExec) and not node.ansi \
                and not node._has_host_kernels() \
                and isinstance(node.children[0], TpuWindowExec):
            window = node.children[0]
            post_ops, post_schema = node.ops, node.output
        if (isinstance(window, TpuWindowExec) and not window.ansi
                # partitioned windows belong to the ICI window rewrite
                # in mesh mode; partition-less ones still fuse
                and not (mesh_claims and window.partition_by)):
            pre_agg = None
            child = window.children[0]
            if (isinstance(child, TpuHashAggregateExec)
                    and child.mode == AggregateMode.COMPLETE
                    and not child._has_collect and not child.ansi):
                pre_agg = child
            if pre_agg is not None or post_ops is not None:
                if conf.get(WINDOW_CHAIN_FUSION):
                    _record("TpuWindowChainFusedExec", True)
                    node = TpuWindowChainFusedExec(window, pre_agg,
                                                   post_ops, post_schema)
                else:
                    _record("TpuWindowChainFusedExec", False,
                            f"{WINDOW_CHAIN_FUSION.key} is false")
        node.children = [
            TpuTransitionOverrides._fuse_window_chain(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        return node

    @staticmethod
    def _rewrite_ici_sort(node: TpuExec, conf: TpuConf) -> TpuExec:
        """ICI mesh mode: a global TpuSortExec becomes the distributed
        range-exchange sort (sampled global splitters + all-to-all +
        per-device sort + ordered emit — exec/ici.TpuIciSortExec)."""
        import jax

        from spark_rapids_tpu.config import (MESH_ENABLED, MESH_EPOCH_BYTES,
                                             SHUFFLE_MODE)
        from spark_rapids_tpu.exec.ici import TpuIciSortExec

        from spark_rapids_tpu.config import MESH_SORT_ENABLED

        node.children = [
            TpuTransitionOverrides._rewrite_ici_sort(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        if not (isinstance(node, TpuSortExec) and node.is_global):
            return node
        reason = _mesh_stage_reason(conf, MESH_SORT_ENABLED)
        if reason is not None:
            _record("TpuIciSortExec", False, reason)
            return node
        from spark_rapids_tpu.config import MESH_DEVICES as _MD
        from spark_rapids_tpu.parallel.mesh import make_mesh

        _record("TpuIciSortExec", True)
        return TpuIciSortExec(node, make_mesh(conf.get(_MD) or None),
                              epoch_bytes=conf.get(MESH_EPOCH_BYTES))

    @staticmethod
    def _rewrite_ici_agg(node: TpuExec, conf: TpuConf) -> TpuExec:
        """ICI mesh mode: collapse Final<-[Coalesce]<-Exchange<-Partial into
        one SPMD collective program (exec/ici.py).

        Runs after fuse_stages so the partial aggregate already carries its
        fused scan-side filter/project ops into the per-device program."""
        import jax

        from spark_rapids_tpu.config import (MESH_AGG_ENABLED,
                                             MESH_ENABLED, SHUFFLE_MODE)
        from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.exec.ici import TpuIciShuffleAggExec
        from spark_rapids_tpu.plan.nodes import AggregateMode

        node.children = [
            TpuTransitionOverrides._rewrite_ici_agg(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        if not (isinstance(node, TpuHashAggregateExec)
                and node.mode == AggregateMode.FINAL):
            return node
        from spark_rapids_tpu.exec.exchange import (
            TpuAdaptiveShuffleReaderExec,
        )

        mid = node.children[0]
        if isinstance(mid, (TpuCoalesceBatchesExec,
                            TpuAdaptiveShuffleReaderExec)):
            mid = mid.children[0]
        if not isinstance(mid, TpuShuffleExchangeExec):
            return node
        partial = mid.children[0]
        if not (isinstance(partial, TpuHashAggregateExec)
                and partial.mode == AggregateMode.PARTIAL):
            return node
        reason = _mesh_stage_reason(conf, MESH_AGG_ENABLED)
        if reason is not None:
            _record("TpuIciShuffleAggExec", False, reason)
            return node
        from spark_rapids_tpu.config import MESH_DEVICES, MESH_EPOCH_BYTES
        from spark_rapids_tpu.parallel.mesh import make_mesh

        _record("TpuIciShuffleAggExec", True)
        return TpuIciShuffleAggExec(
            partial, node, make_mesh(conf.get(MESH_DEVICES) or None),
            epoch_bytes=conf.get(MESH_EPOCH_BYTES))

    @staticmethod
    def _rewrite_ici_join(node: TpuExec, conf: TpuConf) -> TpuExec:
        """ICI mesh mode: Join <- (Exchange, Exchange) becomes one pair of
        SPMD programs — all-to-all both sides over ICI, local sorted-probe
        join per device (exec/ici.TpuIciShuffleJoinExec)."""
        import jax

        from spark_rapids_tpu.config import MESH_ENABLED, SHUFFLE_MODE
        from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
        from spark_rapids_tpu.exec.ici import TpuIciShuffleJoinExec
        from spark_rapids_tpu.exec.join import (
            TpuAdaptiveJoinExec,
            TpuShuffledSymmetricHashJoinExec,
        )
        from spark_rapids_tpu.plan.nodes import JoinType

        from spark_rapids_tpu.config import MESH_JOIN_ENABLED

        node.children = [
            TpuTransitionOverrides._rewrite_ici_join(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        join = node
        if isinstance(join, TpuAdaptiveJoinExec):
            # the collective plan replaces the AQE wrapper: a mesh
            # all-to-all already is the "shuffle" it would avoid
            join = join.shuffled
        if not isinstance(join, TpuShuffledSymmetricHashJoinExec):
            return node
        reason = _mesh_stage_reason(conf, MESH_JOIN_ENABLED)
        if reason is None and join.join_type not in (
                JoinType.INNER, JoinType.LEFT_OUTER, JoinType.LEFT_SEMI,
                JoinType.LEFT_ANTI, JoinType.RIGHT_OUTER,
                JoinType.FULL_OUTER):
            reason = (f"join type {join.join_type.value} has no mesh "
                      "materialization")
        if reason is None and join.condition is not None \
                and join.join_type != JoinType.INNER:
            # non-inner residual conditions are tag-time fallbacks anyway
            reason = ("residual join condition is only supported for "
                      "INNER mesh joins")
        if reason is None and not all(
                isinstance(c, TpuShuffleExchangeExec)
                for c in join.children):
            reason = "join inputs are not both shuffle exchanges"
        if reason is not None:
            _record("TpuIciShuffleJoinExec", False, reason)
            return node
        from spark_rapids_tpu.config import MESH_DEVICES
        from spark_rapids_tpu.parallel.mesh import make_mesh

        from spark_rapids_tpu.config import MESH_EPOCH_BYTES as _MEB

        _record("TpuIciShuffleJoinExec", True)
        return TpuIciShuffleJoinExec(
            join, join.children[0].children[0],
            join.children[1].children[0],
            make_mesh(conf.get(MESH_DEVICES) or None),
            epoch_bytes=conf.get(_MEB))

    @staticmethod
    def _rewrite_ici_window(node: TpuExec, conf: TpuConf) -> TpuExec:
        """ICI mesh mode: a partitioned TpuWindowExec becomes the
        distributed mesh window (hash all-to-all on PARTITION BY +
        single-chip window per device — exec/ici.TpuIciWindowExec).
        Partition-less windows keep the single-chip exec (a global window
        is one ordered scan; there is nothing to co-locate)."""
        from spark_rapids_tpu.config import (MESH_DEVICES,
                                             MESH_EPOCH_BYTES,
                                             MESH_WINDOW_ENABLED)
        from spark_rapids_tpu.exec.ici import (
            TpuIciWindowExec,
            mesh_exchange_schema_supported,
        )
        from spark_rapids_tpu.exec.window import TpuWindowExec

        node.children = [
            TpuTransitionOverrides._rewrite_ici_window(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        if not (isinstance(node, TpuWindowExec) and node.partition_by):
            return node
        reason = _mesh_stage_reason(conf, MESH_WINDOW_ENABLED)
        if reason is None and not mesh_exchange_schema_supported(
                node.children[0].output):
            reason = ("input schema has nested/unsupported columns for "
                      "the mesh exchange")
        if reason is not None:
            _record("TpuIciWindowExec", False, reason)
            return node
        from spark_rapids_tpu.parallel.mesh import make_mesh

        _record("TpuIciWindowExec", True)
        return TpuIciWindowExec(
            node, make_mesh(conf.get(MESH_DEVICES) or None),
            epoch_bytes=conf.get(MESH_EPOCH_BYTES))

    @staticmethod
    def _rewrite_ici_repartition(node: TpuExec, conf: TpuConf) -> TpuExec:
        """ICI mesh mode, LAST of the mesh rewrites: any remaining hash /
        round-robin shuffle exchange (not claimed by the agg/join/sort/
        window stages above) lowers to the generic mesh all-to-all
        repartition (exec/ici.TpuIciRepartitionExec)."""
        from spark_rapids_tpu.config import (MESH_DEVICES,
                                             MESH_EPOCH_BYTES,
                                             MESH_REPARTITION_ENABLED)
        from spark_rapids_tpu.exec.ici import (
            TpuIciRepartitionExec,
            mesh_exchange_schema_supported,
        )
        from spark_rapids_tpu.plan.nodes import (HashPartitioning,
                                                 RoundRobinPartitioning)

        node.children = [
            TpuTransitionOverrides._rewrite_ici_repartition(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        if not (isinstance(node, TpuShuffleExchangeExec)
                and isinstance(node.partitioning,
                               (HashPartitioning, RoundRobinPartitioning))):
            return node
        reason = _mesh_stage_reason(conf, MESH_REPARTITION_ENABLED)
        if reason is None and not mesh_exchange_schema_supported(
                node.output):
            reason = ("output schema has nested/unsupported columns for "
                      "the mesh exchange")
        if reason is not None:
            _record("TpuIciRepartitionExec", False, reason)
            return node
        from spark_rapids_tpu.config import ICI_CROSS_SLICE_HOSTS
        from spark_rapids_tpu.parallel.mesh import make_mesh

        _record("TpuIciRepartitionExec", True)
        return TpuIciRepartitionExec(
            node, make_mesh(conf.get(MESH_DEVICES) or None),
            epoch_bytes=conf.get(MESH_EPOCH_BYTES),
            cross_hosts=conf.get(ICI_CROSS_SLICE_HOSTS))

    @staticmethod
    def _coalesce_single_device_shuffle(node: TpuExec,
                                        conf: TpuConf) -> TpuExec:
        """AQE-style shuffle partition coalescing for one device: hash/
        round-robin exchanges repartition for parallelism that a single
        chip does not have, and every extra partition costs a program
        launch (and potentially a compile).
        Collapse them to a single partition; results are unchanged
        (aggs/joins are partition-count independent)."""
        import jax

        from spark_rapids_tpu.config import SINGLE_DEVICE_SHUFFLE_COALESCE
        from spark_rapids_tpu.plan.nodes import (HashPartitioning,
                                                 RoundRobinPartitioning,
                                                 SinglePartitioning)

        node.children = [
            TpuTransitionOverrides._coalesce_single_device_shuffle(c, conf)
            if isinstance(c, TpuExec) else c for c in node.children]
        if not conf.get(SINGLE_DEVICE_SHUFFLE_COALESCE):
            return node
        if len(jax.devices()) > 1:
            return node
        from spark_rapids_tpu.config import DISTRIBUTED_ENABLED

        if isinstance(node, TpuShuffleExchangeExec) and isinstance(
                node.partitioning,
                (HashPartitioning, RoundRobinPartitioning)) \
                and not getattr(node, "_ooc_sized", False) \
                and not conf.get(DISTRIBUTED_ENABLED):
            # sized exchanges keep their partitions: on one chip they
            # are the out-of-core schedule, not elidable parallelism.
            # Distributed exchanges (ISSUE 14) keep them too: reduce
            # partitions are the unit of cross-host placement — with
            # one local chip and N remote workers, collapsing would
            # collapse the cluster to one worker
            node.partitioning = SinglePartitioning()
        return node

    @staticmethod
    def _insert_coalesce(node: TpuExec, conf: TpuConf) -> TpuExec:
        from spark_rapids_tpu.config import (
            ADAPTIVE_ENABLED,
            EXCHANGE_COALESCE_SMALL_BYTES,
        )
        from spark_rapids_tpu.exec.exchange import (
            TpuAdaptiveShuffleReaderExec,
        )

        node.children = [
            TpuTransitionOverrides._insert_coalesce(c, conf)
            if isinstance(c, TpuExec) else c
            for c in node.children]
        # overload governor (ISSUE 13): plan-time batch-size goals
        # shrink under YELLOW/RED so newly planned queries start with
        # smaller working sets (one ambient check when disabled)
        from spark_rapids_tpu.governor import context as _GOV

        _gov = _GOV.GOVERNOR
        goal_bytes = conf.get(BATCH_SIZE_BYTES)
        if _gov is not None:
            goal_bytes = _gov.degraded_goal(goal_bytes)
        new_children = []
        for c in node.children:
            if isinstance(c, TpuShuffleExchangeExec):
                if conf.get(ADAPTIVE_ENABLED):
                    # general AQE: the reader RECORDS per-partition
                    # rows/bytes and coalesces on the measured stats
                    # (GpuCustomShuffleReaderExec analog)
                    _record("TpuAdaptiveShuffleReaderExec", True)
                    new_children.append(TpuAdaptiveShuffleReaderExec(
                        c, goal_bytes,
                        small_bytes=conf.get(
                            EXCHANGE_COALESCE_SMALL_BYTES)))
                else:
                    _record("TpuAdaptiveShuffleReaderExec", False,
                            f"{ADAPTIVE_ENABLED.key} is false")
                    goal = CoalesceGoal(goal_bytes)
                    new_children.append(TpuCoalesceBatchesExec(goal, c))
            else:
                new_children.append(c)
        node.children = new_children
        return node

    @staticmethod
    def _rewrite_topn(node: TpuExec) -> TpuExec:
        node.children = [TpuTransitionOverrides._rewrite_topn(c)
                         if isinstance(c, TpuExec) else c
                         for c in node.children]
        if isinstance(node, (TpuGlobalLimitExec, TpuLocalLimitExec)):
            child = node.children[0]
            # Limit(Sort) or Limit(Coalesce(Exchange(Sort)))
            if isinstance(child, TpuSortExec):
                return TpuTopNExec(node.n, child.orders, child.children[0],
                                   child.ansi)
        return node
