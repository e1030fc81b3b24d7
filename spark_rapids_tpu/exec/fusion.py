"""Whole-plan subtree fusion (ISSUE 17) — maximal pipeline-able chains
as ONE jitted program.

Reference analog: none — the reference accelerates per-operator kernels
and eats a ~10µs launch per edge; here every program boundary is a
launch plus, usually, a host sync and a materialized intermediate in
HBM, and every distinct program is a compile of seconds.  ``fuse_stages`` (exec/basic.py) already merges
adjacent project/filter stages and absorbs a stage into the aggregate
above it; this pass closes the remaining pipeline breaks — an Expand
between stages, a multi-projection Expand by itself — by compiling each
maximal chain of segment-capable operators into one XLA program routed
through the compilecache registry.

Eligibility is the intersection of three gates:

* the fusibility manifest (analysis/fusibility.py, committed at
  ``tools/fusibility_manifest.json``): only exec classes classified
  ``fusable`` or ``fusable-with-rewrite`` may join a chain —
  :data:`MANIFEST_ELIGIBLE` mirrors the committed manifest and
  tests/test_fusion_pipeline.py pins the two identical;
* segment capability: the exec provides :meth:`TpuExec.fusion_segment`
  (a traceable ``(cols, num_rows) -> (cols, num_rows, flags)`` piece);
* the cost model's boundary rule: the chain fuses through an edge only
  while ``profiling.model.predicted_intermediate_bytes`` for that edge
  stays within ``spark.rapids.tpu.fusion.maxIntermediateFraction`` of
  the HBM pool — a predicted-oversized intermediate splits the chain at
  the predicted boundary (exec/partition_sizing.py supplies the
  estimate ladder: static AOT rows, calibrated rows EWMA, capacity).

Docs: docs/whole_plan_fusion.md.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.expr.base import SparkArithmeticException
from spark_rapids_tpu.perfcounters import sync_get, tpu_jit

# Exec classes the committed fusibility manifest classifies ``fusable``
# or ``fusable-with-rewrite`` — the manifest half of the eligibility
# intersection.  tests/test_fusion_pipeline.py regenerates the manifest
# and pins this set to it exactly, so a reclassified exec cannot keep
# fusing (or stay excluded) silently.
MANIFEST_ELIGIBLE = frozenset({
    "TpuBroadcastNestedLoopJoinExec",
    "TpuExpandExec",
    "TpuFusedPipelineExec",
    "TpuGenerateExec",
    "TpuHashAggregateExec",
    "TpuIciShuffleAggExec",
    "TpuIciShuffleJoinExec",
    "TpuIciSortExec",
    "TpuIciWindowExec",
    "TpuJoinAggFusedExec",
    "TpuShuffleExchangeExec",
    "TpuSortExec",
    "TpuStageExec",
    "TpuWindowChainFusedExec",
    "TpuWindowExec",
    "_BaseTpuJoinExec",
})


def manifest_eligible(node: TpuExec) -> bool:
    """Manifest gate: some class in the exec's MRO is classified fusable
    / fusable-with-rewrite (subclasses run their base's kernels — the
    same inheritance rule ``build_manifest`` applies)."""
    return any(c.__name__ in MANIFEST_ELIGIBLE for c in type(node).__mro__)


@dataclasses.dataclass
class PipelineSegment:
    """One operator's traceable slice of a fused pipeline.

    ``make(in_schema)`` returns ``(fn, msgs_store)`` where ``fn(cols,
    num_rows) -> (cols, num_rows, flags)`` is pure traced compute over
    device columns and ``msgs_store`` is the ANSI error-message aux the
    trace fills — it travels WITH the fused executable as part of the
    registry entry's aux (the manifest's fusable-with-rewrite rewrite).

    ``fp`` is the segment's registry fingerprint parts (None → the fused
    program stays instance-private, never shared).  ``count_map`` maps
    the input batch's host row count to the output count when that is
    statically derivable (projections preserve it, expand multiplies
    it); None means data-dependent (filters) and the fused program must
    sync the count.  ``programs_unfused`` is how many programs the
    operator launches per input batch UNFUSED — the pass only installs
    a fused node when the chain saves launches."""

    name: str
    fp: Optional[tuple]
    make: Callable[[T.StructType], tuple]
    out_schema: T.StructType
    count_map: Optional[Callable[[int], int]] = None
    programs_unfused: int = 1


class TpuFusedPipelineExec(TpuExec):
    """A chain of pipeline segments compiled as ONE jitted program.

    ``describe()`` lists every constituent operator, so ``df.explain()``
    shows the fused subtree as a single node with constituent
    attribution, and the diagnostics operator span / progress pull for
    the fused node carries the same constituent list (recorder spans key
    on ``node_name``/``describe``)."""

    def __init__(self, segments: Sequence[PipelineSegment],
                 constituents: Sequence[str], child: TpuExec):
        super().__init__([child])
        self.segments = list(segments)      # bottom-up application order
        self.constituents = list(constituents)
        self._jitted = None

    @property
    def output(self) -> T.StructType:
        return self.segments[-1].out_schema

    @property
    def node_name(self) -> str:
        return "TpuFusedPipelineExec"

    def describe(self) -> str:
        return "TpuFusedPipeline[" + " -> ".join(self.constituents) + "]"

    # -- AOT shape propagation ---------------------------------------
    def aot_output_rows(self):
        rows = self.aot_input_rows()
        if rows is None:
            return None
        for seg in self.segments:
            if seg.count_map is None:
                return None
            rows = [seg.count_map(r) for r in rows]
        return rows

    def aot_emits_single_batch(self) -> bool:
        # one output batch per input batch (expand's variants concat
        # INSIDE the program), so batch count passes through
        return self.aot_child_single_batch()

    # -- program construction ----------------------------------------
    def _program(self, in_schema: T.StructType):
        """(registry key parts, factory) — shared by the runtime build
        and AOT enumeration so both land on the same entry."""
        from spark_rapids_tpu.compilecache.keys import conf_fp, schema_fp

        fps = [s.fp for s in self.segments]
        key_parts = None if any(f is None for f in fps) else (
            "fusedpipe", schema_fp(in_schema), tuple(fps), conf_fp())
        segments = self.segments

        def factory():
            fns, stores = [], []
            schema = in_schema
            for seg in segments:
                fn, store = seg.make(schema)
                fns.append(fn)
                stores.append(store)
                schema = seg.out_schema

            def fused(cols, num_rows):
                flags_all: tuple = ()
                for fn in fns:
                    cols, num_rows, flags = fn(cols, num_rows)
                    cols = tuple(cols)
                    flags_all = flags_all + tuple(flags)
                return cols, jnp.asarray(num_rows), flags_all

            return tpu_jit(fused), stores

        return key_parts, factory

    def aot_programs(self):
        from spark_rapids_tpu.compilecache.aot import (
            AotProgram,
            dummy_batch_args,
        )

        caps = self.aot_input_caps()
        if not caps:
            return []
        in_schema = self.children[0].output
        key_parts, factory = self._program(in_schema)
        if key_parts is None:
            return []

        def args_factory():
            return [dummy_batch_args(in_schema, c) for c in caps]

        return [AotProgram(key_parts, factory, args_factory,
                           f"fusedpipe:{self.describe()[:44]}")]

    def _build(self, in_schema: T.StructType):
        from spark_rapids_tpu.compilecache.registry import cached_program

        key_parts, factory = self._program(in_schema)
        entry = cached_program(key_parts, factory, label=self.describe())
        jitted, stores = entry.jitted, entry.aux
        static_maps = [s.count_map for s in self.segments]
        count_static = all(m is not None for m in static_maps)
        out_schema = self.output

        def run(batch: ColumnarBatch) -> ColumnarBatch:
            cols, count, flags = jitted(
                tuple(batch.columns), jnp.int32(batch.num_rows))
            if flags or not count_static:
                # count + every ANSI flag in ONE logical round trip —
                # the whole chain's only host sync
                host = sync_get((count,) + tuple(flags))
                msgs = [m for store in stores for m in store]
                for f, m in zip(host[1:], msgs):
                    if f:
                        raise SparkArithmeticException(m)
                n = int(host[0])
            else:
                # every segment's count is host-derivable: zero syncs
                n = batch.num_rows
                for m in static_maps:
                    n = m(n)
            return ColumnarBatch(list(cols), n, out_schema)

        return run

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        child = self.children[0]
        for batch in child.execute_columnar():
            if self._jitted is None:
                self._jitted = self._build(batch.schema)
            with self.metrics["opTime"].timed():
                out = self._jitted(batch)
            yield self._count_output(out)


# ---------------------------------------------------------------------
# the plan-time fusion pass
# ---------------------------------------------------------------------

def fusion_budget_bytes(conf) -> int:
    """The HBM budget a fused chain's predicted intermediates must stay
    within: pool * fusion.maxIntermediateFraction."""
    from spark_rapids_tpu.config import FUSION_MAX_INTERMEDIATE_FRACTION
    from spark_rapids_tpu.memory.device_manager import get_device_manager

    pool = get_device_manager().pool_bytes
    frac = float(conf.get(FUSION_MAX_INTERMEDIATE_FRACTION))
    return max(int(pool * frac), 1 << 16)


def _segment_of(node) -> Optional[PipelineSegment]:
    """The node's pipeline segment when ALL eligibility gates short of
    the cost model pass: single child, manifest-eligible class, and a
    non-None fusion_segment."""
    if not (isinstance(node, TpuExec) and len(node.children) == 1):
        return None
    if not manifest_eligible(node):
        return None
    fn = getattr(node, "fusion_segment", None)
    if fn is None:
        return None
    return fn()


def _build_fused(chain: List[Tuple[TpuExec, PipelineSegment]],
                 child, conf) -> TpuExec:
    """Split a top-down chain at predicted-oversized edges, then install
    one TpuFusedPipelineExec per group that saves launches."""
    from spark_rapids_tpu.overrides.transitions import _record
    from spark_rapids_tpu.profiling.model import (
        predicted_intermediate_bytes,
    )

    budget = fusion_budget_bytes(conf)
    bottom_up = list(reversed(chain))
    groups: List[List[Tuple[TpuExec, PipelineSegment]]] = [[bottom_up[0]]]
    for lower, upper in zip(bottom_up, bottom_up[1:]):
        est = predicted_intermediate_bytes(lower[0], conf)
        if est is not None and est > budget:
            _record("TpuFusedPipelineExec", False,
                    f"predicted intermediate {est}B above {lower[0].node_name} "
                    f"exceeds fusion budget {budget}B — chain split at the "
                    "predicted boundary")
            groups.append([upper])
        else:
            groups[-1].append(upper)

    out = child
    for group in groups:          # bottom-most group first
        launches = sum(seg.programs_unfused for _, seg in group)
        if launches >= 2:
            fused = TpuFusedPipelineExec(
                [seg for _, seg in group],
                [ex.describe() for ex, _ in group], out)
            _record("TpuFusedPipelineExec", True)
            PC.bump("subtrees_fused")
            out = fused
        else:
            # a lone single-program stage gains nothing from the fused
            # wrapper; keep the original exec (rewired onto the chain)
            for ex, _ in group:       # group is a single member here
                ex.children = [out]
                out = ex
    return out


def fuse_pipelines(root: TpuExec, conf) -> TpuExec:
    """The pass: walk the exec tree, collapse every maximal eligible
    chain (TpuTransitionOverrides.apply, after the specialized join-agg
    / window-chain fusions so they keep first claim)."""
    from spark_rapids_tpu.config import FUSION_ENABLED
    from spark_rapids_tpu.overrides.transitions import _record

    enabled = conf.get(FUSION_ENABLED)

    def rewrite(node):
        if not isinstance(node, TpuExec):
            return node
        seg = _segment_of(node)
        if seg is not None:
            chain = [(node, seg)]
            cur = node.children[0]
            while True:
                s = _segment_of(cur)
                if s is None:
                    break
                chain.append((cur, s))
                cur = cur.children[0]
            below = rewrite(cur)
            if sum(s.programs_unfused for _, s in chain) >= 2:
                if enabled:
                    return _build_fused(chain, below, conf)
                _record("TpuFusedPipelineExec", False,
                        f"{FUSION_ENABLED.key} is false")
            # nothing to fuse (or disabled): rewire the chain unchanged
            for ex, _ in reversed(chain):
                ex.children = [below]
                below = ex
            return below
        node.children = [rewrite(c) for c in node.children]
        return node

    return rewrite(root)
