"""TpuExec — base of all TPU operators.

Reference analog: the GpuExec trait (SURVEY.md §1 L4):
``internalDoExecuteColumnar(): RDD[ColumnarBatch]`` plus GpuMetrics.  Here an
operator yields an iterator of device ColumnarBatches; device work happens in
jit-compiled stage functions cached per shape bucket (see basic.py), so the
per-batch Python cost is one dispatch.

Metrics mirror the reference's standard names (GpuMetric / GpuTaskMetrics):
opTime, numOutputRows, numOutputBatches, sortTime, joinTime, concatTime,
semaphoreWaitTime, spillTime, retryCount — surfaced via .metrics and the
explain output.
Tracing (SURVEY.md §5.1): every batch pull of every operator runs under
the ``srt.op.<node_name>`` span of ``perfcounters.span`` (exec/runtime.py)
— the NVTX-range analog: a ``jax.profiler.TraceAnnotation`` while a
profiler session is on, and a row of the folded span table always.
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, List, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import METRICS_LEVEL, get_conf


class TpuMetric:
    ESSENTIAL = "ESSENTIAL"
    MODERATE = "MODERATE"
    DEBUG = "DEBUG"

    def __init__(self, name: str, level: str = "MODERATE"):
        self.name = name
        self.level = level
        self.value = 0

    def add(self, v):
        self.value += v

    def __iadd__(self, v):
        self.value += v
        return self

    class _Timer:
        def __init__(self, metric):
            self.metric = metric

        def __enter__(self):
            self.t0 = time.perf_counter_ns()
            return self

        def __exit__(self, *a):
            self.metric.value += time.perf_counter_ns() - self.t0

    def timed(self):
        return TpuMetric._Timer(self)


class _SchemaOnlyExec:
    """Stand-in child inside a detached trace clone (detached_for_trace):
    registry-shared stage functions only ever read ``.output`` from their
    children at trace time."""

    __slots__ = ("_schema",)

    def __init__(self, schema):
        self._schema = schema

    @property
    def output(self):
        return self._schema


class TpuExec:
    """Base TPU operator; children may be TpuExec or transition nodes.

    Metric registration mirrors the reference's GpuExec pattern: the
    three standard metrics register here with their reference levels
    (numOutputRows ESSENTIAL; opTime / numOutputBatches MODERATE), and a
    subclass declares its operator-specific metrics up front via
    ``EXTRA_METRICS`` (name -> level) — so the diagnostics layer and
    ``explain("analyze")`` can filter on ``spark.rapids.sql.metrics.
    level`` without guessing.  ``metric()`` still creates undeclared
    names on the fly (at DEBUG level, the reference's default for ad-hoc
    metrics)."""

    EXTRA_METRICS: Dict[str, str] = {}

    def __init__(self, children: Sequence["TpuExec"]):
        self.children: List[TpuExec] = list(children)
        self.metrics: Dict[str, TpuMetric] = {}
        self.metrics["numOutputRows"] = TpuMetric(
            "numOutputRows", TpuMetric.ESSENTIAL)
        for m in ("opTime", "numOutputBatches"):
            self.metrics[m] = TpuMetric(m, TpuMetric.MODERATE)
        for m, level in self.EXTRA_METRICS.items():
            self.metrics[m] = TpuMetric(m, level)

    # ad-hoc metrics created by the fault domain record operator-level
    # failures — ESSENTIAL like the resilience events themselves, so
    # explain("analyze") at the default level never hides a retry/fallback
    _ADHOC_METRIC_LEVELS = {
        "transientRetries": TpuMetric.ESSENTIAL,
        "retryCount": TpuMetric.ESSENTIAL,
        "runtimeFallbacks": TpuMetric.ESSENTIAL,
        "breakerTrips": TpuMetric.ESSENTIAL,
        # I/O fault domain (ISSUE 5): skipped files and per-file device
        # ->native decoder retries are resilience events too
        "filesSkipped": TpuMetric.ESSENTIAL,
        "fileDecoderFallbacks": TpuMetric.ESSENTIAL,
    }

    def metric(self, name: str) -> TpuMetric:
        if name not in self.metrics:
            self.metrics[name] = TpuMetric(
                name, self._ADHOC_METRIC_LEVELS.get(name, TpuMetric.DEBUG))
        return self.metrics[name]

    @property
    def output(self) -> T.StructType:
        raise NotImplementedError

    @property
    def node_name(self) -> str:
        return type(self).__name__

    def describe(self) -> str:
        return self.node_name

    def inner_execs(self) -> Sequence["TpuExec"]:
        """Execs this node runs that are not among its children (the
        planned join inside TpuAdaptiveJoinExec, whose children are this
        node's own): the recorder registers them under a stable path,
        ``<path>.i<k>``, and explain("analyze") shows them."""
        return ()

    def pretty(self, indent: int = 0) -> str:
        s = "  " * indent + self.describe()
        for c in self.children:
            s += "\n" + c.pretty(indent + 1)
        return s

    def metrics_report(self, indent: int = 0) -> str:
        """Per-operator metric rollup after execution — the Spark SQL UI
        metrics surface (GpuMetric / GpuTaskMetrics analog, SURVEY §5.5).
        Time metrics render in ms; zero-valued metrics are elided."""
        parts = []
        for name, m in sorted(self.metrics.items()):
            if not m.value:
                continue
            if name.endswith(("Time", "time")):
                parts.append(f"{name}={m.value / 1e6:.1f}ms")
            else:
                parts.append(f"{name}={m.value}")
        s = "  " * indent + self.describe()
        if parts:
            s += "  [" + ", ".join(parts) + "]"
        for c in self.children:
            if hasattr(c, "metrics_report"):
                s += "\n" + c.metrics_report(indent + 1)
        return s

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        """Yield device batches; implemented by subclasses."""
        raise NotImplementedError(self.node_name)

    def detached_for_trace(self) -> "TpuExec":
        """A shallow clone safe to capture in a registry-shared jit
        closure.  The process-global program registry keeps entries alive
        across queries; a bound-method closure over ``self`` would pin
        the whole exec subtree — scan host columns, plan-node twins,
        device caches — for as long as the entry lives.  The clone keeps
        only the semantic fields the trace reads; children become schema
        stubs and every cache/plan back-reference is dropped."""
        import copy

        clone = copy.copy(self)
        clone.children = [_SchemaOnlyExec(c.output) for c in self.children]
        clone.metrics = {}
        # sweep cache/back-reference attrs by convention so a subclass
        # adding a new per-instance cache cannot silently re-introduce
        # the leak; plus the known non-conforming names
        drop = {"_origin_plan", "_aot_submission", "_twin_cache",
                "_reg_scope", "_device_cache", "_slot"}
        for name in list(clone.__dict__):
            if name in drop or name.endswith(
                    ("_jit", "_jits", "_jitted", "_jit_cache", "_cache")):
                clone.__dict__.pop(name, None)
        return clone

    # -- plan-time AOT compilation (compilecache/aot.py) ----------------
    def aot_output_rows(self):
        """Per-batch row counts this operator will emit, when derivable
        from the plan alone (local/range scans and the narrow operators
        above them); None when data-dependent (exchange partitions,
        aggregate groups, join pair counts...).  Drives shape-bucket
        prediction for the AOT pipeline."""
        return None

    def aot_output_caps(self):
        """Predicted output batch CAPACITIES (shape buckets) — what
        programs actually specialize on.  Default: derived from the row
        estimate; operators whose output capacity is predictable even
        when row counts are not (aggregates under a groups cap) override
        this directly."""
        rows = self.aot_output_rows()
        if rows is None:
            return None
        from spark_rapids_tpu.compilecache.aot import bucket_of

        return sorted({bucket_of(r) for r in rows})

    def aot_emits_single_batch(self) -> bool:
        """True when this operator emits exactly one batch regardless of
        input batching (concat-style operators, non-partial aggregates) —
        lets a concat consumer above trust aot_output_caps even without a
        row estimate."""
        return False

    def aot_input_rows(self):
        """First child's static row estimate (the common input shape)."""
        if not self.children:
            return None
        child = self.children[0]
        fn = getattr(child, "aot_output_rows", None)
        return fn() if fn is not None else None

    def aot_input_caps(self):
        """Capacities of the batches the first child will emit — for
        PER-BATCH consumers (stage/aggregate programs run once per input
        batch, so any batch count works)."""
        if not self.children:
            return None
        fn = getattr(self.children[0], "aot_output_caps", None)
        return fn() if fn is not None else None

    def aot_input_concat_caps(self):
        """Capacity of the CONCATENATION of the first child's batches —
        for concat consumers (sort/window); see compilecache.aot
        concat_caps for the rule."""
        if not self.children:
            return None
        from spark_rapids_tpu.compilecache.aot import concat_caps

        return concat_caps(self.children[0])

    def aot_child_single_batch(self) -> bool:
        """True when the first child is known to emit exactly one batch."""
        rows = self.aot_input_rows()
        if rows is not None:
            return len(rows) == 1
        if not self.children:
            return False
        single = getattr(self.children[0], "aot_emits_single_batch", None)
        return bool(single()) if single is not None else False

    def aot_programs(self):
        """The (stage function x shape-bucket) programs this operator
        will need, as compilecache.aot.AotProgram items; default: none
        enumerable.  Implementations MUST derive key parts and factories
        from the same helpers the runtime path uses, so an AOT-compiled
        entry is exactly the one the first batch looks up."""
        return []

    def fusion_segment(self):
        """This operator's traceable pipeline slice for whole-plan
        fusion (exec/fusion.PipelineSegment), or None when it cannot be
        inlined into a larger traced region.  Only implemented by execs
        the fusibility manifest classifies fusable / fusable-with-
        rewrite (the pass checks both)."""
        return None

    def _count_output(self, b: ColumnarBatch) -> ColumnarBatch:
        self.metrics["numOutputRows"] += b.num_rows
        self.metrics["numOutputBatches"] += 1
        return b

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        # install the unified operator runtime (exec/runtime.py): ONE
        # batch loop dispatching every registered per-batch concern —
        # cancel, governor, progress, diagnostics, fault domain, trace —
        # in the order the runtime's CONCERNS registry pins (ISSUE 17;
        # previously a six-deep wrapper stack built here)
        if "execute_columnar" in cls.__dict__:
            from spark_rapids_tpu.exec.runtime import make_operator_runtime

            cls.execute_columnar = make_operator_runtime(
                cls.execute_columnar)

    def collect_metrics(self, into=None) -> Dict[str, int]:
        into = into if into is not None else {}
        for m in self.metrics.values():
            into[f"{self.node_name}.{m.name}"] = (
                into.get(f"{self.node_name}.{m.name}", 0) + m.value)
        for c in self.children:
            if isinstance(c, TpuExec):
                c.collect_metrics(into)
        return into
