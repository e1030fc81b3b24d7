"""The one operator runtime — a single per-batch dispatch loop.

Reference analog: GpuExec's ``internalDoExecuteColumnar`` plus the
wrapper conventions scattered through the reference (NvtxRange,
RmmRapidsRetryIterator, GpuMetric update sites).  Before ISSUE 17 every
``execute_columnar`` was wrapped SIX deep by ``exec/base.py``
(``_cancel_guard(_governor_checkpoint(_progress(_diag(_fault_domain(
_traced(...))))))``) — five delegating generator frames resumed per
batch on every operator edge, each re-checking one ambient slot.  Here
one runtime generator owns the batch loop and dispatches every
registered per-batch concern from the flat :data:`CONCERNS` list; the
fault domain remains the sole inner iterator (it must restart the raw
operator), so the per-batch Python cost drops from eight generator
resumes to three (runtime -> fault domain -> operator).

Each concern keeps its exact pre-unification semantics, pinned by the
existing suites (tests/test_lifecycle.py, test_governor.py,
test_progress.py, test_diagnostics.py, test_resilience.py) plus the
strictly-fewer-calls pin in tests/test_operator_runtime.py:

* ``cancel`` — outermost of all: ONE ambient contextvar check per batch
  pull against the current query's CancelToken.  A tripped token raises
  QueryCancelled / QueryDeadlineExceeded from the pull site BEFORE any
  more work starts, never wrapped in a diagnostics span it would not
  close, and before ``begin_pull`` so the in-flight progress stack
  never holds a pull that was never started (ISSUE 4).
* ``governor`` — after the cancel check, before the progress span: with
  an active governor every batch pull runs one rate-limited pressure
  update and, when THIS query is the armed preemption target, the
  cooperative pause-and-spill.  A pause happens OUTSIDE the progress
  pull span (a paused query is degrading gracefully, not stalled) and
  AFTER the cancel check (a tripped token raises instead of pausing).
  Disabled: one ambient attribute check, zero governor-module calls
  (ISSUE 13).
* ``progress`` — its pull span covers the whole recorded batch,
  retries included; StopIteration closes the span ``finished=True``, an
  escaping exception closes it ``finished=False`` without counting an
  advance (ISSUE 12).  Disabled: one ambient attribute check.
* ``diagnostics`` — the operator span opens INSIDE the progress pull
  and covers the fault domain (retries / fallbacks attribute here);
  ``end_op`` runs on success, StopIteration, and every unwind (ISSUE
  3).  Disabled: one ambient attribute check.
* ``fault_domain`` — the stage-level fault domain
  (resilience/domain.py) drives the operator's raw iterator:
  classification, bounded transient/OOM restarts, runtime CPU
  fallback, breaker recording, chaos hooks.

The ``srt.op.<node_name>`` span (``perfcounters.span``) is part of the
loop, not a concern with an ambient switch: it opens around every
``next(it)`` of the fault domain's iterator and closes before the batch
is yielded, so no span is ever held across a ``yield`` and a retry's
time lies inside the operator that retried.  It puts the operator on
the profiler's clock and into the folded span table; the recorder's
``begin_op`` / ``end_op`` are called at the same point.

Docs: docs/whole_plan_fusion.md (the runtime dispatch contract).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

from spark_rapids_tpu.diagnostics import context as _DIAG
from spark_rapids_tpu.governor import context as _GOV
from spark_rapids_tpu.lifecycle.context import CURRENT as _QCTX
from spark_rapids_tpu.perfcounters import span as _span
from spark_rapids_tpu.progress import context as _PROG


@dataclasses.dataclass(frozen=True)
class Concern:
    """One registered per-batch concern.

    ``ambient`` returns the concern's active ambient state (or None when
    disabled) — the probes the runtime loop calls each batch come FROM
    this registry, so the list is the dispatch order, not documentation.
    ``kind`` is ``"per-pull"`` (probed around every batch pull) or
    ``"iterator"`` (owns/wraps the operator's iterator itself)."""

    name: str
    kind: str
    doc: str
    ambient: Optional[Callable[[], object]] = None


CONCERNS = (
    Concern("cancel", "per-pull",
            "CancelToken check before any per-batch work",
            _QCTX.get),
    Concern("governor", "per-pull",
            "pressure checkpoint + cooperative pause-and-spill",
            lambda: _GOV.GOVERNOR),
    Concern("progress", "per-pull",
            "live pull span: begin_pull/end_pull around the batch",
            lambda: _PROG.TRACKER),
    Concern("diagnostics", "per-pull",
            "operator span + attribution slot for the whole pull",
            lambda: _DIAG.RECORDER),
    Concern("fault_domain", "iterator",
            "classification / retries / CPU fallback / breaker"),
)

# the runtime loop's probes, bound once from the registry: dispatch
# order IS the tuple order above (pinned by tests/test_operator_runtime)
_AMBIENT_CANCEL = CONCERNS[0].ambient
_AMBIENT_GOVERNOR = CONCERNS[1].ambient
_AMBIENT_PROGRESS = CONCERNS[2].ambient
_AMBIENT_DIAGNOSTICS = CONCERNS[3].ambient


def make_operator_runtime(raw_fn):
    """Wrap a subclass's raw ``execute_columnar`` in the unified
    runtime (installed by ``TpuExec.__init_subclass__``)."""
    @functools.wraps(raw_fn)
    def execute_columnar(self, *a, **kw):
        from spark_rapids_tpu.resilience.domain import run_fault_domain

        it = run_fault_domain(self, raw_fn, a, kw)
        # one span object for all pulls of this iterator: opened around
        # next(it) and closed again before every yield
        sp = _span("srt.op." + self.node_name)
        try:
            while True:
                # -- per-pull concerns, in CONCERNS order ------------
                ctx = _AMBIENT_CANCEL()
                if ctx is not None:
                    ctx.token.check()
                gov = _AMBIENT_GOVERNOR()
                if gov is not None:
                    gov.batch_pull_checkpoint()
                trk = _AMBIENT_PROGRESS()
                rec = _AMBIENT_DIAGNOSTICS()
                if trk is None and rec is None:
                    # disabled fast path: four ambient checks, one pull
                    with sp:
                        try:
                            b = next(it)
                        except StopIteration:
                            return
                    yield b
                    continue
                h = trk.begin_pull(self) if trk is not None else None
                span = rec.begin_op(self) if rec is not None else None
                rows = None
                done = False
                b = None
                try:
                    try:
                        try:
                            with sp:
                                b = next(it)
                            rows = b.num_rows
                        except StopIteration:
                            done = True
                    finally:
                        # the diagnostics span closes FIRST (it opened
                        # last), on success, exhaustion, and unwind
                        if span is not None:
                            path, token, t0 = span
                            rec.end_op(path, token, t0, rows)
                except BaseException:
                    # the pull died (cancel trip, operator failure):
                    # close the in-flight progress entry without
                    # counting an advance, then let the unwind proceed
                    if h is not None:
                        trk.end_pull(h, None, 0, finished=False)
                    raise
                if done:
                    if h is not None:
                        trk.end_pull(h, None, 0, finished=True)
                    return
                if h is not None:
                    trk.end_pull(h, rows, b.nbytes(), finished=False)
                yield b
        finally:
            it.close()

    return execute_columnar
