"""The unified operator runtime (ISSUE 17, exec/runtime.py).

Pins the CONCERNS registry (order IS dispatch order), the
__init_subclass__ install, and the tentpole's overhead claim: with
diagnostics / progress / governor / telemetry all off, the unified
runtime makes STRICTLY FEWER Python calls per batch than the
pre-unification six-deep wrapper stack (replicated below from the old
exec/base.py, its trace wrapper on the untraced branch it shipped on:
17 calls a pull, as before ISSUE 26), and zero
calls into the disabled concerns' modules.  Since ISSUE 26 the unified
side carries the always-on operator span (``srt.op.<node_name>``).
"""
import cProfile
import functools
import pstats

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.exec.runtime import CONCERNS, make_operator_runtime

SCHEMA = T.StructType([T.StructField("v", T.LONG, False)])


# ---------------------------------------------------------------------------
# the legacy six-deep wrapper stack, replicated verbatim (pre-ISSUE-17
# exec/base.py) — the baseline the strictly-fewer-calls pin compares to
# ---------------------------------------------------------------------------

def _traced(fn):
    """The legacy trace wrapper's untraced branch, the one that ran as it
    shipped (its switch was an attribute of the operator, off by
    default; the traced branch went with the switch in ISSUE 26): one
    delegating generator frame a pull."""
    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        yield from fn(self, *a, **kw)

    return wrapper


def _progress(fn):
    from spark_rapids_tpu.progress import context as _PROG

    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        it = fn(self, *a, **kw)
        try:
            while True:
                trk = _PROG.TRACKER
                h = trk.begin_pull(self) if trk is not None else None
                if h is None:
                    try:
                        b = next(it)
                    except StopIteration:
                        return
                    yield b
                    continue
                try:
                    b = next(it)
                except StopIteration:
                    trk.end_pull(h, None, 0, finished=True)
                    return
                except BaseException:
                    trk.end_pull(h, None, 0, finished=False)
                    raise
                trk.end_pull(h, b.num_rows, b.nbytes(), finished=False)
                yield b
        finally:
            it.close()

    return wrapper


def _governor_checkpoint(fn):
    from spark_rapids_tpu.governor import context as _GOV

    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        it = fn(self, *a, **kw)
        try:
            while True:
                gov = _GOV.GOVERNOR
                if gov is not None:
                    gov.batch_pull_checkpoint()
                try:
                    b = next(it)
                except StopIteration:
                    return
                yield b
        finally:
            it.close()

    return wrapper


def _cancel_guard(fn):
    from spark_rapids_tpu.lifecycle.context import CURRENT as _QCTX

    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        it = fn(self, *a, **kw)
        try:
            while True:
                ctx = _QCTX.get()
                if ctx is not None:
                    ctx.token.check()
                try:
                    b = next(it)
                except StopIteration:
                    return
                yield b
        finally:
            it.close()

    return wrapper


def _fault_domain(fn):
    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        from spark_rapids_tpu.resilience.domain import run_fault_domain

        yield from run_fault_domain(self, fn, a, kw)

    return wrapper


def _diag(fn):
    from spark_rapids_tpu.diagnostics import context as _CTX

    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        it = fn(self, *a, **kw)
        try:
            while True:
                rec = _CTX.RECORDER
                if rec is None:
                    try:
                        b = next(it)
                    except StopIteration:
                        return
                    yield b
                    continue
                span = rec.begin_op(self)
                if span is None:
                    try:
                        b = next(it)
                    except StopIteration:
                        return
                    yield b
                    continue
                path, token, t0 = span
                rows = None
                try:
                    try:
                        b = next(it)
                    except StopIteration:
                        return
                    rows = b.num_rows
                finally:
                    rec.end_op(path, token, t0, rows)
                yield b
        finally:
            it.close()

    return wrapper


def _legacy_stack(raw_fn):
    return _cancel_guard(_governor_checkpoint(
        _progress(_diag(_fault_domain(_traced(raw_fn))))))


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

class _Source(TpuExec):
    """Minimal operator: yields pre-built batches, no device work."""

    def __init__(self, batches):
        super().__init__([])
        self._b = batches

    @property
    def output(self):
        return SCHEMA

    def execute_columnar(self):
        for b in self._b:
            yield b


def _raw(self):
    for b in self._b:
        yield b


def _batches(n):
    b = ColumnarBatch.from_pydict({"v": [1, 2, 3]}, SCHEMA)
    return [b] * n


def _assert_all_concerns_off():
    from spark_rapids_tpu.diagnostics import context as _DIAG
    from spark_rapids_tpu.governor import context as _GOV
    from spark_rapids_tpu.lifecycle.context import CURRENT as _QCTX
    from spark_rapids_tpu.progress import context as _PROG

    assert _QCTX.get() is None and _GOV.GOVERNOR is None
    assert _PROG.TRACKER is None and _DIAG.RECORDER is None


def _steady_profile(make_iter, pulls=200):
    """cProfile stats over ``pulls`` steady-state batch pulls (iterator
    setup and first pull excluded)."""
    it = make_iter()
    next(it)
    pr = cProfile.Profile()
    pr.enable()
    for _ in range(pulls):
        next(it)
    pr.disable()
    return pstats.Stats(pr)


# ---------------------------------------------------------------------------
# pins
# ---------------------------------------------------------------------------

def test_concerns_registry_order():
    """The registry IS the dispatch order: cancel first (a tripped
    token raises before any work), governor before the progress span
    (a pause is not a stall), diagnostics innermost of the per-pull
    concerns; the fault domain owns the iterator.  Five concerns: the
    operator's trace span is no concern with an ambient switch, it is
    part of the loop (test_span.py pins it)."""
    assert [c.name for c in CONCERNS] == [
        "cancel", "governor", "progress", "diagnostics", "fault_domain"]
    assert [c.kind for c in CONCERNS] == ["per-pull"] * 4 + ["iterator"]
    for c in CONCERNS:
        assert c.doc
        if c.kind == "per-pull":
            assert c.ambient is not None


def test_subclass_install():
    """__init_subclass__ installs the runtime around any subclass's own
    execute_columnar (and only around its own)."""
    raw = _Source.__dict__["execute_columnar"]
    assert raw.__wrapped__ is not None          # functools.wraps chain
    assert raw.__name__ == "execute_columnar"

    class _Derived(_Source):                     # no override: inherited
        pass

    assert "execute_columnar" not in _Derived.__dict__

    op = _Source(_batches(3))
    out = list(op.execute_columnar())
    assert len(out) == 3 and out[0].num_rows == 3


def test_disabled_path_zero_concern_module_calls():
    """Everything off: the steady-state loop never enters the progress /
    governor / diagnostics / lifecycle modules (the per-module
    disabled-path contract each suite pins individually, now enforced
    at the unified dispatch site)."""
    _assert_all_concerns_off()
    op = _Source(_batches(250))
    stats = _steady_profile(lambda: op.execute_columnar())
    banned = ("spark_rapids_tpu/progress/", "spark_rapids_tpu/governor/",
              "spark_rapids_tpu/diagnostics/", "spark_rapids_tpu/lifecycle/")
    offenders = [f for f in stats.stats
                 if any(mod in f[0].replace("\\", "/") for mod in banned)]
    assert not offenders, offenders


def test_unified_runtime_strictly_fewer_calls_than_legacy():
    """THE tentpole overhead pin: with every concern disabled, the
    unified runtime's per-batch Python call count, the always-on
    operator span included, is STRICTLY below that of the replicated
    six-deep wrapper stack as it shipped (untraced)."""
    _assert_all_concerns_off()
    pulls = 200

    legacy_op = _Source(_batches(pulls + 50))
    legacy_fn = _legacy_stack(_raw)
    legacy_calls = _steady_profile(
        lambda: legacy_fn(legacy_op), pulls).total_calls

    unified_op = _Source(_batches(pulls + 50))
    unified_fn = make_operator_runtime(_raw)
    # as inside a collect: the operator's span nests in srt.collect and
    # srt.execute, and only the outermost span merges into the counters
    with PC.span("srt.execute"):
        unified_calls = _steady_profile(
            lambda: unified_fn(unified_op), pulls).total_calls

    assert unified_calls < legacy_calls, (unified_calls, legacy_calls)
    # and the margin is structural, not noise: the legacy stack resumes
    # five delegating generator frames per batch that the runtime does
    # not have (runtime -> fault domain -> raw is the whole chain).  The
    # span, one object per operator iterator, spends four of those five
    # calls on its table (enter, exit, the clock twice): 16 calls a pull
    # against 17
    assert legacy_calls == 17 * pulls + 1, legacy_calls   # the yardstick
    assert legacy_calls - unified_calls >= pulls, (
        unified_calls, legacy_calls)


def test_results_identical_to_legacy():
    """Same batches, same order, same exhaustion through both stacks."""
    data = _batches(7)
    legacy = list(_legacy_stack(_raw)(_Source(data)))
    unified = list(make_operator_runtime(_raw)(_Source(data)))
    assert len(legacy) == len(unified) == 7
    for a, b in zip(legacy, unified):
        assert a is b
