"""Plan-time AOT compilation — warm every stage program before its batch.

After overrides produce the exec tree, :func:`submit_plan` walks it in
execution order (post-order: the operators that run first submit first),
asks each exec for its :meth:`aot_programs` — the (stage function x
shape-bucket) programs the query will need, predicted from the plan's
static row estimates (``aot_output_rows``) — and compiles them on a
bounded background thread pool.  Batch 1 of operator 1 then overlaps the
compiles of everything downstream instead of serializing minute-long
compiles between launches; the runtime registry lookup blocks only when
it reaches a program whose background compile is still in flight.

Shape prediction is deliberately conservative: a program is enumerated
only when its input schema is fully static (flat numeric/decimal/bool/
date/timestamp columns — string widths and nested element widths are
data-dependent) and its input row count is derivable from the plan
(local/range scans and the narrow operators above them; anything below an
exchange or aggregate output is unknown).  A wrong guess only wastes one
background compile; a skipped program just compiles inline as before.

Warm-ups run ``jitted.lower(*abstract).compile()`` over ShapeDtypeStruct
operands — no device memory is allocated and nothing executes, so the
pool never competes with the query for HBM or bypasses the admission
semaphore.  The XLA compile lands in the persistent on-disk cache
(``spark.rapids.tpu.compile.cacheDir``, on by default), so the runtime's
first dispatch — and every future process — deserializes the executable
instead of compiling it: the minutes-long XLA build happens exactly once,
off the critical path.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future  # annotation only; pool is daemon
from typing import Callable, List, Optional, Sequence, Tuple

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.compilecache.registry import (
    ProgramEntry,
    cached_program,
    registry_enabled,
)


class AotProgram:
    """One enumerable program: registry key parts + builder + dummy args.

    ``args_factory() -> list of concrete arg tuples`` — one per predicted
    shape bucket; the jitted program is shape-polymorphic, so one entry
    warms every bucket it will serve."""

    __slots__ = ("key_parts", "factory", "args_factory", "label")

    def __init__(self, key_parts, factory, args_factory, label: str):
        self.key_parts = key_parts
        self.factory = factory        # () -> (jitted, aux)
        self.args_factory = args_factory  # () -> [args, ...] (may be [])
        self.label = label


# ---------------------------------------------------------------------------
# dummy-batch construction (the abstract operand for the warm-up call)
# ---------------------------------------------------------------------------

def _static_field(dt: T.DataType) -> bool:
    """True when the device layout of this type is fully determined by the
    schema (no data-dependent widths)."""
    if isinstance(dt, (T.StringType, T.ArrayType, T.MapType, T.StructType)):
        return False
    return True


def abstract_scalar(dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((), jnp.dtype(dtype))


def abstract_array(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def dummy_columns(schema: T.StructType, capacity: int):
    """ABSTRACT device columns (jax.ShapeDtypeStruct leaves) of
    ``capacity`` for a static schema, or None when any field's layout is
    data-dependent.  Abstract operands let the warm-up ``lower().
    compile()`` without allocating a byte of device memory or executing
    anything — the pool never competes with the query for HBM and never
    bypasses the admission semaphore."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import DeviceColumn

    cols = []
    for f in schema.fields:
        dt = f.dataType
        if not _static_field(dt):
            return None
        validity = abstract_array((capacity,), jnp.bool_)
        if isinstance(dt, T.DecimalType) and dt.is_128:
            data = abstract_array((capacity, 2), jnp.int64)
        else:
            try:
                sdt = T.storage_dtype(dt)
            except Exception:
                return None
            data = abstract_array((capacity,), sdt)
        cols.append(DeviceColumn(dt, validity, data=data))
    return tuple(cols)


def dummy_batch_args(schema: T.StructType, rows: int):
    """The canonical (cols, num_rows) call signature most stage programs
    take, at the bucket capacity ``rows`` rounds up to."""
    import jax.numpy as jnp

    cols = dummy_columns(schema, bucket_of(rows))
    if cols is None:
        return None
    return (cols, abstract_scalar(jnp.int32))


def bucket_of(rows: int) -> int:
    # DEFAULT_ROW_BUCKETS, not the conf ladder: the runtime paths this
    # predicts for (from_host_columns, Range, concat) all bucket with the
    # module default — predicting from the conf would warm shapes nothing
    # ever dispatches whenever the conf differs
    from spark_rapids_tpu.columnar.column import (
        DEFAULT_ROW_BUCKETS,
        round_up_bucket,
    )

    return round_up_bucket(max(int(rows), 1), DEFAULT_ROW_BUCKETS)


def batch_caps(node):
    """Predicted per-batch capacities of an exec's output, or None."""
    fn = getattr(node, "aot_output_caps", None)
    return fn() if fn is not None else None


def concat_caps(node):
    """Predicted capacity list for the CONCATENATION of an exec's output
    batches: from its row estimate, or its capacity estimate when it is
    known to emit a single batch."""
    rows_fn = getattr(node, "aot_output_rows", None)
    rows = rows_fn() if rows_fn is not None else None
    if rows:
        return [bucket_of(sum(rows))]
    single = getattr(node, "aot_emits_single_batch", None)
    if single is not None and single():
        return batch_caps(node)
    return None


def single_word_keys(key_exprs) -> bool:
    """True when every join-key expression packs to exactly one sort-key
    word (flat <=64-bit types) — the precondition for predicting the
    probe program's build-words operand shape at plan time."""
    for e in key_exprs or []:
        dt = getattr(e, "dataType", None)
        if dt is None or not _static_field(dt):
            return False
        if isinstance(dt, T.DecimalType) and dt.is_128:
            return False
    return True


# ---------------------------------------------------------------------------
# the background pool
# ---------------------------------------------------------------------------

class _DaemonPool:
    """Minimal daemon-thread worker pool.  concurrent.futures joins its
    non-daemon workers at interpreter exit, which would make a short
    script hang for the duration of every queued speculative compile
    (seconds each); daemon workers just die —
    abandoned jobs' entries stay 'inflight', which only runtime lookups
    in this (already exiting) process would ever wait on."""

    def __init__(self, n: int):
        import queue

        self._q: "queue.Queue" = queue.Queue()
        self._threads = []
        for i in range(max(1, n)):
            t = threading.Thread(target=self._work,
                                 name=f"srt-aot-{i}", daemon=True)
            t.start()
            self._threads.append(t)

    def _work(self):
        while True:
            job = self._q.get()
            if job is None:          # stop(): one sentinel per worker
                self._q.task_done()
                return
            fn, args = job
            try:
                fn(*args)
            except Exception:
                pass
            finally:
                self._q.task_done()

    def stop(self, timeout_s: float) -> bool:
        """Let every queued job finish, then end the workers (FIFO: the
        sentinels queue behind the jobs).  True when all threads ended
        within the timeout."""
        for _ in self._threads:
            self._q.put(None)
        deadline = time.monotonic() + max(timeout_s, 0.0)
        for t in self._threads:
            t.join(max(deadline - time.monotonic(), 0.0))
        return not any(t.is_alive() for t in self._threads)

    def submit(self, fn, *args):
        self._q.put((fn, args))
        return None

    def quiesce(self, timeout_s: float) -> bool:
        """Bounded wait for the queue to drain (all submitted jobs
        finished).  Daemon workers dying MID-COMPILE at interpreter
        exit can abort the whole process inside XLA's C++ teardown, so
        batch drivers that submit speculative compiles near their exit
        (the overload stress harness, ISSUE 13) drain here first.
        True when the pool went idle within the timeout."""
        import time as _time

        deadline = _time.monotonic() + max(timeout_s, 0.0)
        while _time.monotonic() < deadline:
            if self._q.unfinished_tasks == 0:
                return True
            _time.sleep(0.05)
        return self._q.unfinished_tasks == 0


_POOL: Optional[_DaemonPool] = None
_POOL_LOCK = threading.Lock()


def quiesce_aot(timeout_s: float = 30.0) -> bool:
    """Drain the background AOT pool if one exists (bounded); see
    :meth:`_DaemonPool.quiesce`."""
    pool = _POOL
    return pool.quiesce(timeout_s) if pool is not None else True


def shutdown_aot(timeout_s: float = 30.0) -> bool:
    """Drain the background AOT pool and END its threads (bounded); the
    next submission builds a fresh pool.  For owners that must leave no
    background XLA compile running behind them (a test module's
    teardown, a batch driver's exit)."""
    global _POOL
    with _POOL_LOCK:
        pool, _POOL = _POOL, None
    return pool.stop(timeout_s) if pool is not None else True


def _get_pool() -> _DaemonPool:
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            from spark_rapids_tpu.config import COMPILE_AOT_THREADS, get_conf

            _POOL = _DaemonPool(int(get_conf().get(COMPILE_AOT_THREADS)))
        return _POOL


def _compile_job(entry: ProgramEntry,
                 args_factory: Callable[[], Optional[tuple]],
                 label: str, conf=None, token=None,
                 owner_qid: Optional[str] = None) -> None:
    """Warm one program via the AOT API: ``jitted.lower(*abstract).
    compile()`` on the RAW jitted (bypassing the launch/compile perf
    counters — a background warm-up is not an engine launch).  Operands
    are abstract (ShapeDtypeStructs), so nothing allocates on device and
    nothing executes; the trace + XLA compile also land in JAX's
    lowering/executable caches and (when configured) the persistent
    on-disk cache, which is where the runtime's own dispatch finds them.
    The submitting query's conf is pinned thread-locally for the trace
    (expressions read conf at trace time; the main thread may re-plan
    another session meanwhile)."""
    import contextlib

    from spark_rapids_tpu.config import ambient_conf

    from spark_rapids_tpu.compilecache.registry import get_registry

    # claim the entry: a runtime lookup may have STOLEN a still-queued
    # job (compiling inline beats waiting behind the pool) — then this
    # job is a no-op
    with get_registry()._lock:
        if entry.aot_state != "queued":
            entry.ready_event.set()
            return
        entry.aot_state = "compiling"
    scope = ambient_conf(conf) if conf is not None \
        else contextlib.nullcontext()
    try:
        with scope:
            # a cancelled submitter's speculative warm-ups are dead work:
            # skip them (the runtime path compiles inline if ever needed)
            if token is not None and token.cancelled:
                return
            arg_sets = args_factory() or []
            if arg_sets and not isinstance(arg_sets, list):
                arg_sets = [arg_sets]
            raw = getattr(entry.jitted, "_jitted", entry.jitted)
            for args in arg_sets:
                if args is None:
                    continue
                if token is not None and token.cancelled:
                    return
                t0 = time.perf_counter_ns()
                raw.lower(*args).compile()
                dt = time.perf_counter_ns() - t0
                entry.compiled_by = "aot"
                PC.bump("aot_compiles")
                from spark_rapids_tpu.diagnostics import context as _DIAG

                rec = _DIAG.RECORDER
                if rec is not None:
                    rec.aot_compile(label, dt)
                # separate counter: compile_wall_ns is the CRITICAL-PATH
                # (inline) compile wall; folding background wall into it
                # would double-count every warmed program (the runtime's
                # first dispatch still pays the cache-deserialize there)
                PC.bump("aot_compile_wall_ns", dt)
                # live progress (ISSUE 12): the pool thread's wall
                # shows up under the SUBMITTING query, not nowhere
                from spark_rapids_tpu.progress import context as _PROG

                if _PROG.TRACKER is not None:
                    _PROG.TRACKER.add_background(
                        owner_qid, "aot_compile", dt)
    except Exception:
        # a failed warm-up must never hurt the query: the runtime path
        # compiles inline exactly as it would have without AOT
        PC.bump("aot_compile_errors")
    finally:
        entry.aot_state = "ready"
        entry.ready_event.set()


class AotSubmission:
    """Handle over one plan's submitted warm-ups."""

    def __init__(self):
        self.items: List[Tuple[str, ProgramEntry, Optional[Future]]] = []
        self.skipped: List[str] = []

    def add(self, label: str, entry: ProgramEntry, fut: Optional[Future]):
        self.items.append((label, entry, fut))

    @property
    def programs(self) -> List[str]:
        return [label for label, _, _ in self.items]

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted compile finished; True if all did.
        Cancellable: raises if the current query's token trips while
        waiting."""
        from spark_rapids_tpu.lifecycle.context import current_token

        token = current_token()
        deadline = None if timeout is None else time.monotonic() + timeout
        for _, entry, _fut in self.items:
            if entry.aot_state is None:
                continue   # was already compiled before this submission
            while True:
                left = None if deadline is None \
                    else max(deadline - time.monotonic(), 0.0)
                slice_s = 0.05 if token is not None else left
                if left is not None:
                    slice_s = min(slice_s, left) if slice_s is not None \
                        else left
                if entry.ready_event.wait(slice_s):
                    break
                if token is not None:
                    token.check()
                if deadline is not None and time.monotonic() >= deadline:
                    return False
        return True

    def states(self) -> dict:
        out = {}
        for label, entry, _ in self.items:
            out[label] = entry.aot_state or (
                "ready" if entry.traced() else "cold")
        return out

    def summary(self) -> str:
        st = self.states()
        ready = sum(1 for v in st.values() if v == "ready")
        return (f"aot: {ready}/{len(st)} programs ready, "
                f"{len(self.skipped)} skipped")


def submit_plan(root, wait: bool = False) -> AotSubmission:
    """Enumerate and background-compile every predictable program of an
    exec tree.  Post-order: the programs the iterator needs first are
    submitted (and thus likely finish) first."""
    sub = AotSubmission()
    if not registry_enabled():
        return sub
    # the lower().compile() warm-up does NOT populate the jit dispatch
    # cache (verified on jax 0.4.37: _cache_size() stays 0); its product
    # reaches the runtime THROUGH the persistent on-disk cache, which the
    # first dispatch deserializes.  Without a configured cache dir the
    # pool would double every compile and save nothing — skip entirely
    try:
        import jax

        if not getattr(jax.config, "jax_compilation_cache_dir", None):
            sub.skipped.append("persistent cache disabled: AOT would "
                              "double compile work")
            return sub
    except Exception:
        return sub
    from spark_rapids_tpu.config import get_conf
    from spark_rapids_tpu.lifecycle.context import current, current_token

    conf = get_conf()   # pinned for every background trace of this plan
    token = current_token()   # the submitting query's cancel token
    ctx = current()           # ...and its id, for progress attribution
    owner_qid = ctx.query_id if ctx is not None else None
    pool = _get_pool()
    seen_keys = set()
    for node in _post_order(root):
        progs = ()
        try:
            progs = node.aot_programs()
        except Exception:
            sub.skipped.append(f"{type(node).__name__}: enumeration failed")
            continue
        for prog in progs or ():
            if prog.key_parts is None:
                sub.skipped.append(prog.label)
                continue
            from spark_rapids_tpu.compilecache.keys import fingerprint

            # dedup BEFORE the registry lookup: a duplicate's non-waiting
            # hit would clear the original's handoff flag and miscount
            # the query's own first runtime claim as a cache hit
            fp = fingerprint(*prog.key_parts)
            if fp in seen_keys:
                continue
            seen_keys.add(fp)
            try:
                # non-blocking: the submitter must never sleep on another
                # plan's (or a duplicate program's) in-flight compile —
                # only runtime lookups wait for executables
                created: list = []
                entry = cached_program(prog.key_parts, prog.factory,
                                       prog.label, wait_inflight=False,
                                       created_out=created)
            except Exception:
                sub.skipped.append(prog.label)
                continue
            if not (created and created[0]):
                # ONLY entries this submission itself created are
                # background-compiled: an entry another (possibly
                # concurrently executing) query created may be mid-trace
                # on its thread — racing a second trace of the same fn
                # would corrupt shared trace-time aux state
                sub.add(prog.label, entry, None)
                continue
            entry.aot_state = "queued"
            entry.ready_event.clear()
            try:
                fut = pool.submit(_compile_job, entry, prog.args_factory,
                                  prog.label, conf, token, owner_qid)
            except Exception:
                # a failed submit (e.g. executor shutting down) must not
                # leave a queued entry nobody will ever mark ready —
                # the runtime lookup would block on it forever
                entry.aot_state = None
                entry.ready_event.set()
                sub.skipped.append(prog.label)
                continue
            sub.add(prog.label, entry, fut)
    if wait:
        sub.wait()
    return sub


def _post_order(node):
    for c in getattr(node, "children", []) or []:
        if hasattr(c, "aot_programs") or getattr(c, "children", None):
            yield from _post_order(c)
    if hasattr(node, "aot_programs"):
        yield node


def maybe_submit_aot(root, conf) -> Optional[AotSubmission]:
    """collect()-time hook: submit once per planned exec tree, never let a
    warm-up failure reach the query."""
    from spark_rapids_tpu.config import COMPILE_AOT_ENABLED

    try:
        if not conf.get(COMPILE_AOT_ENABLED):
            return None
        # overload governor (ISSUE 13): under YELLOW/RED, background
        # compiles DEFER — the pool threads' trace work and executable
        # memory are speculation pressure can reclaim.  Nothing is
        # stamped on the root, so a later collect under GREEN submits
        # normally.
        from spark_rapids_tpu.governor import context as _GOV

        gov = _GOV.GOVERNOR
        if gov is not None and gov.pause_background():
            return None
        existing = getattr(root, "_aot_submission", None)
        if existing is not None:
            return existing
        sub = submit_plan(root)
        try:
            root._aot_submission = sub
        except Exception:
            pass
        return sub
    except Exception:
        return None
