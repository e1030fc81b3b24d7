"""In-process executable registry — the same-process half of the cache.

Reference analog: none — libcudf kernels are precompiled, so the reference
never thinks about executable identity.  On TPU the XLA compile IS the
kernel build step; this registry makes a compiled stage program a
process-wide asset keyed by its semantic fingerprint instead of a private
of whichever exec instance happened to trace it first.  A re-planned query
(fresh DataFrame, fresh session with equal settings, breaker-forced
re-plan) therefore compiles nothing the process has already built.

Entries hold the ``tpu_jit`` wrapper (shape-polymorphic: jax's own cache
keys the per-bucket executables under it) plus ``aux`` — trace-time
metadata the builder produced (e.g. a fused stage's ANSI error messages,
which fill as a tracing side effect and must travel WITH the executable).

Concurrency contract with the AOT pool (aot.py): while a background
compile of an entry is in flight, a runtime ``cached_program`` lookup for
the same key BLOCKS on the entry's ready event — the iterator waits only
when it reaches a program that is not ready yet, never races a duplicate
compile.

Bounded: ``spark.rapids.tpu.compile.registry.maxPrograms`` LRU-evicts so a
long test session cannot pin every executable it ever built.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Tuple

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu.diagnostics import context as _DIAG


class ProgramEntry:
    """One registered program: jitted callable + trace-time aux data."""

    __slots__ = ("key", "label", "jitted", "aux", "aot_state",
                 "ready_event", "compiled_by", "created_at", "hits",
                 "handoff_pending")

    def __init__(self, key: str, jitted, aux, label: str = ""):
        self.key = key
        self.label = label
        self.jitted = jitted
        self.aux = aux
        # None = never touched by the AOT pool; "inflight" = a background
        # compile owns it; "ready" = background compile finished (ok or not)
        # None = never touched by the AOT pool (or stolen back by the
        # runtime); "queued" = submitted, job not started; "compiling" =
        # a pool worker owns the trace; "ready" = job finished (ok or not)
        self.aot_state: Optional[str] = None
        self.ready_event = threading.Event()
        self.compiled_by = "inline"
        self.created_at = time.monotonic()
        self.hits = 0
        # True while an AOT-created entry awaits its OWN query's first
        # runtime lookup — that handoff is not reuse and must not count
        self.handoff_pending = False

    def traced(self) -> bool:
        """True once at least one shape specialization exists."""
        try:
            return self.jitted._cache_size() > 0
        except Exception:
            return True  # unknown cache API: assume warm, never re-submit


class ProgramRegistry:
    def __init__(self, max_programs: int = 1024):
        self._lock = threading.RLock()
        self._entries: "OrderedDict[str, ProgramEntry]" = OrderedDict()
        self.max_programs = max_programs

    def lookup(self, key: str,
               factory: Callable[[], Tuple[Any, Any]],
               label: str = "",
               wait_inflight: bool = True,
               created_out: Optional[list] = None) -> ProgramEntry:
        """Return the entry for ``key``, building it via ``factory`` on a
        miss.  ``factory() -> (jitted, aux)`` must be cheap (closure + jit
        wrapper creation; no tracing/compiling happens here).
        ``wait_inflight=False`` (the AOT submitter) returns immediately
        even when a background compile owns the entry — only RUNTIME
        lookups block for the executable.  ``created_out`` (a list)
        receives True/False for miss/hit."""
        with self._lock:
            e = self._entries.get(key)
            created = e is None
            if e is not None:
                self._entries.move_to_end(key)
                e.hits += 1
                # not reuse, so not a hit: the AOT submitter's own
                # re-lookups, and the first RUNTIME claim of an entry the
                # same plan's AOT pass just created (the handoff) —
                # otherwise every cold query would report hits == misses
                if wait_inflight:
                    if e.handoff_pending:
                        e.handoff_pending = False
                    else:
                        PC.bump("compile_cache_hits")
                        rec = _DIAG.RECORDER
                        if rec is not None:
                            rec.cache_event(True, label or e.label)
                else:
                    # a LATER submission touching the entry means the
                    # original query is done with it: any future runtime
                    # claim is genuine reuse
                    e.handoff_pending = False
                # steal: a background job still QUEUED (not compiling)
                # should not make the runtime wait behind unrelated pool
                # work — compiling inline now is strictly faster; the job
                # sees the state flip and becomes a no-op
                if wait_inflight and e.aot_state == "queued":
                    e.aot_state = None
                    e.ready_event.set()
            else:
                jitted, aux = factory()
                e = ProgramEntry(key, jitted, aux, label)
                e.handoff_pending = not wait_inflight
                self._entries[key] = e
                PC.bump("compile_cache_misses")
                rec = _DIAG.RECORDER
                if rec is not None:
                    rec.cache_event(False, label)
                # LRU bound; never evict an entry a background compile
                # still owns (the recompile would double minutes of work)
                excess = len(self._entries) - max(self.max_programs, 1)
                if excess > 0:
                    for k in list(self._entries):
                        if excess <= 0:
                            break
                        cand = self._entries[k]
                        if cand.aot_state in ("queued", "compiling"):
                            continue
                        del self._entries[k]
                        excess -= 1
            if created_out is not None:
                created_out.append(created)
        # outside the lock: a hit on an entry whose AOT compile is
        # actively running waits for it (the "iterator blocks only if the
        # program is not ready yet" contract); the job sets the event in
        # a finally.  Bounded as a last-resort guard — if the event never
        # fires (killed pool, interpreter teardown) the caller proceeds
        # and compiles inline, which is always safe
        # generous cap: proceeding while the pool worker is mid-trace of
        # the SAME fn would race the shared trace-time aux (ANSI message
        # store) — blocking longer is strictly safer than corrupting it,
        # and "compiling" is only ever set by an actively running job.
        # Cancellable (ISSUE 4): a cancelled/deadline-tripped query must
        # not sit behind minutes of pool compile work, so inside a query
        # the wait polls the CancelToken in short slices
        from spark_rapids_tpu.lifecycle.context import current_token

        token = current_token()
        waited = 0.0
        while wait_inflight and e.aot_state == "compiling" \
                and waited < 7200.0:
            slice_s = 0.05 if token is not None else 30.0
            if e.ready_event.wait(slice_s):
                break
            waited += slice_s
            if token is not None:
                token.check()
        return e

    def peek(self, key: str) -> Optional[ProgramEntry]:
        with self._lock:
            return self._entries.get(key)

    def stats(self) -> dict:
        with self._lock:
            states = {}
            for e in self._entries.values():
                states[e.aot_state or "inline"] = \
                    states.get(e.aot_state or "inline", 0) + 1
            return {"programs": len(self._entries), "by_state": states}

    def entries(self):
        with self._lock:
            return list(self._entries.values())

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


_REGISTRY = ProgramRegistry()


def get_registry() -> ProgramRegistry:
    return _REGISTRY


def reset_registry() -> None:
    _REGISTRY.reset()


def registry_enabled() -> bool:
    from spark_rapids_tpu.config import COMPILE_REGISTRY_ENABLED, get_conf

    return bool(get_conf().get(COMPILE_REGISTRY_ENABLED))


def cached_program(key_parts, factory: Callable[[], Tuple[Any, Any]],
                   label: str = "",
                   wait_inflight: bool = True,
                   created_out: Optional[list] = None) -> ProgramEntry:
    """The exec-layer entry point: fingerprint ``key_parts``, return the
    shared entry (or an unregistered one when the registry kill switch is
    off, or when key_parts is None — i.e. the caller's expressions were
    not safely fingerprintable)."""
    from spark_rapids_tpu.compilecache.keys import fingerprint

    if key_parts is None or not registry_enabled():
        jitted, aux = factory()
        if created_out is not None:
            created_out.append(True)
        return ProgramEntry("<unregistered>", jitted, aux, label)
    from spark_rapids_tpu.config import COMPILE_REGISTRY_MAX_PROGRAMS, \
        get_conf

    _REGISTRY.max_programs = int(get_conf().get(
        COMPILE_REGISTRY_MAX_PROGRAMS))
    return _REGISTRY.lookup(fingerprint(*key_parts), factory, label,
                            wait_inflight=wait_inflight,
                            created_out=created_out)


def cached_jit_program(key_parts, builder, label: str = "",
                       name: Optional[str] = None, **jit_kwargs):
    """The shared exec-layer wrapper most call sites want: a ``tpu_jit``
    of ``builder`` shared through the registry when ``key_parts`` is
    fingerprintable, instance-private otherwise.  ``name`` is the
    program's name in a trace (``tpu_jit``), no part of the key.
    Returns the jitted callable."""
    from spark_rapids_tpu.perfcounters import tpu_jit

    if key_parts is None:
        return tpu_jit(builder, name, **jit_kwargs)
    return cached_program(
        key_parts, lambda: (tpu_jit(builder, name, **jit_kwargs), None),
        label=label).jitted
