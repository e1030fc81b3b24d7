"""Failure classification — one taxonomy for everything escaping a stage.

Reference analog: the retry state machine in SURVEY.md §2.3 distinguishes
GpuRetryOOM / GpuSplitAndRetryOOM (recoverable, roll back + spill/split)
from everything else (the task dies and CPU Spark reruns the stage).  XLA
surfaces a richer error space — jaxlib raises ``XlaRuntimeError`` carrying
an absl status code, often *wrapped* by framework layers via ``raise ...
from e`` — so classification must walk the cause chain and read status
codes, not just ``repr`` the outermost exception.

Classes:

  * DEVICE_OOM      — RESOURCE_EXHAUSTED anywhere in the chain, or the
                      cooperative TpuRetryOOM/TpuSplitAndRetryOOM pair.
                      Handled by the memory/retry.py path: spill + retry.
  * TRANSIENT       — infrastructure errors that may heal on their own
                      (UNAVAILABLE, DEADLINE_EXCEEDED, ABORTED, CANCELLED,
                      UNKNOWN, INTERNAL; runtime/plugin disconnects).
                      Bounded retry with exponential backoff + jitter.
  * DETERMINISTIC   — compile / lowering / unsupported-dtype / shape
                      errors: retrying re-derives the same failure, so the
                      stage goes straight to the CPU oracle (and feeds the
                      circuit breaker).
  * PROPAGATE       — semantic errors that are the *correct result* of the
                      query (ANSI overflow, FAILFAST parse errors) plus
                      control-flow exceptions; the fault domain must
                      re-raise these unchanged.
  * WORKER_LOST     — a distributed worker is gone for good (heartbeat
                      silence, dead socket past the transient budget).
                      Not a per-batch-backoff case and not an operator
                      bug: the distributed tier answers with partition
                      re-placement + re-drive from the producer-side
                      spilled partition queues; if it still escapes, the
                      fault domain falls back WITHOUT feeding the
                      operator's circuit-breaker key (infrastructure
                      churn must not banish a healthy stage to CPU).
  * WORKER_DEGRADED — a distributed worker is SLOW, not dead (gray
                      failure, ISSUE 20): persistent soft-deadline
                      misses or a latency EWMA past slowFactor x the
                      fleet median.  Same re-drive answer as
                      WORKER_LOST (WorkerDegraded subclasses
                      WorkerLost) but the worker stays a member —
                      DEGRADED, demoted in placement, promotable back
                      — and the quarantine breaker stays closed.
                      Never DETERMINISTIC.

Framed-block I/O taxonomy (ISSUE 14): ``ConnectionError`` /
``BrokenPipeError`` / ``socket.timeout`` anywhere in the chain classify
TRANSIENT — a reconnect may heal them — while the typed
:class:`WorkerLost` raised once the block layer's transient budget is
exhausted classifies WORKER_LOST.
"""
from __future__ import annotations

from typing import Iterator

DEVICE_OOM = "deviceOom"
TRANSIENT = "transient"
DETERMINISTIC = "deterministic"
PROPAGATE = "propagate"
WORKER_LOST = "workerLost"
WORKER_DEGRADED = "workerDegraded"

# absl / XLA status codes (the string form jaxlib prefixes messages with)
_OOM_CODES = ("RESOURCE_EXHAUSTED",)
_TRANSIENT_CODES = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                    "CANCELLED", "UNKNOWN")
_DETERMINISTIC_CODES = ("INVALID_ARGUMENT", "UNIMPLEMENTED", "NOT_FOUND",
                        "FAILED_PRECONDITION", "OUT_OF_RANGE")

# cooperative OOM exceptions from memory/retry.py, matched by name to keep
# this module import-cycle-free (retry.py imports us for is_device_oom)
_OOM_TYPE_NAMES = ("TpuRetryOOM", "TpuSplitAndRetryOOM")

# exceptions that ARE the query's correct observable behavior — plus the
# lifecycle layer's control-flow exceptions (ISSUE 4): a cancellation or
# deadline must surface unchanged, NEVER be retried, CPU-fallbacked, or
# counted by the circuit breaker (the query was killed, the stage did
# not fail)
_PROPAGATE_TYPE_NAMES = ("SparkArithmeticException",
                         "SparkDateTimeException",
                         "SparkNumberFormatException",
                         "QueryCancelled",
                         "QueryDeadlineExceeded",
                         "QueryRejected")

# typed corruption errors from the integrity checksums (shuffle frame
# CRC, disk-spill CRC): re-reading re-derives the same corruption, so
# they classify DETERMINISTIC (the fallthrough default — listed here so
# the contract is explicit and message contents can never reclassify)
_DETERMINISTIC_TYPE_NAMES = ("ShuffleCorruption", "SpillCorruption",
                             "ProtocolCorruption")

# a distributed worker declared gone (distributed/protocol.py).  Matched
# by name (import-cycle-free) and BEFORE the ConnectionError isinstance
# check — WorkerLost subclasses ConnectionError, but retry/backoff is
# exactly the wrong response once the loss is declared
_WORKER_LOST_TYPE_NAMES = ("WorkerLost",)

# a distributed worker declared SLOW, not dead (ISSUE 20 gray failure):
# the op exhausted its budget against a DEGRADED straggler.  Matched by
# name BEFORE the WorkerLost check (WorkerDegraded subclasses WorkerLost
# so existing re-drive paths handle it) and never DETERMINISTIC — a
# straggler is infrastructure weather, never an operator bug
_WORKER_DEGRADED_TYPE_NAMES = ("WorkerDegraded",)

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")

# OSError errnos that may heal on retry (network / interrupt flavored);
# everything else (ENOSPC, EACCES, ENOENT, ...) is deterministic
import errno as _errno

_TRANSIENT_ERRNOS = frozenset((
    _errno.EAGAIN, _errno.EINTR, _errno.ETIMEDOUT, _errno.ECONNRESET,
    _errno.ECONNABORTED, _errno.ECONNREFUSED, _errno.EHOSTUNREACH,
    _errno.ENETUNREACH, _errno.ENETRESET, _errno.EPIPE, _errno.EBUSY,
))


def exception_chain(exc: BaseException) -> Iterator[BaseException]:
    """Yield ``exc`` and every ``__cause__``/``__context__`` beneath it
    (cause preferred, cycle-guarded) — wrapped XLA errors keep their
    status visible to the classifier.  ``raise X from None`` sets
    ``__suppress_context__``: the raiser declared the context unrelated,
    so the walk stops there (an error raised while *handling* an OOM must
    not inherit the OOM's class when explicitly disowned)."""
    seen = set()
    cur: BaseException = exc
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        yield cur
        if cur.__cause__ is not None:
            cur = cur.__cause__
        elif cur.__suppress_context__:
            cur = None
        else:
            cur = cur.__context__


def _status_of(exc: BaseException):
    """The absl status-code token of one chain link, or None."""
    if type(exc).__name__ != "XlaRuntimeError":
        return None
    msg = str(exc)
    for code in (_OOM_CODES + _TRANSIENT_CODES + _DETERMINISTIC_CODES
                 + ("INTERNAL", "DATA_LOSS", "PERMISSION_DENIED")):
        if msg.startswith(code) or f"{code}:" in msg:
            return code
    return None


def is_device_oom(exc: BaseException) -> bool:
    """RESOURCE_EXHAUSTED (or the cooperative OOM pair) anywhere in the
    cause chain — the fix for wrapped XLA errors being misclassified as
    deterministic failures."""
    for link in exception_chain(exc):
        if type(link).__name__ in _OOM_TYPE_NAMES:
            return True
        if _status_of(link) in _OOM_CODES:
            return True
        s = repr(link)
        if any(m in s for m in _OOM_MARKERS):
            return True
    return False


def classify_failure(exc: BaseException) -> str:
    """Map an exception (walking its cause chain) to a failure class."""
    from spark_rapids_tpu.resilience.faults import (
        InjectedCompileError,
        InjectedTransientError,
    )

    if isinstance(exc, (KeyboardInterrupt, SystemExit, GeneratorExit)):
        return PROPAGATE
    for link in exception_chain(exc):
        if type(link).__name__ in _PROPAGATE_TYPE_NAMES:
            return PROPAGATE
    for link in exception_chain(exc):
        if type(link).__name__ in _WORKER_DEGRADED_TYPE_NAMES:
            return WORKER_DEGRADED
    for link in exception_chain(exc):
        if type(link).__name__ in _WORKER_LOST_TYPE_NAMES:
            return WORKER_LOST
    for link in exception_chain(exc):
        if type(link).__name__ in _DETERMINISTIC_TYPE_NAMES:
            return DETERMINISTIC
    if is_device_oom(exc):
        return DEVICE_OOM
    for link in exception_chain(exc):
        if isinstance(link, InjectedTransientError):
            return TRANSIENT
        if isinstance(link, InjectedCompileError):
            return DETERMINISTIC
        code = _status_of(link)
        if code in _TRANSIENT_CODES:
            return TRANSIENT
        if code == "INTERNAL":
            # XLA INTERNAL covers both compiler bugs and runtime hiccups;
            # the runtime ones usually mention the transport/program load
            msg = str(link)
            if any(m in msg for m in ("socket", "connection", "stream",
                                      "transfer", "premature")):
                return TRANSIENT
            return DETERMINISTIC
        if code in _DETERMINISTIC_CODES:
            return DETERMINISTIC
        if isinstance(link, (ConnectionError, TimeoutError,
                             BrokenPipeError)):
            return TRANSIENT
        if isinstance(link, OSError) and link.errno in _TRANSIENT_ERRNOS:
            # only network/interrupt-flavored OS errors may heal on their
            # own; ENOSPC, EACCES, ENOENT etc. re-derive every retry (and
            # retrying a disk-full spill makes the pressure worse)
            return TRANSIENT
    # compile / trace / type errors and anything unidentified: retrying
    # re-derives the same failure, so treat as deterministic
    return DETERMINISTIC
