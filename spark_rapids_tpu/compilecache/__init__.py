"""Plan-time AOT compilation pipeline + persistent executable cache.

A fresh XLA compile costs seconds to minutes, and the seed engine compiled
every stage program lazily on the first batch of the first run —
serialized, inside the query's critical path.  This package moves
compilation off the critical path with two halves:

* ``registry`` — an in-process executable registry: every exec routes its
  ``tpu_jit`` creation through :func:`cached_program` keyed by a
  collision-safe fingerprint (expression SQL + schemas + mode + relevant
  confs), so a re-planned query (fresh session, same logical plan) reuses
  the already-compiled programs instead of re-tracing.  Counters:
  ``compile_cache_hits`` / ``compile_cache_misses`` / ``compile_wall_ns``.

* ``aot`` — plan-time enumeration: after overrides produce the exec tree,
  :func:`submit_plan` walks it, predicts each stage program's (function x
  shape-bucket) from the plan's static row estimates, and compiles them
  concurrently on a bounded background pool — batch 1 of operator 1
  overlaps the compiles of everything downstream.  The runtime lookup
  blocks only when it reaches a program whose AOT compile is still in
  flight.

The cross-process half rides JAX's on-disk compilation cache
(``jax_compilation_cache_dir``), pointed at ``spark.rapids.tpu.compile.
cacheDir`` by the session (see session._apply_compile_cache) — a fresh
process re-running the same plan deserializes executables instead of
compiling.  Every path that enables the on-disk cache must first call
:func:`ensure_atomic_cache_put` (crash-consistent entry publication —
see its docstring for why torn entries segfault).
"""
import os
import time

_ATOMIC_PUT_APPLIED = False


def ensure_atomic_cache_put() -> None:
    """Make jax's persistent compile-cache writes crash-consistent.

    Stock ``jax._src.lru_cache.LRUCache.put`` writes the serialized
    executable to its FINAL path with one plain ``write_bytes`` — no
    tmp+rename.  Two real failure modes follow: a process killed
    mid-write (a crashed driver; the --driver-kill harness lands
    SIGKILLs exactly there) leaves a truncated entry at the final
    path, and a concurrent reader — the AOT background pool in this
    process, or a worker process sharing the directory — can read a
    half-written file.  Either way ``deserialize_executable`` on torn
    bytes SEGFAULTS the reader, possibly a completely different
    process days later.  Re-bind ``put`` to stage the bytes beside the
    final path and publish with ``os.replace``, so an entry is either
    absent or complete — the same discipline as the recovery journal's
    checkpoint commit (docs/recovery.md).  Idempotent.  Written against
    the jax 0.9 layout of ``jax._src.lru_cache``; a jax that lacks any
    name the replacement uses raises here instead of silently running
    with torn-write exposure.
    """
    global _ATOMIC_PUT_APPLIED
    if _ATOMIC_PUT_APPLIED:
        return
    from jax._src import lru_cache as _lru

    missing = [n for n in ("_CACHE_SUFFIX", "_ATIME_SUFFIX")
               if not hasattr(_lru, n)]
    missing += [f"LRUCache.{n}" for n in ("put", "_evict_if_needed")
                if not hasattr(_lru.LRUCache, n)]
    if missing:
        raise RuntimeError(
            "jax._src.lru_cache no longer has " + ", ".join(missing)
            + ": compilecache.ensure_atomic_cache_put must be ported to "
            "this jax before the persistent compile cache is enabled")

    def _atomic_put(self, key, val):
        if not key:
            raise ValueError("key cannot be empty")
        if self.eviction_enabled and len(val) > self.max_size:
            return
        cache_path = self.path / f"{key}{_lru._CACHE_SUFFIX}"
        atime_path = self.path / f"{key}{_lru._ATIME_SUFFIX}"
        if self.eviction_enabled:
            self.lock.acquire(timeout=self.lock_timeout_secs)
        try:
            if cache_path.exists():
                return
            self._evict_if_needed(additional_size=len(val))
            tmp = cache_path.with_name(
                cache_path.name + f".tmp.{os.getpid()}")
            try:
                tmp.write_bytes(val)
                os.replace(tmp, cache_path)
            except OSError:
                # a broken disk degrades caching, never the query
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return
            if self.eviction_enabled:
                try:
                    atime_path.write_bytes(
                        time.time_ns().to_bytes(8, "little"))
                except OSError:
                    pass
        finally:
            if self.eviction_enabled:
                self.lock.release()

    _lru.LRUCache.put = _atomic_put
    _ATOMIC_PUT_APPLIED = True


def apply_persistent_cache_dir(cache_dir: str) -> bool:
    """The one place this package points jax's persistent compile cache
    at a directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set, whoever
    started the process has placed the cache (jax reads that variable
    itself): nothing is set here and False is returned.  Otherwise the
    cache goes to ``cache_dir``; a directory that cannot be created
    leaves the cache off (with a warning) rather than moving it."""
    ensure_atomic_cache_put()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return False
    import warnings

    import jax

    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as e:
        warnings.warn(f"persistent compile cache disabled: cannot create "
                      f"{cache_dir!r}: {e}")
        return False
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return True
from spark_rapids_tpu.compilecache.keys import (  # noqa: F401
    conf_fp,
    exprs_fp,
    fingerprint,
    schema_fp,
)
from spark_rapids_tpu.compilecache.registry import (  # noqa: F401
    ProgramEntry,
    cached_program,
    get_registry,
    registry_enabled,
    reset_registry,
)
from spark_rapids_tpu.compilecache.aot import (  # noqa: F401
    AotSubmission,
    maybe_submit_aot,
    submit_plan,
)
