"""Durability guards for the perf-counter patches and the compile cache.

The counters live on monkey-patched JAX internals
(``ArrayImpl.__array__``, scalar dunders, ``_cache_size``); a JAX upgrade
that drops one must fail LOUDLY (the install raises at import), never
zero the counts.  Also pins the one-cache-authority behavior of
``TpuSession``.
"""
import os

from spark_rapids_tpu import perfcounters as PC


def test_sync_counting_patches_installed():
    # if a jax upgrade breaks the ArrayImpl patches this must fail, not
    # silently report zero syncs forever
    assert PC.SYNC_COUNTING is True


def test_tpu_jit_counts_programs_and_compiles():
    import jax.numpy as jnp

    fn = PC.tpu_jit(lambda x: x * 2 + 1)
    x = jnp.arange(16)
    snap = PC.snapshot()
    fn(x).block_until_ready()
    d1 = PC.since(snap)
    assert d1["programs_launched"] == 1
    assert d1["compiles"] == 1          # first call traces + compiles
    assert d1["launch_wall_ns"] > 0
    snap = PC.snapshot()
    fn(x).block_until_ready()
    d2 = PC.since(snap)
    assert d2["programs_launched"] == 1
    assert d2["compiles"] == 0          # warm cache


def test_host_sync_counted_on_materialize():
    # device_get + scalar dunders are the engine's materialization paths;
    # raw np.asarray on the CPU backend can take the zero-copy buffer
    # protocol and legitimately skip __array__, so it is not pinned here
    import jax

    import jax.numpy as jnp

    y = (jnp.arange(64) + 1)
    y.block_until_ready()
    snap = PC.snapshot()
    arr = jax.device_get(y)
    d = PC.since(snap)
    assert arr[3] == 4
    assert d["host_syncs"] == 1
    assert d["bytes_d2h"] >= y.nbytes
    # scalar dunders count too
    snap = PC.snapshot()
    assert int(jnp.int32(7)) == 7
    assert PC.since(snap)["host_syncs"] == 1


def test_sync_get_is_one_logical_sync():
    import jax.numpy as jnp

    tree = {"a": jnp.arange(8), "b": jnp.ones(8)}
    snap = PC.snapshot()
    out = PC.sync_get(tree)
    d = PC.since(snap)
    assert d["host_syncs"] == 1          # one round trip, two leaves
    assert out["a"][2] == 2


def test_nested_sync_event_counts_once():
    """ISSUE 3 satellite: a sync_get issued from inside another
    sync_event is part of the same logical round trip — the old
    __enter__ bumped host_syncs at every depth, double-counting."""
    import jax.numpy as jnp

    y = jnp.arange(8)
    snap = PC.snapshot()
    with PC.sync_event():
        PC.sync_get({"a": y})            # nested: must NOT count again
        with PC.sync_event():
            pass
    assert PC.since(snap)["host_syncs"] == 1


def test_counting_jit_concurrent_first_call_counts_one_compile():
    """ISSUE 3 satellite: two threads racing the same uncompiled program
    could both observe a _cache_size() delta (or neither); detection is
    now serialized per wrapper — exactly one compile lands."""
    import threading

    import jax.numpy as jnp

    fn = PC.tpu_jit(lambda x: x * 3 + 2)
    x = jnp.arange(32)
    snap = PC.snapshot()
    barrier = threading.Barrier(2)
    errors = []

    def worker():
        try:
            barrier.wait()
            fn(x).block_until_ready()
        except Exception as e:           # pragma: no cover - surfaced below
            errors.append(e)

    ts = [threading.Thread(target=worker) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors
    d = PC.since(snap)
    assert d["programs_launched"] == 2
    assert d["compiles"] == 1, f"compile race miscount: {d['compiles']}"
    # a later new-shape call still detects its compile
    snap = PC.snapshot()
    fn(jnp.arange(64)).block_until_ready()
    assert PC.since(snap)["compiles"] == 1


def test_counter_keys_are_snake_case_only():
    """ISSUE 7 satellite: the one-release camelCase read/write aliases
    (ISSUE 3) are gone — snapshot()/since() expose canonical snake_case
    keys only, and the ALIASES table no longer exists."""
    assert "transient_retries" in PC.COUNTERS
    assert not hasattr(PC, "ALIASES")
    snap = PC.snapshot()
    for legacy in ("transientRetries", "oomRestarts", "runtimeFallbacks",
                   "breakerTrips", "breakerPlanFallbacks",
                   "queryFallbacks"):
        assert legacy not in snap
    PC.bump("oom_restarts")
    d = PC.since(snap)
    assert d["oom_restarts"] == 1
    assert "oomRestarts" not in d
    PC.reset()


def test_session_applies_compile_cache_conf(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR unset: the cache goes to the fixed
    in-checkout ``.jax_compile_cache/<backend>`` (the path is part of the
    cache key, so it never moves), or to ``<conf dir>/<backend>``."""
    import jax

    from spark_rapids_tpu import session as S

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # force a fresh application regardless of earlier sessions in-process
    S._COMPILE_CACHE_APPLIED = None
    S.TpuSession({})
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(S.__file__)))
    want = os.path.join(checkout, ".jax_compile_cache",
                        jax.default_backend())
    assert jax.config.jax_compilation_cache_dir == want
    assert S._COMPILE_CACHE_APPLIED == want
    # a later session with an explicitly different dir is honored, not
    # silently ignored
    other = str(tmp_path / "xc")
    S.TpuSession({"spark.rapids.tpu.compileCache.dir": other})
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        other, jax.default_backend())
    S._COMPILE_CACHE_APPLIED = None
    S.TpuSession({})      # restore the default for the rest of the suite


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch,
                                                        tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: whoever started the process placed
    the cache — no code path may call
    jax.config.update("jax_compilation_cache_dir", ...)."""
    import jax

    from spark_rapids_tpu import session as S
    from spark_rapids_tpu.distributed.worker import _warm_caches

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "out"))
    updated = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updated.append(k), real_update(k, v))[1])
    before = jax.config.jax_compilation_cache_dir
    S._COMPILE_CACHE_APPLIED = None
    try:
        S.TpuSession({})
        S.TpuSession({"spark.rapids.tpu.compile.cacheDir":
                      str(tmp_path / "conf")})
        _warm_caches(str(tmp_path / "warm"))
        assert updated == []
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        monkeypatch.undo()
        S._COMPILE_CACHE_APPLIED = None
        S.TpuSession({})  # restore the default for the rest of the suite


def test_concurrent_increments_lose_nothing():
    """COUNTERS[k] += n is three bytecodes; unguarded concurrent
    increments can lose updates at thread switches.  Every write now
    routes through PC.bump's lock — N threads x M bumps must land
    exactly."""
    import threading

    snap = PC.snapshot()
    threads = 8
    per_thread = 5000

    def worker():
        for _ in range(per_thread):
            PC.bump("transient_retries")
            PC.bump("bytes_h2d", 3)

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    d = PC.since(snap)
    assert d["transient_retries"] == threads * per_thread
    assert d["bytes_h2d"] == threads * per_thread * 3
    PC.reset()
