"""Test harness config.

Mirrors integration_tests/src/main/python/conftest.py in the reference:
tests run the same query twice — TPU plugin on vs off — and compare.  Tests
run on the XLA CPU backend with a virtual 8-device mesh
(xla_force_host_platform_device_count) so the full suite, including
multi-chip sharding tests, runs on any machine; the same code paths execute
unchanged on real TPU chips.
"""
import gc
import os
import threading
import time

# Force the CPU backend for tests (SRT_TEST_ON_TPU=1 opts into real chips);
# set before anything imports jax.  Running float64 tests on a real v5e
# silently downgrades to the f64 emulation (~1e-15 relative error), which
# breaks exact differential tests.
xf = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in xf:
    os.environ["XLA_FLAGS"] = (
        xf + " --xla_force_host_platform_device_count=8").strip()
if os.environ.get("SRT_TEST_ON_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 'not slow' run")
    config.addinivalue_line(
        "markers", "chaos: fault-injection sweep tests "
        "(tools/run_chaos.py runs these standalone)")
    config.addinivalue_line(
        "markers", "stress: concurrent-query stress harness "
        "(tools/run_stress.py runs the big sweeps standalone)")
    config.addinivalue_line(
        "markers", "profiling: calibration-store / cost-model / advisor "
        "feedback-loop tests (ISSUE 8; unmarked slow, so they run in "
        "tier-1)")
    # ISSUE 31: every run ends with where its time went (the driver's
    # log and a builder's own), unless the command line asks otherwise;
    # ROADMAP D1 reads this table
    if config.option.durations is None:
        config.option.durations = 25


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Stash per-phase reports so teardown fixtures can tell whether the
    test body itself passed (the leak gate must not stack an ERROR on an
    already-failing test)."""
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


@pytest.fixture(autouse=True)
def _resilience_isolation(_leak_gate):
    """The fault list and circuit breaker are process-global: an entry a
    failing test trips would route matching stages of every LATER test to
    the CPU oracle at plan time, turning their differential comparisons
    into vacuous CPU-vs-CPU checks.  Reset around every test."""
    from spark_rapids_tpu.resilience import clear_faults, reset_breaker

    clear_faults()
    reset_breaker()
    yield
    clear_faults()
    reset_breaker()
    # ISSUE 13: the overload governor is process-global too — a test
    # that enabled it must not leave degradation armed for later tests
    # (one ambient check; default sessions never create one)
    from spark_rapids_tpu.governor import context as _GOV

    if _GOV.GOVERNOR is not None:
        from spark_rapids_tpu.governor import shutdown_governor

        shutdown_governor()
    # ISSUE 18: the ledger registry is process-global — a test that
    # enabled accounting must not leave every later test paying the
    # charge tax (and piling settled bills into the retained ring)
    from spark_rapids_tpu.accounting import context as _ACCT

    if _ACCT.LEDGERS is not None:
        from spark_rapids_tpu.accounting import shutdown as _acct_shutdown

        _acct_shutdown()
    # ISSUE 19: the serving tier is process-global — a test that opened
    # tenant sessions must not leave the fair-share scheduler installed
    # (later tests' admissions would be charged to stale usage accounts)
    # or result fragments resident
    from spark_rapids_tpu.serving import context as _SRV

    if _SRV.TIER is not None or _SRV.RESULT_CACHE is not None:
        from spark_rapids_tpu.serving import shutdown_serving

        shutdown_serving()
    # ISSUE 23: the telemetry hub (default-on, built by a test's first
    # TpuSession) owns a sampler thread and histograms — stop it so the
    # thread gate below sees a clean process and no test reads another's
    # SLO series
    from spark_rapids_tpu.telemetry import context as _TEL

    if _TEL.HUB is not None:
        from spark_rapids_tpu import telemetry

        telemetry.shutdown()


def _srt_threads():
    """Live engine threads by ident.  The AOT compile pool is excluded:
    it is one bounded process-wide pool that a module's tests share, and
    ``_module_compile_state`` ends it at module teardown."""
    return {t.ident: t.name for t in threading.enumerate()
            if t.name.startswith("srt-")
            and not t.name.startswith("srt-aot-")}


def _leaked_threads(before, grace_s=3.0):
    """srt-* threads that were not alive at test start and are still
    alive after a bounded grace (shutdown paths join with timeouts; a
    reader woken by its socket closing needs a scheduler slice)."""
    deadline = time.monotonic() + grace_s
    while True:
        new = {i: n for i, n in _srt_threads().items() if i not in before}
        if not new or time.monotonic() >= deadline:
            return sorted(new.values())
        time.sleep(0.02)


@pytest.fixture(autouse=True, scope="module")
def _module_compile_state():
    """ISSUE 23 (tier-1 must reach its end in ONE process): bound what a
    module leaves behind.  Every live XLA:CPU executable holds ~15-17
    memory mappings; at ~3,800 of them the process reaches
    vm.max_map_count (65,530), LLVM's JIT gets "Cannot allocate memory"
    and the next compile segfaults in backend_compile_and_load — the
    seed's rc 139 (PR 23: a probe compiling distinct programs died at
    65,384 mappings, and a full run without the two lines below died at
    84 %; one without ``shutdown_aot`` reached its end).  So at module
    teardown the program registry is emptied and jax's executable caches
    are dropped; the AOT pool is drained and its threads ended too, so
    no background compile outlives its module."""
    yield
    import jax

    from spark_rapids_tpu.compilecache import reset_registry
    from spark_rapids_tpu.compilecache.aot import shutdown_aot

    shutdown_aot(120.0)
    reset_registry()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _leak_gate(request):
    """ISSUE 4 satellite: a leaked spillable handle, semaphore permit, or
    shuffle registration fails the OWNING test instead of silently
    poisoning every later one.  ISSUE 5 extends the report to writer
    staging dirs: a leftover ``_temporary/<uuid>`` means a write unwound
    without its commit protocol running.  ISSUE 14 extends it to REMOTE
    partitions: an exchange still placed on distributed workers means a
    query ended without its release broadcast — blocks pinned in another
    process's store.  ISSUE 16 extends it to RECOVERY artifacts: a
    journaled query left un-ended, an unserved pending checkpoint, or a
    leftover ``checkpoints/<fp>`` dir on disk means a test drove the
    journal without closing its query lifecycle.  ISSUE 18 extends it to
    RESOURCE BILLS: a settled bill with a nonzero residual — device
    bytes charged to the query but never released, persistent df.cache
    handles excluded — is the accounting-side view of a handle leak and
    fails the owning test even after the handle itself was swept.
    ISSUE 19 extends it to SERVING state: an unclosed tenant session or
    a result-cache fragment that outlived its session is a cross-tenant
    leak risk and fails the owning test.  The
    gate only *fails* a test whose body passed (a failing test already
    reported its real error — the leaked state is still cleaned so it
    cannot cascade).  ISSUE 23 extends it to THREADS: a coordinator,
    worker, chaos proxy, telemetry hub or watchdog a test started and
    did not shut down leaves ``srt-*`` threads behind (50 of them were
    alive when the one-process run segfaulted) — the owning test fails."""
    threads_before = _srt_threads()
    yield
    leaked_threads = _leaked_threads(threads_before)
    if leaked_threads:
        rep = getattr(request.node, "rep_call", None)
        if rep is not None and rep.passed:
            pytest.fail(
                "thread leak after test (started and not shut down): "
                + ", ".join(leaked_threads[:20]), pytrace=False)
    from spark_rapids_tpu.lifecycle import (
        leak_report_all,
        reset_leaked_state,
    )

    try:
        leaks = leak_report_all()
    except Exception:
        return
    if not leaks:
        return
    reset_leaked_state()
    rep = getattr(request.node, "rep_call", None)
    if rep is not None and rep.passed:
        pytest.fail(
            "resource leak after test (spillables / semaphore permits / "
            "shuffle registrations / writer staging dirs / remote "
            "distributed partitions / recovery journal + checkpoint "
            "files / nonzero residual resource bills / open serving "
            "sessions + orphaned result fragments):\n"
            + "\n".join(leaks[:20]),
            pytrace=False)


def pytest_sessionfinish(session, exitstatus):
    """Session-shutdown leak check: print (never fail) anything still
    live at exit, so CI logs surface a leak even when the owning test
    could not be identified."""
    try:
        from spark_rapids_tpu.lifecycle import leak_report_all

        leaks = leak_report_all()
    except Exception:
        return
    if leaks:
        import sys

        print("\nspark_rapids_tpu session-shutdown leak report "
              f"({len(leaks)} entries):", file=sys.stderr)
        for line in leaks[:20]:
            print("  " + line.splitlines()[0], file=sys.stderr)


@pytest.fixture
def tpu_session():
    from spark_rapids_tpu.session import TpuSession

    return TpuSession({"spark.rapids.sql.enabled": True})
