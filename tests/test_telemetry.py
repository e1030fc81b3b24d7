"""Telemetry-tier tests (ISSUE 7): the time-series registry, sampler,
SLO histograms, Prometheus exporter, failure flight recorder with
post-mortem bundles, and the bench regression gate.

The pinned contracts:

* disabled path — with the tier off, a launch/sync/collect-heavy
  workload makes ZERO calls into telemetry modules (cProfile, mirroring
  the diagnostics overhead test);
* enabled path — flight recording is per-QUERY, never per batch;
* the Prometheus exposition output round-trips through a from-scratch
  parser (families typed, histogram buckets cumulative, +Inf == count);
* an injected deadline trip and an injected breaker opening each
  produce a post-mortem bundle containing the ring, thread stacks (the
  tripped query's thread named), and a counter snapshot;
* ``tools/bench_gate.py`` flags a synthetic regression and passes a
  clean diff.
"""
import cProfile
import json
import os
import pstats
import re
import sys
import threading
import time

import pytest

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import telemetry
from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, sum_

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


def _mk_session(extra=None):
    conf = {"spark.rapids.sql.enabled": True,
            # no periodic ticks unless a test asks: deterministic counts
            "spark.rapids.tpu.telemetry.samplePeriodMs": "0"}
    conf.update(extra or {})
    return TpuSession(conf)


@pytest.fixture
def fresh_hub():
    """A hub built fresh for this test (and torn down after) so ring /
    postmortem / SLO state is not inherited from earlier tests."""
    telemetry.shutdown()
    s = _mk_session()
    hub = telemetry.get_hub()
    assert hub is not None
    hub.reset_dump_limits()
    yield s, hub
    telemetry.shutdown()


def _agg_df(s, n=256):
    return s.create_dataframe(
        {"a": list(range(n)), "k": [i % 4 for i in range(n)]},
        T.StructType([T.StructField("a", T.LONG, True),
                      T.StructField("k", T.LONG, True)]))


def _agg_query(s, n=256):
    return _agg_df(s, n).group_by("k").agg(sum_("a", "s"))


# ---------------------------------------------------------------------------
# disabled-path overhead (the cProfile bound)
# ---------------------------------------------------------------------------

def test_disabled_path_does_no_telemetry_work():
    """With the tier disabled (no hub), the hot path costs one module-
    attribute read: profiling a launch/sync/collect-heavy workload shows
    ZERO calls into telemetry modules."""
    import jax.numpy as jnp

    telemetry.shutdown()
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.tpu.telemetry.enabled": False})
    assert telemetry.get_hub() is None
    df = _agg_query(s)
    df.collect()                # warm compile caches outside the profile
    fn = PC.tpu_jit(lambda x: x * 2 + 1)
    x = jnp.arange(64)
    fn(x)

    prof = cProfile.Profile()
    prof.enable()
    for _ in range(50):
        fn(x)
        with PC.sync_event():
            pass
    df.collect()
    prof.disable()
    banned = os.path.join("spark_rapids_tpu", "telemetry")
    offenders = [
        (fname, func)
        for (fname, _lineno, func) in pstats.Stats(prof).stats
        if banned in fname]
    assert not offenders, (
        f"telemetry work on the disabled path: {offenders}")


def test_enabled_flight_recording_is_per_query_not_per_batch(fresh_hub):
    """The always-on cost contract: one query = two flight events
    (query_start / query_end), independent of how many batches flow."""
    s, hub = fresh_hub
    df = _agg_df(s, 64)
    multi = df
    for _ in range(5):                       # a multi-batch input
        multi = multi.union(_agg_df(s, 64))
    q = multi.group_by("k").agg(sum_("a", "s"))
    q.collect()                              # warm (plan + compiles)
    before = hub.flight.events_recorded
    q.collect()
    assert hub.flight.events_recorded - before == 2
    kinds = [e["ev"] for e in hub.flight.snapshot()[-2:]]
    assert kinds == ["query_start", "query_end"]


# ---------------------------------------------------------------------------
# registry / sampler / SLO
# ---------------------------------------------------------------------------

def test_slo_histogram_records_per_plan_signature(fresh_hub):
    s, hub = fresh_hub
    q = _agg_query(s)
    for _ in range(3):
        assert sorted(q.collect()) == [(0, 8064), (1, 8128), (2, 8192),
                                       (3, 8256)]
    slo = telemetry.slo_summary()
    assert slo[""]["count"] >= 3             # the all-queries series
    sigs = [k for k in slo if "TpuHashAggregateExec" in k]
    assert sigs, f"no plan-signature series: {list(slo)}"
    st = slo[sigs[0]]
    assert st["count"] >= 3 and st["errors"] == 0
    assert st["p95_ms"] >= st["p50_ms"] >= 0
    assert st["max_ms"] >= st["p95_ms"]      # quantiles clamp to max


def test_sampler_tick_records_process_gauges(fresh_hub):
    s, hub = fresh_hub
    _agg_query(s).collect()                  # builds admission/spill state
    row = hub.sampler.tick()
    for key in ("admission_running", "admission_queued", "active_queries",
                "hbm_pool_bytes", "hbm_used_bytes",
                "compile_registry_programs", "p95_ms"):
        assert key in row, f"missing {key} in {sorted(row)}"
    assert row["admission_running"] == 0     # nothing in flight now
    assert hub.timeline_snapshot()[-1] == row
    # gauges landed in the registry ring too
    g = {se.name: se for se in hub.registry.series_items()}
    assert g["active_queries"].kind == "gauge"
    assert len(g["active_queries"].ring) == 1


def test_sampler_thread_and_jsonl_sink(tmp_path):
    telemetry.shutdown()
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.telemetry.samplePeriodMs": "20",
        "spark.rapids.tpu.telemetry.jsonlDir": str(tmp_path),
    })
    try:
        _agg_query(s).collect()
        hub = telemetry.get_hub()
        deadline = time.monotonic() + 10
        while hub.sampler.ticks < 3 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert hub.sampler.ticks >= 3
        files = [n for n in os.listdir(tmp_path)
                 if n.startswith("telemetry-") and n.endswith(".jsonl")]
        assert len(files) == 1
        lines = [json.loads(ln) for ln in
                 open(tmp_path / files[0]) if ln.strip()]
        assert len(lines) >= 3
        assert {"ts", "active_queries", "p95_ms"} <= set(lines[-1])
    finally:
        telemetry.shutdown()


def test_slo_violation_counter_and_event(fresh_hub):
    s, hub = fresh_hub
    slow = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.telemetry.samplePeriodMs": "0",
        # any real query is slower than a tenth of a microsecond
        "spark.rapids.tpu.telemetry.slo.targetP95Ms": "0.0001",
    })
    snap = PC.snapshot()
    _agg_query(slow).collect()
    assert PC.since(snap)["slo_violations"] == 1
    assert any(e["ev"] == "slo_violation"
               for e in hub.flight.snapshot())


# ---------------------------------------------------------------------------
# Prometheus exposition — golden parse test
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? "
    r"(NaN|[-+]?[0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?)$")
_LABEL_RE = re.compile(r'^([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"$')


def _parse_prometheus(text):
    """From-scratch exposition parser: returns {family: type} and
    [(name, labels-dict, value)] samples; raises on malformed lines."""
    types, samples = {}, []
    for ln in text.splitlines():
        if not ln.strip():
            continue
        if ln.startswith("# TYPE "):
            _, _, rest = ln.partition("# TYPE ")
            fam, typ = rest.split()
            types[fam] = typ
            continue
        if ln.startswith("#"):
            continue
        m = _SAMPLE_RE.match(ln)
        assert m, f"malformed sample line: {ln!r}"
        labels = {}
        if m.group(3):
            for part in re.split(r",(?=[a-zA-Z_])", m.group(3)):
                lm = _LABEL_RE.match(part)
                assert lm, f"malformed label in: {ln!r}"
                labels[lm.group(1)] = lm.group(2)
        samples.append((m.group(1), labels, float(m.group(4))))
    return types, samples


def test_prometheus_export_round_trips_through_parser(fresh_hub):
    s, hub = fresh_hub
    for _ in range(2):
        _agg_query(s).collect()
    hub.sampler.tick()
    text = telemetry.export()
    types, samples = _parse_prometheus(text)

    # families: every sample belongs to a declared family
    fams = set(types)
    for name, _labels, _v in samples:
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in fams or base in fams, f"undeclared family: {name}"
    assert types["srt_query_latency_ms"] == "histogram"
    assert types["srt_active_queries"] == "gauge"
    assert types["srt_queries_admitted_total"] == "counter"

    # histogram invariants per labelset: buckets cumulative, +Inf==count
    by_sig = {}
    for name, labels, v in samples:
        if name == "srt_query_latency_ms_bucket":
            sig = labels.get("plan_sig", "")
            by_sig.setdefault(sig, []).append((labels["le"], v))
    assert "" in by_sig
    for sig, buckets in by_sig.items():
        vals = [v for _le, v in buckets]
        assert vals == sorted(vals), f"non-cumulative buckets for {sig!r}"
        inf = [v for le, v in buckets if le == "+Inf"]
        count = [v for name, labels, v in samples
                 if name == "srt_query_latency_ms_count"
                 and labels.get("plan_sig", "") == sig]
        assert inf == count
    # round-trip a registry gauge value exactly
    want = hub.registry.gauge("active_queries").value
    got = [v for name, labels, v in samples
           if name == "srt_active_queries"]
    assert got == [want]


def test_http_scrape_endpoint(fresh_hub):
    import urllib.request

    s, hub = fresh_hub
    _agg_query(s).collect()
    from spark_rapids_tpu.telemetry.prometheus import start_http

    srv, port = start_http(hub, 0)           # ephemeral port
    assert srv is not None
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            body = resp.read().decode()
        assert "srt_query_latency_ms_bucket" in body
        types, _ = _parse_prometheus(body)
        assert "srt_query_latency_ms" in types
    finally:
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# flight recorder — post-mortem pins
# ---------------------------------------------------------------------------

def test_deadline_trip_dumps_postmortem_naming_tripped_query(fresh_hub):
    """Acceptance pin: an injected deadline trip produces a bundle with
    the ring, the counter snapshot, the active-query table, and every
    thread's stack — the tripped query's thread marked *offender* while
    it is still blocked (the watchdog dumps BEFORE the unwind)."""
    from spark_rapids_tpu.lifecycle import QueryDeadlineExceeded
    from spark_rapids_tpu.memory.semaphore import get_semaphore

    s, hub = fresh_hub
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.telemetry.samplePeriodMs": "0",
        "spark.rapids.sql.concurrentGpuTasks": "1",
        "spark.rapids.tpu.query.timeoutMs": "300",
        "spark.rapids.tpu.query.watchdogPeriodMs": "20",
    })
    df = _agg_query(s)
    # warm the compiles: programs are keyed on the whole conf, so the
    # warm-up runs under the same 300 ms deadline — a cold compile that
    # overruns it is cached by the retry (earlier tests of this module
    # used to do the warming by accident)
    for _ in range(5):
        try:
            df.collect()
            break
        except QueryDeadlineExceeded:
            continue
    hub.reset_dump_limits()
    sem = get_semaphore(1)
    held, release = threading.Event(), threading.Event()

    def hold():
        sem.acquire_if_necessary()
        held.set()
        release.wait(30)
        sem.release_if_necessary()

    t = threading.Thread(target=hold, name="sem-holder")
    t.start()
    assert held.wait(10)
    n_before = len(hub.postmortems)
    try:
        with pytest.raises(QueryDeadlineExceeded):
            df.collect()
    finally:
        release.set()
        t.join(10)
    pms = [p for p in list(hub.postmortems)[n_before:]
           if p["reason"] == "deadline_trip"]
    assert len(pms) == 1, ("dedupe: the collect unwind must not dump "
                           f"again — {[p['reason'] for p in hub.postmortems]}")
    pm = pms[0]
    assert pm["query_id"]                       # names the tripped query
    assert pm["counters"]["deadline_trips"] >= 1
    offenders = [k for k in pm["thread_stacks"] if "*offender*" in k]
    assert len(offenders) == 1
    # the stuck thread's stack shows the blocked wait, not an unwind
    stack = "".join(pm["thread_stacks"][offenders[0]])
    assert "collect" in stack
    assert any(q["query_id"] == pm["query_id"]
               for q in pm["active_queries"])
    assert any(e["ev"] == "deadline_trip" for e in pm["ring"])


def test_breaker_open_dumps_postmortem(fresh_hub):
    """Acceptance pin: an injected breaker opening produces a bundle
    (ring + thread stacks + counter snapshot)."""
    from spark_rapids_tpu.resilience.breaker import get_breaker

    s, hub = fresh_hub
    b = get_breaker()
    key = ("TpuSortExec", "telemetry-test")
    for _ in range(3):
        b.record_failure(key, 3, reason="injected for telemetry pin")
    pm = telemetry.last_postmortem()
    assert pm is not None and pm["reason"] == "breaker_open"
    assert "TpuSortExec" in pm["detail"]
    assert pm["thread_stacks"] and pm["counters"]["breaker_trips"] >= 0
    assert any(e["ev"] == "breaker_open" for e in pm["ring"])


def test_cancel_mid_batch_dumps_postmortem(fresh_hub):
    """A user-cancelled in-flight query produces a query_cancelled
    bundle when its collect unwinds."""
    from spark_rapids_tpu.lifecycle import QueryCancelled, active_queries
    from spark_rapids_tpu.memory.semaphore import get_semaphore

    s, hub = fresh_hub
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.telemetry.samplePeriodMs": "0",
        "spark.rapids.sql.concurrentGpuTasks": "1",
    })
    df = _agg_query(s)
    df.collect()
    sem = get_semaphore(1)
    held, release = threading.Event(), threading.Event()

    def hold():
        sem.acquire_if_necessary()
        held.set()
        release.wait(30)
        sem.release_if_necessary()

    t = threading.Thread(target=hold)
    t.start()
    assert held.wait(10)
    err = []

    def run():
        try:
            df.collect()
        except QueryCancelled:
            err.append("cancelled")

    qt = threading.Thread(target=run)
    qt.start()
    deadline = time.monotonic() + 10
    try:
        while not active_queries() and time.monotonic() < deadline:
            time.sleep(0.01)
        qs = active_queries()
        assert qs
        qs[0].cancel("telemetry test")
        qt.join(15)
    finally:
        release.set()
        t.join(10)
    assert err == ["cancelled"]
    pms = [p for p in hub.postmortems if p["reason"] == "query_cancelled"]
    assert pms and pms[-1]["query_id"]


def test_postmortem_dump_dir_writes_bundle_file(tmp_path):
    telemetry.shutdown()
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.telemetry.samplePeriodMs": "0",
        "spark.rapids.tpu.telemetry.flightRecorder.dumpDir":
            str(tmp_path),
    })
    try:
        hub = telemetry.get_hub()
        hub.reset_dump_limits()
        pm = hub.postmortem("collect_error", query_id="qx",
                            detail="synthetic")
        assert pm["path"] and os.path.exists(pm["path"])
        loaded = json.load(open(pm["path"]))
        assert loaded["bundle"] == "spark_rapids_tpu_postmortem"
        assert loaded["reason"] == "collect_error"
        assert loaded["thread_stacks"]
        assert not [n for n in os.listdir(tmp_path)
                    if n.endswith(".tmp")]        # atomic write
    finally:
        telemetry.shutdown()


def test_flight_recorder_disabled_records_and_dumps_nothing():
    telemetry.shutdown()
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.telemetry.samplePeriodMs": "0",
        "spark.rapids.tpu.telemetry.flightRecorder.enabled": False,
    })
    try:
        hub = telemetry.get_hub()
        _agg_query(s).collect()
        assert hub.flight.events_recorded == 0
        assert hub.postmortem("collect_error", query_id="q") is None
        assert len(hub.postmortems) == 0
    finally:
        telemetry.shutdown()


# ---------------------------------------------------------------------------
# scan metrics in explain("analyze") (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

def test_scan_metrics_annotated_in_explain_analyze(tmp_path):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.hot_cache import clear_hot_cache

    p = str(tmp_path / "t.parquet")
    pq.write_table(
        pa.table({"k": np.arange(4000) % 8, "v": np.arange(4000)}), p,
        compression="snappy")
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.scan.hotTableCache.enabled": True,
        "spark.rapids.tpu.diagnostics.enabled": True,
    })
    try:
        q = s.read.parquet(p).group_by("k").agg(sum_("v", "sv"))
        q.collect()
        out_miss = q.explain("analyze")
        assert "hotCacheMisses=1" in out_miss, out_miss
        q.collect()
        out_hit = q.explain("analyze")
        # per-query DELTAS, not cumulative: the hit run shows only the hit
        assert "hotCacheHits=1" in out_hit, out_hit
        assert "hotCacheMisses" not in out_hit
    finally:
        clear_hot_cache()
        s.close(check_leaks=False)


# ---------------------------------------------------------------------------
# bench gate (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

def _payloads():
    base = {"value": 0.8, "scan_inclusive_geomean": 0.2,
            "queries": {"qa_hot": {"scan_transfer_s": 1.0,
                                   "compileWall_s": 2.0}},
            "slo": {"": {"p95_ms": 100.0}}}
    good = {"value": 0.82, "scan_inclusive_geomean": 0.21,
            "queries": {"qa_hot": {"scan_transfer_s": 1.02,
                                   "compileWall_s": 2.2}},
            # the slo section is informational, never gated: warm-up
            # collects make its p95 cache-state dependent
            "slo": {"": {"p95_ms": 900.0}}}
    bad = {"value": 0.5, "scan_inclusive_geomean": 0.05,
           "queries": {"qa_hot": {"scan_transfer_s": 3.0,
                                  "compileWall_s": 9.0}},
           "slo": {"": {"p95_ms": 400.0}}}
    return base, good, bad


def test_bench_gate_flags_synthetic_regression():
    import bench_gate

    base, good, bad = _payloads()
    assert bench_gate.gate(base, good) == []
    regressions = bench_gate.gate(base, bad)
    text = "\n".join(regressions)
    assert "hot-path geomean" in text
    assert "scan_transfer_s" in text
    assert "compile wall" in text


def test_bench_gate_concurrency_p95():
    import bench_gate

    base = {"metric": "concurrency", "latency_ms": {"p95": 50.0}}
    ok = {"metric": "concurrency", "latency_ms": {"p95": 54.0}}
    bad = {"metric": "concurrency", "latency_ms": {"p95": 200.0}}
    dead = {"metric": "concurrency", "latency_ms": {"p95": 0.0}}
    assert bench_gate.gate(base, ok) == []
    assert len(bench_gate.gate(base, bad)) == 1
    # zero queries completed is a collapse, not a vacuous pass
    assert any("collapsed" in r for r in bench_gate.gate(base, dead))


def test_bench_gate_refuses_vacuous_comparisons():
    """A gate that silently checks nothing is a false PASS: payload-type
    mismatch, a partial new run, a collapsed geomean, and baseline
    queries missing from the new run must all flag."""
    import bench_gate

    single, _good, _bad = _payloads()
    conc = {"metric": "concurrency", "latency_ms": {"p95": 50.0}}
    assert any("mismatch" in r for r in bench_gate.gate(single, conc))
    assert any("mismatch" in r for r in bench_gate.gate(conc, single))

    partial = dict(single, partial=True)
    assert any("PARTIAL" in r for r in bench_gate.gate(single, partial))

    collapsed = {"value": 0.0, "scan_inclusive_geomean": 0.0,
                 "queries": {}}
    regs = bench_gate.gate(single, collapsed)
    assert any("collapsed" in r for r in regs)
    assert any("missing from new run" in r for r in regs)


def test_bench_gate_cli_exit_codes(tmp_path):
    import bench_gate

    base, good, bad = _payloads()
    pb, pg, pbad = (tmp_path / "b.json", tmp_path / "g.json",
                    tmp_path / "x.json")
    pb.write_text(json.dumps(base))
    pg.write_text(json.dumps(good))
    pbad.write_text(json.dumps(bad))
    assert bench_gate.main([str(pb), str(pg)]) == 0
    assert bench_gate.main([str(pb), str(pbad), "--json"]) == 1


# ---------------------------------------------------------------------------
# stress-harness timeline (ISSUE 7 satellite, tier-1 twin)
# ---------------------------------------------------------------------------

def test_stress_harness_records_telemetry_timeline(tmp_path):
    from run_stress import run_stress

    out = str(tmp_path / "timeline.json")
    s = run_stress(n_threads=2, rounds=1, seed=3, cancel_budget=0,
                   quiet=True, telemetry_out=out)
    assert s["failures"] == [] and s["leaks"] == []
    tel = s["telemetry"]
    assert tel["ticks"] >= 1 and tel["path"] == out
    data = json.load(open(out))
    assert data["timeline"]
    row = data["timeline"][-1]
    for key in ("ts", "admission_queued", "hbm_used_bytes", "p95_ms"):
        assert key in row
    assert data["slo"].get("", {}).get("count", 0) >= 1
    telemetry.shutdown()


def test_check_counters_telemetry_gate_in_sync():
    from check_counters import check

    assert check() == []
