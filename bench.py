"""Benchmark — BASELINE.md rungs 1 + 2.

Rung 1: TPC-H Q6 (scan+filter+product+sum, decimal money columns).
Rung 2: a TPC-DS-shaped mini-suite over a synthetic star schema
(store_sales ⋈ date_dim / store_returns):

  qa  date-dim broadcast join + grouped agg      (TPC-DS q3 shape)
  qb  shuffled LEFT join on (ticket, item) + agg (q25/q93 shape)
  qc  grouped agg + rank() window + filter       (q47/q51 shape)

Baselines: every query also runs on an HONEST vectorized
CPU baseline — hand-written numpy (bincount/searchsorted/lexsort), not the
row-at-a-time object-decimal oracle — and the headline `vs_baseline` is the
geomean TPU speedup over THAT.  The oracle path (`spark.rapids.sql.enabled
false`) is reported alongside as `vs_oracle`.

Timing excludes the first (compile) run.  Rung-2 queries run SCAN-INCLUSIVE
(device batches are NOT cached: every repeat pays host->device transfer);
Q6 reports both cached and scan-inclusive modes.  Effective GB/s =
referenced input bytes / TPU wall time, beside the HBM peak of the
device kind the run found (HBM_PEAK_GBPS; an unknown kind gets no
hbm_frac).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}
with per-query detail nested under "queries".

The payload names the device it ran on (platform, device_kind,
device_count) and, per query, the six fallback counters (runtime, query,
breaker-plan, advisor-plan, file-decoder, chunk-decode): a reader refuses
a run where any is non-zero.  The CPU stage fallback is switched off for
the timed sessions, so a program the chip's compiler refuses fails the
rung.  A rung that raises or mismatches is listed under "failed" and the
process exits non-zero.  Run it on another backend with JAX_PLATFORMS.

Env knobs: BENCH_ROWS (default 20M: at 2M the fixed per-query sync and
launch overheads dominate and most of HBM sits idle), BENCH_Q6_ROWS
(default 50M when BENCH_ROWS >= 10M), BENCH_REPEATS (default 2),
BENCH_TIME_BUDGET seconds (default 2400) — cold compiles can take
minutes, so the suite emits its JSON line from whatever completed inside
the budget instead of dying at an outer timeout with nothing (each
completed query is timed fully; skipped ones are listed under
"skipped").  BENCH_OUT (default BENCH_STREAM.json, "0" disables) streams
per-query results to a JSON file as each query completes — a `timeout`
SIGKILL mid-suite still leaves a parseable record of everything
finished; per-query counters include compileWall_s and the compile-cache
hit/miss counts, plus the cost model's predicted-vs-actual wall
(costPredictedWall_s, costModelHits/Misses — BENCH_PROFILE_DIR sets the
calibration store, "0" disables).

Query order: q6 -> qa -> qb -> qc -> rung3 -> q6_parquet, so a budget
kill cannot erase the window or spill numbers.  The transfer-bound _scan
variants and the CPU-oracle multi-repeats only run at <= 4M rows (the
row oracle is row-at-a-time python; at 20M+ they would eat the budget).
"""
from __future__ import annotations

import json
import math
import os
import time
from decimal import Decimal

import numpy as np


# Peak HBM bandwidth by jax device_kind, GB/s.  Source: Google Cloud
# documentation, "TPU v5e" (16 GB HBM2e at 819 GB/s per chip).  A kind
# that is not here gets no hbm_frac — never a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}

# the timed sessions must fail, not answer from the CPU oracle, when the
# device path breaks (resilience/domain.py stage fallback)
_NO_CPU_FALLBACK = {
    "spark.rapids.tpu.resilience.runtimeFallbackEnabled": False}


def _device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _not_finished(names, completed, universe=None):
    """Skip-list bookkeeping (ISSUE 10 satellite): only queries that did
    NOT complete belong in ``skipped_on_time_budget`` — a SIGKILL during
    rung3 must not mark an already-completed-and-streamed q6_parquet as
    skipped.  A query counts as finished when its record (or any
    mode/variant record: ``qa_join_agg`` -> ``qa_join_agg_hot``,
    ``rung3`` -> ``rung3_dec128_nested``) landed in the payload; a
    variant that is itself another tracked query name (``rung3_ooc``)
    never vouches for its prefix."""
    universe = set(universe if universe is not None else names)
    out = []
    for nm in names:
        done = any(
            (q == nm or q.startswith(nm + "_"))
            and not (q != nm and q in universe)
            for q in completed)
        if not done and nm not in out:
            out.append(nm)
    return out
N_STORES = 40
N_ITEMS = 100_000
N_DATES = 2555          # ~7 years of date_dim
DATE_SK0 = 2_450_000    # TPC-DS-style surrogate key base


# ===========================================================================
# data generation (shared by the TPU path and the vectorized CPU baselines)
# ===========================================================================

def make_store_sales(n: int):
    rng = np.random.default_rng(20260730)
    return {
        "date_sk": (DATE_SK0
                    + rng.integers(0, N_DATES, n)).astype(np.int32),
        "store_sk": rng.integers(1, N_STORES + 1, n).astype(np.int32),
        "item_sk": rng.integers(1, N_ITEMS + 1, n).astype(np.int32),
        "ticket": rng.integers(0, max(n // 8, 1), n),
        "quantity": rng.integers(1, 100, n),
        # DECIMAL(7,2) unscaled cents
        "ext_sales": rng.integers(100, 1_000_000, n),
        "net_profit": rng.integers(-100_000, 400_000, n),
    }


def make_date_dim():
    sk = np.arange(DATE_SK0, DATE_SK0 + N_DATES, dtype=np.int32)
    day = np.arange(N_DATES)
    year = (1998 + day // 365).astype(np.int32)
    doy = day % 365
    qoy = (doy // 92 + 1).clip(1, 4).astype(np.int32)
    moy = (doy // 31 + 1).clip(1, 12).astype(np.int32)
    return {"date_sk": sk, "d_year": year, "d_qoy": qoy, "d_moy": moy}


def make_store_returns(ss, n_ret: int):
    """Returns reference a sample of sales rows (unique (ticket,item))."""
    rng = np.random.default_rng(7)
    key = ss["ticket"] * np.int64(2 * N_ITEMS) + ss["item_sk"]
    uniq, first_idx = np.unique(key, return_index=True)
    take = rng.choice(len(uniq), size=min(n_ret, len(uniq)), replace=False)
    idx = first_idx[take]
    return {
        "ticket": ss["ticket"][idx],
        "item_sk": ss["item_sk"][idx],
        "return_amt": rng.integers(50, 500_000, len(idx)),
    }


# ===========================================================================
# TPU-path dataframes
# ===========================================================================

def _df(session, cols, types_):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import HostColumn
    from spark_rapids_tpu.plan.nodes import LocalTableScan
    from spark_rapids_tpu.session import DataFrame

    host = [HostColumn.from_numpy(np.ascontiguousarray(v), t)
            for (v, t) in zip(cols.values(), types_)]
    schema = T.StructType([T.StructField(name, t, False)
                           for name, t in zip(cols.keys(), types_)])
    return DataFrame(LocalTableScan(host, schema), session)


def df_store_sales(session, ss):
    from spark_rapids_tpu import types as T

    dec72 = T.DecimalType(7, 2)
    return _df(session, ss, [T.INT, T.INT, T.INT, T.LONG, T.LONG,
                             dec72, dec72])


def df_date_dim(session, dd):
    from spark_rapids_tpu import types as T

    return _df(session, dd, [T.INT, T.INT, T.INT, T.INT])


def df_store_returns(session, sr):
    from spark_rapids_tpu import types as T

    return _df(session, sr, [T.LONG, T.INT, T.DecimalType(7, 2)])


# ---------------------------------------------------------------------------
# rung 1: TPC-H Q6
# ---------------------------------------------------------------------------

def make_lineitem(n: int):
    rng = np.random.default_rng(20260729)
    return {
        "l_extendedprice": rng.integers(90_000, 10_500_000, n),
        "l_discount": rng.integers(0, 11, n),
        "l_quantity": rng.integers(100, 5100, n),
        "l_shipdate_days": rng.integers(8400, 9500, n).astype(np.int32),
    }


def build_q6(session, li):
    import datetime

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.session import col, lit, sum_

    dec = T.DecimalType(12, 2)
    df = _df(session, li, [dec, dec, dec, T.DATE])
    d0 = datetime.date(1994, 1, 1)
    d1 = datetime.date(1995, 1, 1)
    return (df.filter((col("l_shipdate_days") >= lit(d0))
                      & (col("l_shipdate_days") < lit(d1))
                      & (col("l_discount") >= lit(Decimal("0.05")))
                      & (col("l_discount") <= lit(Decimal("0.07")))
                      & (col("l_quantity") < lit(Decimal(24))))
            .select((col("l_extendedprice") * col("l_discount"))
                    .alias("revenue"))
            .agg(sum_("revenue", "revenue")))


def cpu_q6_vectorized(li):
    """Unscaled-int64 numpy Q6 — the honest CPU baseline."""
    f = ((li["l_shipdate_days"] >= 8766) & (li["l_shipdate_days"] < 9131)
         & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
         & (li["l_quantity"] < 2400))
    # product of two DECIMAL(12,2) -> scale 4; int64 is exact here
    return int(np.sum(li["l_extendedprice"][f] * li["l_discount"][f]))


# ---------------------------------------------------------------------------
# rung 2 queries
# ---------------------------------------------------------------------------

def build_qa(session, ss, dd):
    from spark_rapids_tpu.expr.predicates import EqualTo
    from spark_rapids_tpu.session import col, lit, sum_

    sales = df_store_sales(session, ss)
    dates = df_date_dim(session, dd)
    return (sales.join(dates.filter(EqualTo(col("d_qoy"), lit(1))),
                       on="date_sk")
            .group_by("d_year", "store_sk")
            .agg(sum_("ext_sales", "sum_sales")))


def cpu_qa_vectorized(ss, dd):
    qoy = np.zeros(DATE_SK0 + N_DATES + 1, np.int32)
    year = np.zeros(DATE_SK0 + N_DATES + 1, np.int32)
    qoy[dd["date_sk"]] = dd["d_qoy"]
    year[dd["date_sk"]] = dd["d_year"]
    f = qoy[ss["date_sk"]] == 1
    yk = year[ss["date_sk"][f]].astype(np.int64)
    key = (yk - 1998) * (N_STORES + 1) + ss["store_sk"][f]
    sums = np.bincount(key, weights=ss["ext_sales"][f].astype(np.float64),
                       minlength=(N_STORES + 1) * 16)
    out = {}
    for k in np.nonzero(sums)[0]:
        out[(1998 + k // (N_STORES + 1), k % (N_STORES + 1))] = int(sums[k])
    return out


def build_qb(session, ss, sr):
    from spark_rapids_tpu.session import col, lit, sum_
    from spark_rapids_tpu.expr.conditional import Coalesce
    from spark_rapids_tpu.expr.base import Literal
    from spark_rapids_tpu import types as T

    sales = df_store_sales(session, ss)
    rets = df_store_returns(session, sr)
    joined = sales.join(rets, on=["ticket", "item_sk"], how="left")
    net = (col("ext_sales")
           - Coalesce([col("return_amt"),
                       Literal(Decimal("0.00"), T.DecimalType(7, 2))]))
    return (joined.select(col("store_sk"), net.alias("net"))
            .group_by("store_sk").agg(sum_("net", "net_sales")))


def cpu_qb_vectorized(ss, sr):
    K = np.int64(2 * N_ITEMS)
    skey = ss["ticket"] * K + ss["item_sk"]
    rkey = sr["ticket"] * K + sr["item_sk"]
    order = np.argsort(rkey)
    rk_sorted = rkey[order]
    ramt_sorted = sr["return_amt"][order]
    pos = np.searchsorted(rk_sorted, skey)
    pos_c = np.clip(pos, 0, len(rk_sorted) - 1)
    found = (len(rk_sorted) > 0) & (rk_sorted[pos_c] == skey)
    matched = np.where(found, ramt_sorted[pos_c], 0)
    net = ss["ext_sales"] - matched
    sums = np.bincount(ss["store_sk"], weights=net.astype(np.float64),
                       minlength=N_STORES + 1)
    return {int(s): int(sums[s]) for s in range(1, N_STORES + 1)}


def build_qc(session, ss):
    from spark_rapids_tpu.plan.nodes import WindowFunction
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.session import col, lit, sum_

    sales = df_store_sales(session, ss)
    daily = (sales.group_by("store_sk", "date_sk")
             .agg(sum_("ext_sales", "day_sales")))
    ranked = daily.window(
        [WindowFunction("rank", None, "rk")],
        partition_by=["store_sk"],
        order_by=[(col("day_sales"), SortSpec(ascending=False,
                                              nulls_first=False))])
    return ranked.filter(col("rk") <= lit(5))


def cpu_qc_vectorized(ss):
    key = ss["store_sk"].astype(np.int64) * np.int64(N_DATES + 1) \
        + (ss["date_sk"].astype(np.int64) - DATE_SK0)
    sums = np.bincount(key, weights=ss["ext_sales"].astype(np.float64),
                       minlength=(N_STORES + 1) * (N_DATES + 1))
    nz = np.nonzero(sums)[0]
    stores = nz // (N_DATES + 1)
    vals = sums[nz]
    order = np.lexsort((-vals, stores))
    st_sorted = stores[order]
    v_sorted = vals[order]
    idx = np.arange(len(order))
    starts = np.ones(len(order), np.bool_)
    starts[1:] = st_sorted[1:] != st_sorted[:-1]
    run_start = np.maximum.accumulate(np.where(starts, idx, -1))
    # SQL rank() with ties: 1 + rows before the first peer of this value
    new_val = starts.copy()
    new_val[1:] |= v_sorted[1:] != v_sorted[:-1]
    anchor = np.maximum.accumulate(np.where(new_val, idx, -1))
    rank = anchor - run_start + 1
    keep = rank <= 5
    out = set()
    dates_back = (nz % (N_DATES + 1)) + DATE_SK0
    d_sorted = dates_back[order]
    for s, d, v, r in zip(st_sorted[keep], d_sorted[keep],
                          v_sorted[keep], rank[keep]):
        out.add((int(s), int(d), int(v), int(r)))
    return out


# ===========================================================================
# harness
# ===========================================================================

def _time_repeats(fn, repeats, counters=False):
    """Time fn (excluding the first, compile, run).  With counters=True the
    third return value holds backend-independent per-run perf counters
    (programs launched / compiles / host syncs / bytes moved) averaged
    over the timed repeats."""
    from spark_rapids_tpu import perfcounters as PC

    # warm until a run triggers no fresh XLA compile (max 3): the engine
    # switches strategy after run 1 (e.g. the join's unique-build fast path
    # compiles on run 2), and a compile landing inside the timed repeats
    # would report seconds of compile as if it were execution
    for _ in range(3):
        pre = PC.COUNTERS["compiles"]
        fn()
        if PC.COUNTERS["compiles"] == pre:
            break
    snap = None
    if counters:
        snap = PC.snapshot()
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    dt = (time.perf_counter() - t0) / repeats
    if not counters:
        return dt, out
    from spark_rapids_tpu import perfcounters as PC

    d = PC.since(snap)
    per_run = {
        "nProgramsLaunched": d["programs_launched"] / repeats,
        "nCompiles": d["compiles"] / repeats,
        "nHostSyncs": d["host_syncs"] / repeats,
        "bytesD2H": d["bytes_d2h"] / repeats,
        "bytesH2D": d["bytes_h2d"] / repeats,
        "launchWall_s": d["launch_wall_ns"] / repeats / 1e9,
        # transport decomposition (ISSUE 6 satellite): scan_transfer_s
        # is the wall inside scan upload sites (pad+device_put and
        # compressed-page ships — host arrow decode excluded);
        # scan_compute_s is the JITTED-program wall (uploads are never
        # jitted, so the two are disjoint; for the scan rungs the
        # launches are dominated by decode+query programs, for
        # device-resident rungs it equals launchWall_s) — together
        # scan_inclusive movements split into transfer vs compute; the
        # prefetch/overlap and hot-cache counters say how much transfer
        # hid behind compute or was skipped entirely
        "scan_transfer_s": d["scan_transfer_ns"] / repeats / 1e9,
        "scan_compute_s": d["launch_wall_ns"] / repeats / 1e9,
        "bytesH2DLogical": d["bytes_h2d_logical"] / repeats,
        "bytesH2DOverlapped": d["bytes_h2d_overlapped"] / repeats,
        "prefetchStall_s": d["prefetch_stall_ns"] / repeats / 1e9,
        "nPagesDeviceDecompressed":
            d["pages_device_decompressed"] / repeats,
        "nChunkDecodeFallbacks": d["chunk_decode_fallbacks"] / repeats,
        "nHotCacheHits": d["hot_cache_hits"] / repeats,
        "nHotCacheMisses": d["hot_cache_misses"] / repeats,
        # compile-cache detail (compilecache/): wall spent in fresh XLA
        # compiles (inline + AOT pool) and registry hit/miss counts —
        # compileWall_s is where cold-start time goes
        "compileWall_s": d["compile_wall_ns"] / repeats / 1e9,
        "aotCompileWall_s": d["aot_compile_wall_ns"] / repeats / 1e9,
        "nCompileCacheHits": d["compile_cache_hits"] / repeats,
        "nCompileCacheMisses": d["compile_cache_misses"] / repeats,
        # resilience events (ISSUE 3 satellite): a bench run that only
        # finished because stages retried or fell back to the oracle must
        # say so in its own record
        "nTransientRetries": d["transient_retries"] / repeats,
        "nOomRestarts": d["oom_restarts"] / repeats,
        "nRuntimeFallbacks": d["runtime_fallbacks"] / repeats,
        "nBreakerTrips": d["breaker_trips"] / repeats,
        "nQueryFallbacks": d["query_fallbacks"] / repeats,
        "nBreakerPlanFallbacks": d["breaker_plan_fallbacks"] / repeats,
        "nAdvisorPlanFallbacks": d["advisor_plan_fallbacks"] / repeats,
        # I/O fault domain (ISSUE 5 satellite): a bench run that only
        # finished by skipping bad inputs or retrying files on the
        # native decoder must say so in its own record
        "nFilesSkippedCorrupt": d["files_skipped_corrupt"] / repeats,
        "nFilesSkippedMissing": d["files_skipped_missing"] / repeats,
        "nFileDecoderFallbacks": d["file_decoder_fallbacks"] / repeats,
        # cost model (ISSUE 8 satellite): the plan-time prediction the
        # calibration store produced for each timed run vs the measured
        # tpu_s — tools/bench_gate.py renders the (non-gating)
        # prediction-error column from these
        "costPredictedWall_s":
            d["cost_model_predicted_wall_ns"] / repeats / 1e9,
        "costMatchedActualWall_s":
            d["cost_model_matched_actual_wall_ns"] / repeats / 1e9,
        "costModelHits": d["cost_model_hits"] / repeats,
        "costModelMisses": d["cost_model_misses"] / repeats,
        # out-of-core exchange + ICI shuffle (ISSUE 10): exchange walls
        # decompose into the partition programs (exchangePartition_s)
        # vs the spill-backed queue (exchangeSpill_s — serialize /
        # track / materialize), with the collective-shuffle wall
        # (iciShuffle_s) as the third component on mesh runs; the
        # count columns say how the planner sized partitions and how
        # the AQE reader re-coalesced them
        "exchangePartition_s": d["exchange_partition_ns"] / repeats / 1e9,
        "exchangeSpill_s": d["exchange_spill_ns"] / repeats / 1e9,
        "iciShuffle_s": d["ici_shuffle_ns"] / repeats / 1e9,
        "nIciEpochs": d["ici_epochs"] / repeats,
        "nIciRowsExchanged": d["ici_rows_exchanged"] / repeats,
        "nExchangePartitionsPlanned":
            d["exchange_partitions_planned"] / repeats,
        "nExchangeHostBlocks": d["exchange_host_blocks"] / repeats,
        "nPartitionsCoalesced": d["partitions_coalesced"] / repeats,
    }
    # resource bill (ISSUE 18 satellite): the last settled bill's
    # device footprint columns.  With accounting disabled (the bench
    # default) last_bill() is None and the columns are absent — the
    # accountingOverhead A/B below owns the enabled-cost story.
    from spark_rapids_tpu import accounting as _acct

    lb = _acct.last_bill()
    if lb is not None:
        sp = lb.get("spill") or {}
        per_run["devicePeakBytes"] = lb.get("device_peak_bytes", 0)
        per_run["deviceByteSeconds"] = lb.get("device_byte_seconds", 0.0)
        per_run["spilledBytes"] = (sp.get("host_bytes", 0)
                                   + sp.get("disk_bytes", 0))
    return dt, out, per_run


def _diag_conf():
    """Diagnostics confs for bench sessions (ISSUE 3 satellite): every
    bench run doubles as a diagnostics corpus.  BENCH_DIAG_DIR (default
    diag_logs; "0" disables) receives one JSONL event log per query,
    ready for tools/profile_report.py; the per-query record carries the
    last timed run's log path under "eventLog".  Recorder overhead on
    the timed TPU runs is one lock+append per event (µs) under launches
    that cost 10ms-300ms — but when comparing against a pre-diagnostics
    BENCH_r* baseline at sub-ms granularity, set BENCH_DIAG_DIR=0 for
    the un-instrumented numbers (the CPU baselines never run through
    the recorder either way)."""
    diag_dir = os.environ.get("BENCH_DIAG_DIR", "diag_logs")
    if not diag_dir or diag_dir == "0":
        return {}
    return {
        "spark.rapids.tpu.diagnostics.enabled": True,
        "spark.rapids.tpu.diagnostics.eventLogDir": diag_dir,
        # no rotation for bench corpora: a sweep writes one log per
        # collect and BENCH_OUT records the paths — rotating at the
        # default 64 would dangle the recorded eventLog references
        "spark.rapids.tpu.diagnostics.eventLog.maxFiles": 0,
    }


def _profile_conf():
    """Calibration-store conf for bench sessions (ISSUE 8 satellite):
    every bench round both FEEDS the store (operator spans fold in at
    query_end) and MEASURES it (the plan-time prediction for each query
    lands in the record as costPredictedWall_s, diffable across rounds
    by tools/bench_gate.py's prediction-error column).
    BENCH_PROFILE_DIR overrides the store location (default
    profile_store; "0" disables — e.g. when comparing against a
    pre-profiling baseline at sub-ms granularity)."""
    prof_dir = os.environ.get("BENCH_PROFILE_DIR", "profile_store")
    if not prof_dir or prof_dir == "0":
        return {}
    return {"spark.rapids.tpu.profile.dir": prof_dir}


def _event_log_of(df) -> str:
    diag = getattr(df, "_last_diag", None)
    return getattr(diag, "event_log_path", None) or ""


def _session(enabled: bool, cache_batches: bool = False):
    from spark_rapids_tpu.session import TpuSession

    return TpuSession({
        "spark.rapids.sql.enabled": enabled,
        "spark.rapids.tpu.scan.cacheDeviceBatches": cache_batches,
        **(_NO_CPU_FALLBACK if enabled else {}),
        **_diag_conf(),
        **_profile_conf(),
    })


def _bytes_of(*col_dicts):
    return float(sum(v.nbytes for d in col_dicts for v in d.values()))


def run_concurrency(n_workers: int, rounds: int = 3,
                    rows: int = 200_000) -> dict:
    """``bench.py --concurrency N`` (ISSUE 4 satellite): N threads run
    the rung-2-shaped mini queries concurrently through the query
    lifecycle layer; reports p50/p95 per-query latency and admission
    queue wait.  Emits one JSON line like the main suite."""
    import threading

    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.lifecycle import last_query_stats
    from spark_rapids_tpu.session import TpuSession, sum_

    ss = make_store_sales(rows)
    dd = make_date_dim()
    conf = {
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.concurrentQueries": str(
            int(os.environ.get("BENCH_CONCURRENT_QUERIES", 4))),
        "spark.rapids.tpu.admission.maxQueueDepth": "64",
    }

    def q(s):
        sales = _df(s, {k: ss[k] for k in ("date_sk", "store_sk",
                                           "ext_sales")},
                    [T.INT, T.INT, T.LONG])
        dates = _df(s, dd, [T.INT, T.INT, T.INT, T.INT])
        return sales.join(dates, on="date_sk", how="inner") \
            .group_by("store_sk").agg(sum_("ext_sales", "s"))

    # warm compile once, single-threaded
    q(TpuSession(conf)).collect()

    walls, waits, lock = [], [], threading.Lock()
    snap = PC.snapshot()
    t0 = time.perf_counter()

    def worker():
        s = TpuSession(conf)
        for _ in range(rounds):
            q(s).collect()
            st = last_query_stats() or {}
            with lock:
                walls.append(st.get("wall_ns", 0))
                waits.append(st.get("admission_wait_ns", 0))

    threads = [threading.Thread(target=worker) for _ in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0
    d = PC.since(snap)

    def pct(xs, p):
        xs = sorted(xs) or [0]
        return round(xs[min(int(len(xs) * p), len(xs) - 1)] / 1e6, 3)

    out = {
        "metric": "concurrency", "unit": "ms",
        "workers": n_workers, "rounds": rounds, "rows": rows,
        "wall_s": round(wall_s, 3),
        "queries": len(walls),
        "qps": round(len(walls) / wall_s, 2) if wall_s else 0.0,
        "latency_ms": {"p50": pct(walls, 0.5), "p95": pct(walls, 0.95)},
        "queue_wait_ms": {"p50": pct(waits, 0.5), "p95": pct(waits, 0.95)},
        "counters": {k: d[k] for k in (
            "queries_admitted", "queries_rejected", "queries_cancelled",
            "deadline_trips", "admission_wait_ns")},
    }
    print(json.dumps(out))
    return out


def run_serving(n_workers: int, rounds: int = 4,
                rows: int = 200_000) -> dict:
    """``bench.py --serving N`` (ISSUE 19 satellite): the mixed-tenant
    serving benchmark — 2 'light' workers and ``N - 2`` 'heavy' workers
    drive the rung-2-shaped mini query through isolated tenant
    sessions, fair-share admission, tenant quotas, and the
    result-fragment cache.  Emits one JSON line whose shed-rate /
    per-tenant-p95 / cross-tenant-leak columns tools/bench_gate.py
    pins: leaks and warm-repeat recompiles are STRICT zeros, p95 and
    shed rate are baseline-relative like the --concurrency gate.

    Per-tenant p95 comes from the walls of the TIMED phase only (every
    query there runs warm and unique), not the SLO histograms — those
    include the warm phase's compile walls, which depend on cache
    state, exactly what the bench gate must not flag."""
    import threading

    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.governor import shutdown_governor
    from spark_rapids_tpu.lifecycle import (
        QueryRejected,
        last_query_stats,
        leak_report_all,
        reset_admission,
    )
    from spark_rapids_tpu.serving import peek_serving, shutdown_serving
    from spark_rapids_tpu.session import TpuSession, sum_

    n_workers = max(n_workers, 3)
    ss = make_store_sales(rows)
    dd = make_date_dim()

    shutdown_governor()
    shutdown_serving()
    reset_admission()
    conf = {
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.serving.enabled": True,
        # equal weights: the fairness the gate pins must come from the
        # usage accounts, not from tilting the scale
        "spark.rapids.tpu.serving.weights": "light:1,heavy:1",
        "spark.rapids.tpu.serving.quotas": "heavy:2",
        "spark.rapids.tpu.governor.enabled": True,
        "spark.rapids.tpu.governor.updatePeriodMs": "10",
        "spark.rapids.tpu.concurrentQueries": str(
            int(os.environ.get("BENCH_CONCURRENT_QUERIES", 3))),
        "spark.rapids.tpu.admission.maxQueueDepth": "64",
        "spark.rapids.tpu.resilience.backoffBaseMs": "0",
    }
    TpuSession(conf)                   # installs the tier + scheduler
    tier = peek_serving()

    def q(s, n_limit):
        sales = _df(s, {k: ss[k] for k in ("date_sk", "store_sk",
                                           "ext_sales")},
                    [T.INT, T.INT, T.LONG])
        dates = _df(s, dd, [T.INT, T.INT, T.INT, T.INT])
        return sales.join(dates, on="date_sk", how="inner") \
            .group_by("store_sk").agg(sum_("ext_sales", "s")) \
            .limit(n_limit)

    # warm phase: one canonical collect per tenant compiles the shape
    # and seeds a result fragment for the warm-repeat pin below
    for tenant in ("light", "heavy"):
        sess = tier.session(tenant)
        sess.collect(q(sess.spark, 10))

    walls = {"light": [], "heavy": []}
    sheds = {"light": 0, "heavy": 0}
    submitted = {"light": 0, "heavy": 0}
    lock = threading.Lock()
    snap = PC.snapshot()
    t0 = time.perf_counter()

    def worker(idx: int, tenant: str):
        sess = tier.session(tenant)
        for it in range(rounds):
            # a unique limit literal per iteration -> a unique result
            # key -> real execution (no cache short-circuit in the
            # timed phase)
            n = 11 + idx * 1000 + it
            try:
                sess.collect(q(sess.spark, n))
            except QueryRejected as e:
                with lock:
                    sheds[tenant] += 1
                    submitted[tenant] += 1
                time.sleep(min((e.retry_after_ms or 0) / 1000.0, 0.25))
                continue
            st = last_query_stats() or {}
            with lock:
                submitted[tenant] += 1
                walls[tenant].append(st.get("wall_ns", 0))

    threads = [threading.Thread(
        target=worker, args=(i, "light" if i < 2 else "heavy"))
        for i in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0
    d = PC.since(snap)

    # warm-repeat pin: the canonical query repeats from the result
    # cache — zero fresh compiles, one hit per tenant
    snap_warm = PC.snapshot()
    for tenant in ("light", "heavy"):
        sess = tier.session(tenant)
        sess.collect(q(sess.spark, 10))
    d_warm = PC.since(snap_warm)

    # cross-tenant leak probes: each count here is a hard isolation
    # break (the gate pins the column at 0)
    cross_tenant_leaks = 0
    light, heavy = tier.session("light"), tier.session("heavy")
    light.create_temp_view("bench_probe", q(light.spark, 10))
    try:
        heavy.view("bench_probe")
        cross_tenant_leaks += 1        # saw another tenant's view
    except KeyError:
        pass
    light.set_conf("spark.rapids.tpu.telemetry.slo.targetP95Ms", "1234")
    if heavy.get_conf(
            "spark.rapids.tpu.telemetry.slo.targetP95Ms") == "1234":
        cross_tenant_leaks += 1        # saw another tenant's conf
    # an identical plan under the other tenant must MISS the cache
    # (limit=9 appears nowhere else: the timed phase starts at 11, the
    # warm phase used 10 — neither tenant can hit its OWN fragment)
    snap_x = PC.snapshot()
    heavy.collect(q(heavy.spark, 9))   # heavy caches limit=9
    light.collect(q(light.spark, 9))   # light's twin must miss
    if PC.since(snap_x)["result_cache_hits"] > 0:
        cross_tenant_leaks += 1        # shared a result fragment

    tier.close_session("light")
    tier.close_session("heavy")
    leaks = list(leak_report_all())
    shutdown_serving()
    shutdown_governor()
    reset_admission()
    from spark_rapids_tpu.compilecache.aot import quiesce_aot

    quiesce_aot(60.0)

    def pct(xs, p):
        xs = sorted(xs) or [0]
        return round(xs[min(int(len(xs) * p), len(xs) - 1)] / 1e6, 3)

    n_queries = sum(len(v) for v in walls.values())
    n_submitted = sum(submitted.values())
    out = {
        "metric": "serving", "unit": "ms",
        "workers": n_workers, "rounds": rounds, "rows": rows,
        "wall_s": round(wall_s, 3),
        "queries": n_queries,
        "qps": round(n_queries / wall_s, 2) if wall_s else 0.0,
        "tenants": {t: {
            "queries": len(walls[t]),
            "latency_ms": {"p50": pct(walls[t], 0.5),
                           "p95": pct(walls[t], 0.95)},
            "sheds": sheds[t],
        } for t in ("light", "heavy")},
        "shed_rate": round(sum(sheds.values()) / n_submitted, 4)
        if n_submitted else 0.0,
        "cross_tenant_leaks": cross_tenant_leaks + len(leaks),
        "leaks": leaks[:10],
        "warm_repeat": {
            "result_cache_hits": d_warm["result_cache_hits"],
            "compiles": d_warm["compiles"],
        },
        "counters": {k: d[k] for k in (
            "queries_admitted", "queries_rejected", "fair_share_admissions",
            "tenant_sheds", "tenant_preempts", "result_cache_hits",
            "result_cache_misses")},
    }
    print(json.dumps(out))
    return out


def measure_progress_overhead(rows: int = 100_000,
                              repeats: int = 5) -> dict:
    """``progressOverhead`` (ISSUE 12 satellite): the wall cost of the
    per-batch live-progress instrumentation on a hot in-memory
    aggregate — the same query timed ``repeats``x with
    ``spark.rapids.tpu.progress.enabled`` off then on (both sessions
    share the warm compile cache; each warms once untimed).  Recorded
    in the payload so tools/bench_gate.py can watch the enabled-path
    tax across rounds; the disabled path's zero-call contract is pinned
    separately by tests/test_progress.py."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.session import TpuSession, sum_

    ss = make_store_sales(rows)

    def q(s):
        sales = _df(s, {k: ss[k] for k in ("date_sk", "store_sk",
                                           "ext_sales")},
                    [T.INT, T.INT, T.LONG])
        return sales.group_by("store_sk").agg(sum_("ext_sales", "s"))

    timings = {}
    for key, enabled in (("disabled_s", False), ("enabled_s", True)):
        s = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.tpu.progress.enabled": enabled,
        })
        df = q(s)
        t, _ = _time_repeats(df.collect, repeats)   # warms untimed
        timings[key] = round(t, 6)
    base = timings["disabled_s"]
    timings["overhead_pct"] = round(
        (timings["enabled_s"] - base) * 100.0 / base, 2) if base else 0.0
    timings["rows"] = rows
    timings["repeats"] = repeats
    return timings


def measure_accounting_overhead(rows: int = 100_000,
                                repeats: int = 5) -> dict:
    """``accountingOverhead`` (ISSUE 18 satellite): the wall cost of the
    per-handle bill charging on a hot in-memory aggregate — the same
    query timed ``repeats``x with ``spark.rapids.tpu.accounting.enabled``
    off then on, MIN of repeats per arm (the charge tax is a fixed
    per-handle cost; min discards scheduler noise that an average would
    smear into the 2% gate).  tools/bench_gate.py pins overhead_pct; the
    disabled path's zero-call contract is pinned separately by
    tests/test_accounting.py with cProfile."""
    from spark_rapids_tpu import accounting as _acct
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.session import TpuSession, sum_

    ss = make_store_sales(rows)

    def q(s):
        sales = _df(s, {k: ss[k] for k in ("date_sk", "store_sk",
                                           "ext_sales")},
                    [T.INT, T.INT, T.LONG])
        return sales.group_by("store_sk").agg(sum_("ext_sales", "s"))

    timings = {}
    # disabled arm FIRST: maybe_configure installs the process-global
    # ledger registry, so the enabled session must come second (and be
    # shut down after) to keep the rest of the suite accounting-free
    for key, enabled in (("disabled_s", False), ("enabled_s", True)):
        s = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.tpu.accounting.enabled": enabled,
        })
        df = q(s)
        from spark_rapids_tpu import perfcounters as PC

        for _ in range(3):   # warm until no fresh compile (untimed)
            pre = PC.COUNTERS["compiles"]
            df.collect()
            if PC.COUNTERS["compiles"] == pre:
                break
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            df.collect()
            best = min(best, time.perf_counter() - t0)
        timings[key] = round(best, 6)
    _acct.shutdown()
    base = timings["disabled_s"]
    timings["overhead_pct"] = round(
        (timings["enabled_s"] - base) * 100.0 / base, 2) if base else 0.0
    timings["rows"] = rows
    timings["repeats"] = repeats
    return timings


def main():
    # --concurrency N: run the concurrent-query latency sweep instead of
    # the single-stream suite
    import sys

    # --gate BASELINE.json (ISSUE 7 satellite): after emitting, diff the
    # payload against the baseline with tools/bench_gate.py and exit
    # non-zero on a regression — a bench sweep IS the regression check
    gate_path = None
    if "--gate" in sys.argv:
        gidx = sys.argv.index("--gate")
        if gidx + 1 >= len(sys.argv):
            # a silently-disarmed gate is a false PASS: fail loudly like
            # an unreadable baseline does
            print("bench gate: --gate requires a BASELINE.json operand",
                  file=sys.stderr)
            return 1
        gate_path = sys.argv[gidx + 1]

    def run_gate(payload) -> int:
        if not gate_path:
            return 0
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import bench_gate

        try:
            base = bench_gate.load(gate_path)
        except (OSError, ValueError) as e:
            print(f"bench gate: cannot load baseline {gate_path}: {e}",
                  file=sys.stderr)
            return 1
        regressions = bench_gate.gate(base, payload)
        for r in regressions:
            print(f"REGRESSION: {r}", file=sys.stderr)
        # informational cost-model drift column (never gates)
        for p in bench_gate.prediction_report(base, payload):
            print(f"note: {p}", file=sys.stderr)
        print("bench gate vs " + gate_path + ": "
              + ("PASS" if not regressions
                 else f"FAIL ({len(regressions)} regression(s))"),
              file=sys.stderr)
        return 1 if regressions else 0

    if "--concurrency" in sys.argv:
        idx = sys.argv.index("--concurrency")
        n_workers = int(sys.argv[idx + 1]) if idx + 1 < len(sys.argv) else 4
        out = run_concurrency(
            n_workers,
            rounds=int(os.environ.get("BENCH_CONC_ROUNDS", 3)),
            rows=int(os.environ.get("BENCH_CONC_ROWS", 200_000)))
        return run_gate(out)
    # --serving N (ISSUE 19 satellite): the mixed-tenant serving
    # benchmark — shed-rate / per-tenant-p95 / cross-tenant-leak
    # columns, gated like the concurrency payload
    if "--serving" in sys.argv:
        idx = sys.argv.index("--serving")
        n_workers = int(sys.argv[idx + 1]) if idx + 1 < len(sys.argv) else 6
        out = run_serving(
            n_workers,
            rounds=int(os.environ.get("BENCH_CONC_ROUNDS", 4)),
            rows=int(os.environ.get("BENCH_CONC_ROWS", 200_000)))
        return run_gate(out)
    n = int(os.environ.get("BENCH_ROWS", 20_000_000))
    n_q6 = int(os.environ.get("BENCH_Q6_ROWS",
                              50_000_000 if n >= 10_000_000 else n))
    repeats = int(os.environ.get("BENCH_REPEATS", 2))
    # the row-at-a-time CPU oracle is deterministic and ~15-30x slower than
    # the engine at 20M+; one timed run is enough there
    oracle_repeats = repeats if n <= 4_000_000 else 1
    scan_variants = n <= 4_000_000
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 2400))
    t_start = time.perf_counter()
    skipped = []

    # an outer `timeout`'s SIGTERM must still yield the JSON line: convert
    # it to an exception so the finally-emit below runs with whatever
    # queries completed (cold compiles can exceed any fixed budget)
    import signal

    def _term(_sig, _frm):
        raise TimeoutError("SIGTERM/SIGINT during bench")

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    # Persistent XLA compile cache: one authority — the session applies
    # spark.rapids.tpu.compileCache.dir process-wide (and sets nothing
    # when JAX_COMPILATION_CACHE_DIR placed the cache from outside);
    # BENCH_COMPILE_CACHE overrides the conf (value -> dir, "0" -> off).
    cache_env = os.environ.get("BENCH_COMPILE_CACHE")
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.session import _apply_compile_cache

    _apply_compile_cache(TpuConf(
        {} if cache_env is None
        else {"spark.rapids.tpu.compileCache.dir": cache_env}))
    device = _device_record()
    hbm_peak = HBM_PEAK_GBPS.get(device["device_kind"])
    queries = {}
    # progressOverhead (ISSUE 12): filled right before the final emit
    progress_box = {}
    # accountingOverhead (ISSUE 18): same slot pattern
    accounting_box = {}

    emitted = {"done": False, "rc": 0}
    # rungs that raised or mismatched: the record keeps every other
    # rung, and the process exits non-zero
    failed = []

    def over_budget():
        return time.perf_counter() - t_start > budget

    def progress(msg):
        import sys

        print(f"[bench {time.perf_counter() - t_start:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def _telemetry_section():
        """SLO/telemetry section (ISSUE 7): per-plan-signature latency
        p50/p95 from the process hub's histograms, plus sampler/flight
        state — the numbers tools/bench_gate.py diffs across runs."""
        from spark_rapids_tpu import perfcounters as PC
        from spark_rapids_tpu import telemetry

        hub = telemetry.get_hub()
        if hub is None:
            return {}, {}
        slo = telemetry.slo_summary()
        tel = {
            "sampler_ticks": hub.sampler.ticks,
            "flight_events": hub.flight.events_recorded,
            "postmortems": len(hub.postmortems),
            "slo_violations": PC.COUNTERS.get("slo_violations", 0),
        }
        return slo, tel

    def _payload(partial: bool):
        import copy

        qs = copy.deepcopy(queries)
        rung2 = [q for q in ("qa_join_agg_hot", "qb_left_join_hot",
                             "qc_window_hot") if q in qs]
        geo_vec = (math.exp(sum(math.log(qs[q]["vs_vec"])
                                for q in rung2) / len(rung2))
                   if rung2 else 0.0)
        # scan-inclusive geomean covers every completed query that pays
        # the transfer each run: the qa _scan variant (small-row runs)
        # and q6_parquet (real snappy files through the compressed-
        # transfer device decode, every run)
        rung2_scan = [q for q in ("qa_join_agg_scan", "q6_parquet")
                      if q in qs and qs[q].get("vs_vec", 0) > 0]
        geo_scan = (math.exp(sum(math.log(qs[q]["vs_vec"])
                                 for q in rung2_scan) / len(rung2_scan))
                    if rung2_scan else 0.0)
        for q in qs.values():
            if hbm_peak is not None:
                q["hbm_frac"] = q["eff_gbps"] / hbm_peak
            for k in list(q):
                if isinstance(q[k], (int, float)):
                    q[k] = round(q[k], 6)
        slo, tel = _telemetry_section()
        return {
            "metric": "tpcds_mini_geomean_speedup_vs_vectorized_cpu",
            "value": round(geo_vec, 3),
            "unit": "x",
            "vs_baseline": round(geo_vec, 3),
            "rows": n,
            **device,
            "partial": partial,
            "failed": list(failed),
            "skipped_on_time_budget": list(skipped),
            "scan_inclusive_geomean": round(geo_scan, 3),
            "slo": slo,
            "telemetry": tel,
            "progressOverhead": dict(progress_box) or None,
            "accountingOverhead": dict(accounting_box) or None,
            "hbm_roofline_gbps": hbm_peak,
            "note": ("vs_baseline = geomean TPU speedup over "
                     "hand-vectorized numpy (bincount/searchsorted/"
                     "lexsort) across the completed rung-2 queries with "
                     "device-resident inputs (_hot); "
                     "scan_inclusive_geomean pays the host->device "
                     "transfer every run; 'skipped_on_time_budget' lists "
                     "queries that did not fit the budget and 'failed' "
                     "the rungs that raised or mismatched; "
                     "per-query detail incl. TPC-H Q6 under 'queries'"),
            "queries": qs,
        }

    # streaming output (a `timeout` SIGKILL after the -k grace would erase
    # the whole run if the one JSON line only printed at the very end).
    # Each completed query
    # atomically rewrites BENCH_OUT (tmp + rename) so ANY kill leaves a
    # parseable file with everything finished so far.  "0" disables.
    stream_path = os.environ.get("BENCH_OUT", "BENCH_STREAM.json")

    def _write_stream(payload):
        if not stream_path or stream_path == "0":
            return
        try:
            tmp = stream_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, stream_path)
        except OSError:
            pass

    def stream():
        _write_stream(_payload(partial=True))

    def emit():
        if emitted["done"]:
            return
        emitted["done"] = True
        payload = _payload(partial=False)
        _write_stream(payload)
        print(json.dumps(payload), flush=True)
        emitted["rc"] = run_gate(payload) or (1 if failed else 0)

    _ALL = ["qa_join_agg", "qb_left_join", "qc_window", "rung3",
            "rung3_ooc", "rung4_dist", "rung5_recovery", "q6_parquet"]

    def mark_skipped(names):
        # only queries that did NOT finish (ISSUE 10 satellite): a
        # record already streamed to BENCH_OUT is completed, not skipped
        skipped.extend(_not_finished(
            names, queries, universe=set(_ALL) | {"q6"}))

    def abort(current):
        idx = _ALL.index(current) if current in _ALL else 0
        mark_skipped(_ALL[idx:])
        progress(f"terminated during {current}; emitting partial results")
        emit()

    try:
        # ---- rung 1: Q6 ------------------------------------------------------
        li = make_lineitem(n_q6)
        q6_bytes = _bytes_of(li)

        t_vec, vec_res = _time_repeats(lambda: cpu_q6_vectorized(li), repeats)
        oracle_df = build_q6(_session(False), li)
        t_oracle, oracle_rows = _time_repeats(oracle_df.collect,
                                              oracle_repeats)
        progress(f"q6: baselines done (vec {t_vec:.2f}s, oracle "
                 f"{t_oracle:.2f}s, rows={n_q6})")

        tpu_hot_df = build_q6(_session(True, cache_batches=True), li)
        t_hot, tpu_rows, ctr_hot = _time_repeats(tpu_hot_df.collect, repeats,
                                                 counters=True)
        progress(f"q6_hot: tpu {t_hot:.3f}s (vs_vec {t_vec / t_hot:.2f})")

        assert int(tpu_rows[0][0].scaleb(4)) == vec_res, \
            f"Q6 mismatch: tpu {tpu_rows[0][0]} vs vectorized {vec_res}"
        assert tpu_rows == oracle_rows

        queries["q6_hot"] = dict(
            tpu_s=t_hot, cpu_vec_s=t_vec, cpu_oracle_s=t_oracle,
            rows_per_s=n_q6 / t_hot, eff_gbps=q6_bytes / t_hot / 1e9,
            vs_vec=t_vec / t_hot, vs_oracle=t_oracle / t_hot,
            eventLog=_event_log_of(tpu_hot_df), **ctr_hot)
        stream()
        if scan_variants:
            tpu_scan_df = build_q6(_session(True, cache_batches=False), li)
            t_scan, _, ctr_scan = _time_repeats(tpu_scan_df.collect, repeats,
                                                counters=True)
            queries["q6_scan"] = dict(
                tpu_s=t_scan, cpu_vec_s=t_vec, cpu_oracle_s=t_oracle,
                rows_per_s=n_q6 / t_scan, eff_gbps=q6_bytes / t_scan / 1e9,
                vs_vec=t_vec / t_scan, vs_oracle=t_oracle / t_scan,
                eventLog=_event_log_of(tpu_scan_df), **ctr_scan)
            stream()
        del li
    except TimeoutError:
        mark_skipped(["q6"] + _ALL)
        progress("terminated during rung 1; emitting partial results")
        emit()
        return emitted["rc"]

    # ---- rung 2 ----------------------------------------------------------
    ss = make_store_sales(n)
    dd = make_date_dim()
    sr = make_store_returns(ss, n // 10)

    def run_query(name, build, args, vec_fn, check, bytes_,
                  scan_mode=False):
        if over_budget():
            skipped.append(name)
            progress(f"skipping {name} (budget)")
            return
        t_vec, vec_res = _time_repeats(lambda: vec_fn(), repeats)
        t_oracle, _ = _time_repeats(build(_session(False), *args).collect,
                                    oracle_repeats)
        progress(f"{name}: baselines done (vec {t_vec:.2f}s, oracle "
                 f"{t_oracle:.2f}s)")
        modes = [("hot", True)] + ([("scan", False)] if scan_mode else [])
        for mode, cache in modes:
            df = build(_session(True, cache_batches=cache), *args)
            t_tpu, rows, ctr = _time_repeats(df.collect, repeats,
                                             counters=True)
            try:
                check(rows, vec_res)
            except AssertionError as ex:
                # a mismatch must never erase the rest of the record: log
                # the failure, skip the number, keep benchmarking
                progress(f"{name}_{mode} FAILED correctness: {ex}")
                skipped.append(f"{name}_{mode}:mismatch")
                failed.append(f"{name}_{mode}: mismatch: {ex}")
                continue
            progress(f"{name}_{mode}: tpu {t_tpu:.2f}s "
                     f"(programs={ctr['nProgramsLaunched']:.0f} "
                     f"syncs={ctr['nHostSyncs']:.0f} "
                     f"d2h={ctr['bytesD2H'] / 1e6:.1f}MB)")
            queries[f"{name}_{mode}"] = dict(
                tpu_s=t_tpu, cpu_vec_s=t_vec, cpu_oracle_s=t_oracle,
                rows_per_s=n / t_tpu, eff_gbps=bytes_ / t_tpu / 1e9,
                vs_vec=t_vec / t_tpu, vs_oracle=t_oracle / t_tpu,
                eventLog=_event_log_of(df), **ctr)
            stream()

    def check_qa(rows, want):
        got = {(int(r[0]), int(r[1])): int(r[2].scaleb(2)) for r in rows}
        assert got == want, "qa mismatch vs vectorized baseline"

    try:
        run_query("qa_join_agg", build_qa, (ss, dd),
                  lambda: cpu_qa_vectorized(ss, dd), check_qa,
                  _bytes_of({"a": ss["date_sk"], "b": ss["store_sk"],
                             "c": ss["ext_sales"]}, dd),
                  scan_mode=scan_variants)
    except TimeoutError:
        abort("qa_join_agg")
        return emitted["rc"]

    def check_qb(rows, want):
        got = {int(r[0]): int(r[1].scaleb(2)) for r in rows}
        assert got == want, "qb mismatch vs vectorized baseline"

    try:
        run_query("qb_left_join", build_qb, (ss, sr),
                  lambda: cpu_qb_vectorized(ss, sr), check_qb,
                  _bytes_of({"a": ss["ticket"], "b": ss["item_sk"],
                             "c": ss["store_sk"],
                             "d": ss["ext_sales"]}, sr))
    except TimeoutError:
        abort("qb_left_join")
        return emitted["rc"]

    def check_qc(rows, want):
        got = {(int(r[0]), int(r[1]), int(r[2].scaleb(2)), int(r[3]))
               for r in rows}
        assert got == want, "qc mismatch vs vectorized baseline"

    # qc runs BEFORE the parquet variant and rung-3
    try:
        run_query("qc_window", build_qc, (ss,),
                  lambda: cpu_qc_vectorized(ss), check_qc,
                  _bytes_of({"a": ss["store_sk"], "b": ss["date_sk"],
                             "c": ss["ext_sales"]}))
    except TimeoutError:
        abort("qc_window")
        return emitted["rc"]

    # ---- rung 3 (BASELINE.md): nested structs + decimal128 through the
    # OOC machinery under a constrained pool, with spill counters
    # --------------------------------------------------
    def run_rung3():
        from decimal import Decimal as _D

        import numpy as np

        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.memory.spill import (get_spill_framework,
                                                   reset_spill_framework)
        from spark_rapids_tpu.session import (TpuSession, col, lit, max_,
                                              min_, sum_)

        # 2M-row cap: rung-3 demonstrates the spill machinery under a
        # 64MiB pool (needs >64MiB live batches, ~36B/row), not scale —
        # at 20M+ the OOC host round-trips would eat the whole budget
        n3 = int(os.environ.get("BENCH_RUNG3_ROWS",
                                min(max(n, 2_000_000), 2_000_000)))
        rng = np.random.default_rng(11)
        k = rng.integers(0, 1000, n3).astype(np.int32)
        amt = rng.integers(-10**12, 10**12, n3)   # DECIMAL(25,4) unscaled
        qty = rng.integers(1, 100, n3).astype(np.int32)
        sa = rng.integers(0, 10**6, n3)
        sb = rng.integers(-500, 500, n3).astype(np.int32)

        def build(s):
            from spark_rapids_tpu.columnar.column import HostColumn
            from spark_rapids_tpu.expr.complextypes import GetStructField
            from spark_rapids_tpu.plan.nodes import LocalTableScan
            from spark_rapids_tpu.session import DataFrame

            dec = T.DecimalType(25, 4)
            struct_t = T.StructType([T.StructField("a", T.LONG, False),
                                     T.StructField("b", T.INT, False)])
            host = [
                HostColumn.from_numpy(k, T.INT),
                HostColumn(dec,
                           np.ones(n3, np.bool_),
                           data=np.stack([np.where(amt < 0, -1, 0),
                                          amt], axis=1).astype(np.int64)),
                HostColumn.from_numpy(qty, T.INT),
                HostColumn(struct_t, np.ones(n3, np.bool_), children=[
                    HostColumn.from_numpy(sa, T.LONG),
                    HostColumn.from_numpy(sb, T.INT)]),
            ]
            schema = T.StructType([
                T.StructField("k", T.INT, False),
                T.StructField("amt", dec, False),
                T.StructField("qty", T.INT, False),
                T.StructField("s", struct_t, False)])
            df = DataFrame(LocalTableScan(host, schema), s)
            return (df.filter(col("qty") > lit(5))
                    .select(col("k"), col("amt"),
                            GetStructField(col("s"), "a").alias("sa"))
                    .group_by("k")
                    .agg(sum_("amt", "sum_amt"), min_("amt", "lo"),
                         max_("amt", "hi"), sum_("sa", "ssa")))

        # constrain the pool so the OOC path must spill
        reset_spill_framework()
        from spark_rapids_tpu.config import TpuConf

        conf = {"spark.rapids.sql.enabled": True,
                "spark.rapids.memory.gpu.allocFraction": 0.0001,
                "spark.rapids.sql.batchSizeBytes": 8 << 20,
                "spark.rapids.sql.reader.batchSizeRows": max(n3 // 8, 1),
                **_NO_CPU_FALLBACK, **_diag_conf(), **_profile_conf()}
        fw = get_spill_framework(TpuConf(conf))
        s = TpuSession(conf)
        df3 = build(s)
        t_tpu, rows, ctr = _time_repeats(df3.collect, repeats,
                                         counters=True)
        oracle_rows = build(_session(False)).collect()
        assert sorted(rows) == sorted(oracle_rows), "rung3 mismatch"

        # OOC evidence: a global sort of the full table under the 64MiB
        # pool — TpuSortExec tracks its sorted runs as spillables, so the
        # pool budget forces device->host spills (SURVEY.md §5.7)
        def build_sort(sess):
            from spark_rapids_tpu.columnar.column import HostColumn
            from spark_rapids_tpu.plan.nodes import LocalTableScan
            from spark_rapids_tpu.session import DataFrame

            dec = T.DecimalType(25, 4)
            schema = T.StructType([
                T.StructField("k", T.INT, False),
                T.StructField("amt", dec, False),
                T.StructField("sa", T.LONG, False),
                T.StructField("sa2", T.LONG, False),
                T.StructField("sa3", T.LONG, False)])
            # CHUNKED input (a union of scans): the out-of-core sort only
            # forms spillable runs from a multi-batch stream; the payload
            # columns push the tracked runs past the 64MiB pool floor so
            # the spill path must engage
            nchunk = 8
            step = -(-n3 // nchunk)
            df = None
            for c0 in range(0, n3, step):
                sl = slice(c0, min(c0 + step, n3))
                m = sl.stop - sl.start
                host = [HostColumn.from_numpy(k[sl], T.INT),
                        HostColumn(dec, np.ones(m, np.bool_),
                                   data=np.stack(
                                       [np.where(amt[sl] < 0, -1, 0),
                                        amt[sl]],
                                       axis=1).astype(np.int64)),
                        HostColumn.from_numpy(sa[sl], T.LONG),
                        HostColumn.from_numpy(sa[sl] * 2, T.LONG),
                        HostColumn.from_numpy(sa[sl] + 7, T.LONG)]
                part = DataFrame(LocalTableScan(host, schema), sess)
                df = part if df is None else df.union(part)
            return df.order_by(col("amt"))

        t_sort, nrows_sorted = _time_repeats(build_sort(s).count, repeats)
        assert nrows_sorted == n3
        queries["rung3_dec128_nested"] = dict(
            tpu_s=t_tpu, cpu_vec_s=0.0, cpu_oracle_s=0.0,
            rows_per_s=n3 / t_tpu, eff_gbps=0.0, vs_vec=1.0, vs_oracle=1.0,
            oocSort_s=t_sort, eventLog=_event_log_of(df3),
            poolBytes=float(fw.pool_bytes),
            spillToHostCount=float(fw.spill_to_host_count),
            spillToHostBytes=float(fw.spill_to_host_bytes),
            spillToDiskCount=float(fw.spill_to_disk_count),
            **ctr)
        stream()
        reset_spill_framework()
        progress(f"rung3: tpu {t_tpu:.2f}s pool={fw.pool_bytes >> 20}MiB "
                 f"spills={fw.spill_to_host_count} "
                 f"({fw.spill_to_host_bytes >> 20}MiB to host)")

    if os.environ.get("BENCH_RUNG3", "1") != "0" and not over_budget():
        try:
            run_rung3()
        except TimeoutError:
            abort("rung3")
            return emitted["rc"]
        except Exception as ex:   # rung-3 is additive: never lose rung 1-2
            progress(f"rung3 failed: {ex!r}")
            failed.append(f"rung3: {ex!r}")

    # ---- rung3_ooc (ISSUE 10): hash-join + aggregation whose input
    # exceeds a shrunken HBM pool by >= 10x, streamed through the
    # size-aware partitioned exchange + spill-backed queues ----------------
    def run_rung3_ooc():
        import numpy as np

        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.memory.device_manager import (
            reset_device_manager,
        )
        from spark_rapids_tpu.memory.spill import (get_spill_framework,
                                                   reset_spill_framework)
        from spark_rapids_tpu.session import TpuSession, sum_

        pool = int(os.environ.get("BENCH_OOC_POOL_BYTES", 8 << 20))
        # fact rows sized so flat bytes (int32 + 2x int64 = 20B/row)
        # put the working set >= 10x the pool
        n_fact = int(os.environ.get("BENCH_OOC_ROWS",
                                    max((10 * pool) // 20, 1 << 20)))
        n_dim = 5000
        rng = np.random.default_rng(23)
        fk = rng.integers(0, n_dim, n_fact).astype(np.int32)
        fv = rng.integers(-1000, 1000, n_fact)
        fpad = rng.integers(0, 1 << 30, n_fact)
        dk = np.arange(n_dim, dtype=np.int32)
        dg = (dk % 25).astype(np.int32)
        data_bytes = float(fk.nbytes + fv.nbytes + fpad.nbytes)

        conf = {
            "spark.rapids.sql.enabled": True,
            # cap the pool so the OOC machinery MUST engage
            "spark.rapids.tpu.test.deviceMemoryBytes": str(pool),
            "spark.rapids.sql.batchSizeBytes": max(pool // 8, 1 << 20),
            "spark.rapids.sql.reader.batchSizeRows": max(n_fact // 16, 1),
            # keep the shuffled join: broadcast/AQE elision would skip
            # the exchange machinery this rung exists to exercise
            "spark.sql.autoBroadcastJoinThreshold": "-1",
            "spark.sql.adaptive.enabled": False,
            **_NO_CPU_FALLBACK, **_diag_conf(), **_profile_conf(),
        }
        reset_spill_framework()
        try:
            reset_device_manager()
        except Exception:
            pass
        fw = get_spill_framework(TpuConf(conf))
        try:
            s = TpuSession(conf)

            def build(sess):
                fact = _df(sess, {"k": fk, "v": fv, "pad": fpad},
                           [T.INT, T.LONG, T.LONG])
                dim = _df(sess, {"k": dk, "g": dg}, [T.INT, T.INT])
                return (fact.join(dim, on="k", how="inner")
                        .group_by("g").agg(sum_("v", "sv")))

            def cpu_ooc():
                sums = np.bincount(dg[fk], weights=fv.astype(np.float64),
                                   minlength=25)
                return {int(i): int(sums[i]) for i in range(25)
                        if sums[i]}

            t_vec, want = _time_repeats(cpu_ooc, repeats)
            df_ooc = build(s)
            t_tpu, rows, ctr = _time_repeats(df_ooc.collect, repeats,
                                             counters=True)
            # collect() rebuilds the framework singleton from the
            # session conf; the spill metrics live in the rebuilt one
            from spark_rapids_tpu.memory.spill import peek_spill_framework

            fw = peek_spill_framework() or fw
            got = {int(r[0]): int(r[1]) for r in rows if r[1]}
            assert got == want, "rung3_ooc mismatch vs vectorized CPU"
            queries["rung3_ooc"] = dict(
                tpu_s=t_tpu, cpu_vec_s=t_vec, cpu_oracle_s=0.0,
                rows_per_s=n_fact / t_tpu,
                eff_gbps=data_bytes / t_tpu / 1e9,
                vs_vec=t_vec / t_tpu, vs_oracle=0.0,
                eventLog=_event_log_of(df_ooc),
                poolBytes=float(pool), dataBytes=data_bytes,
                oocRatio=data_bytes / pool,
                spillToHostCount=float(fw.spill_to_host_count),
                spillToHostBytes=float(fw.spill_to_host_bytes),
                spillToDiskCount=float(fw.spill_to_disk_count),
                deviceUsedPeakBytes=float(fw.device_used_peak),
                **ctr)
            stream()
            progress(
                f"rung3_ooc: tpu {t_tpu:.2f}s over "
                f"{data_bytes / 1e6:.0f}MB vs {pool >> 20}MiB pool "
                f"({data_bytes / pool:.0f}x, "
                f"spills={fw.spill_to_host_count}, "
                f"hostBlocks={ctr['nExchangeHostBlocks']:.0f})")
        finally:
            # restore the real pool for the remaining rungs
            reset_spill_framework()
            try:
                reset_device_manager()
            except Exception:
                pass

    if os.environ.get("BENCH_RUNG3_OOC", "1") != "0" and not over_budget():
        try:
            run_rung3_ooc()
        except TimeoutError:
            abort("rung3_ooc")
            return emitted["rc"]
        except Exception as ex:   # additive: never lose rung 1-3
            progress(f"rung3_ooc failed: {ex!r}")
            failed.append(f"rung3_ooc: {ex!r}")
    # ---- rung4_dist (ISSUE 14): the 2-process distributed join rung —
    # the same hash-join + aggregation shape routed over worker
    # PROCESSES at ~100x a shrunken per-worker block store, with one
    # SIGKILL injected mid-shuffle (BENCH_DIST_KILL=0 disables).  The
    # deliverables are the wall, partitionsReplayed / workerLost, and a
    # loud wrong-answer/unrecovered-loss failure for bench_gate. -----------
    def run_rung4_dist():
        import numpy as np

        from spark_rapids_tpu import distributed as DIST
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.distributed import client as DIST_CLIENT
        from spark_rapids_tpu.session import TpuSession, sum_

        n_fact = int(os.environ.get("BENCH_DIST_ROWS", 200_000))
        # default store budget targets ~100x: serialized (compressed)
        # block traffic is ~2.5B/row/worker, so ~5B/row/100 per store
        worker_mem = int(os.environ.get("BENCH_DIST_WORKER_MEM",
                                        max((n_fact * 5) // 100, 4096)))
        kill_armed = os.environ.get("BENCH_DIST_KILL", "1") != "0"
        n_dim = 2000
        rng = np.random.default_rng(29)
        fk = rng.integers(0, n_dim, n_fact).astype(np.int32)
        fv = rng.integers(-1000, 1000, n_fact)
        dk = np.arange(n_dim, dtype=np.int32)
        dg = (dk % 31).astype(np.int32)
        data_bytes = float(fk.nbytes + fv.nbytes)

        conf = {
            "spark.rapids.sql.enabled": True,
            "spark.rapids.tpu.distributed.enabled": True,
            "spark.sql.autoBroadcastJoinThreshold": "-1",
            "spark.sql.adaptive.enabled": False,
            "spark.rapids.sql.batchSizeBytes": 256 << 10,
            "spark.rapids.sql.reader.batchSizeRows":
                max(n_fact // 16, 1),
            "spark.rapids.tpu.distributed.heartbeatMs": 100,
            "spark.rapids.tpu.distributed.workerLostMs": 600,
            "spark.rapids.tpu.distributed.opTimeoutMs": 1000,
            **_diag_conf(), **_profile_conf(),
        }

        def build(sess):
            fact = _df(sess, {"k": fk, "v": fv}, [T.INT, T.LONG])
            dim = _df(sess, {"k": dk, "g": dg}, [T.INT, T.INT])
            return (fact.join(dim, on="k", how="inner")
                    .group_by("g").agg(sum_("v", "sv")))

        def cpu_dist():
            sums = np.bincount(dg[fk], weights=fv.astype(np.float64),
                               minlength=31)
            return {int(i): int(sums[i]) for i in range(31) if sums[i]}

        DIST.reset_coordinator()
        coord = DIST.get_coordinator(TpuConf(conf))
        procs = {w: DIST.spawn_local_worker(coord, w,
                                            mem_bytes=worker_mem)
                 for w in ("bench0", "bench1")}
        try:
            if not coord.wait_for_workers(2, timeout_s=60):
                raise RuntimeError("rung4_dist: workers failed to join")
            t_vec, want = _time_repeats(cpu_dist, repeats)
            s = TpuSession(conf)
            df_dist = build(s)
            state = {"n": 0}

            def hook(exch, pid, seq):
                state["n"] += 1
                if kill_armed and state["n"] == 5 \
                        and procs["bench0"].poll() is None:
                    procs["bench0"].kill()

            from spark_rapids_tpu import perfcounters as PC

            # warm separately from the kill: the fault must land inside
            # the TIMED run so the recorded wall includes recovery
            snap = PC.snapshot()
            DIST_CLIENT.TEST_SHIP_HOOK = hook
            try:
                t0 = time.perf_counter()
                rows = df_dist.collect()
                t_tpu = time.perf_counter() - t0
            finally:
                DIST_CLIENT.TEST_SHIP_HOOK = None
            d = PC.since(snap)
            got = {int(r[0]): int(r[1]) for r in rows if r[1]}
            assert got == want, "rung4_dist WRONG ANSWER vs CPU"
            if kill_armed and not d["partitions_replayed"]:
                raise AssertionError(
                    "rung4_dist: kill armed but no partition was "
                    "re-driven — the loss went unrecovered or the rung "
                    "stopped exercising the distributed path")
            # cluster-observability overhead A/B (ISSUE 15): the same
            # distributed query timed with trace propagation ON vs OFF
            # (no kill — survivors serve both), min of 2 runs per mode;
            # bench_gate pins the on/off delta <= 5%
            def timed_dist_collect():
                t0 = time.perf_counter()
                r2 = build(TpuSession(conf)).collect()
                dt = time.perf_counter() - t0
                assert {int(x[0]): int(x[1]) for x in r2
                        if x[1]} == want, "rung4_dist A/B WRONG ANSWER"
                return dt

            trace_on_s = trace_off_s = trace_overhead_pct = None
            if os.environ.get("BENCH_DIST_TRACE_AB", "1") != "0":
                prior_trace = coord.trace_enabled
                try:
                    coord.trace_enabled = True
                    trace_on_s = min(timed_dist_collect()
                                     for _ in range(2))
                    coord.trace_enabled = False
                    trace_off_s = min(timed_dist_collect()
                                      for _ in range(2))
                    if trace_off_s > 0:
                        trace_overhead_pct = (
                            (trace_on_s - trace_off_s)
                            * 100.0 / trace_off_s)
                finally:
                    coord.trace_enabled = prior_trace
            # hedged-fetch overhead A/B (ISSUE 20): the same healthy
            # distributed query with hedging ON vs OFF — 3 INTERLEAVED
            # rounds per mode (on/off alternating cancels slow drift;
            # min-of-3 approximates each mode's true floor, the 2% pin
            # is tighter than min-of-2 run noise at small sizes).  On a
            # healthy cluster the soft deadline races must all be won
            # by the remote fetch — bench_gate pins the on/off delta
            # <= 2% AND hedgesWon == 0 (a hedge that fires with no
            # straggler means the deadline estimate is broken; hedge-
            # off rounds cannot hedge, so the counter delta across the
            # whole block attributes to the hedge-on rounds)
            hedge_on_s = hedge_off_s = hedge_overhead_pct = None
            hedges_won_healthy = None
            if os.environ.get("BENCH_DIST_HEDGE_AB", "1") != "0":
                prior_hedge = coord.hedge_enabled
                try:
                    snap_h = PC.snapshot()
                    hedge_walls = {True: [], False: []}
                    for _ in range(3):
                        for mode in (True, False):
                            coord.hedge_enabled = mode
                            hedge_walls[mode].append(
                                timed_dist_collect())
                    hedges_won_healthy = PC.since(snap_h)["hedges_won"]
                    hedge_on_s = min(hedge_walls[True])
                    hedge_off_s = min(hedge_walls[False])
                    if hedge_off_s > 0:
                        hedge_overhead_pct = (
                            (hedge_on_s - hedge_off_s)
                            * 100.0 / hedge_off_s)
                finally:
                    coord.hedge_enabled = prior_hedge
            queries["rung4_dist"] = dict(
                tpu_s=t_tpu, cpu_vec_s=t_vec, cpu_oracle_s=0.0,
                rows_per_s=n_fact / t_tpu,
                eff_gbps=data_bytes / t_tpu / 1e9,
                vs_vec=t_vec / t_tpu, vs_oracle=0.0,
                eventLog=_event_log_of(df_dist),
                dataBytes=data_bytes, workerMemBytes=float(worker_mem),
                distRatio=d["dist_block_bytes"] / max(worker_mem, 1),
                killArmed=bool(kill_armed),
                workerLost=float(d["worker_lost"]),
                partitionsReplayed=float(d["partitions_replayed"]),
                distBlocksShipped=float(d["dist_blocks_shipped"]),
                distBlockBytes=float(d["dist_block_bytes"]),
                workersJoined=float(d["workers_joined"]),
                traceOnWall_s=trace_on_s, traceOffWall_s=trace_off_s,
                traceOverheadPct=trace_overhead_pct,
                hedgeOnWall_s=hedge_on_s, hedgeOffWall_s=hedge_off_s,
                hedgeOverheadPct=hedge_overhead_pct,
                hedgesWon=(None if hedges_won_healthy is None
                           else float(hedges_won_healthy)))
            stream()
            overhead_note = ("" if trace_overhead_pct is None else
                             f", trace overhead "
                             f"{trace_overhead_pct:+.1f}%")
            if hedge_overhead_pct is not None:
                overhead_note += (f", hedge overhead "
                                  f"{hedge_overhead_pct:+.1f}% "
                                  f"(won={hedges_won_healthy})")
            progress(
                f"rung4_dist: tpu {t_tpu:.2f}s over "
                f"{data_bytes / 1e6:.0f}MB vs {worker_mem >> 10}KiB/"
                f"worker stores "
                f"(kill={'armed' if kill_armed else 'off'}, "
                f"lost={d['worker_lost']:.0f}, "
                f"replayed={d['partitions_replayed']:.0f}"
                f"{overhead_note})")
        finally:
            for p in procs.values():
                try:
                    p.kill()
                    p.wait(timeout=10)
                except Exception:
                    pass
            DIST.reset_coordinator()

    if os.environ.get("BENCH_RUNG4_DIST", "1") != "0" \
            and not over_budget():
        try:
            run_rung4_dist()
        except TimeoutError:
            abort("rung4_dist")
            return emitted["rc"]
        except Exception as ex:   # additive: never lose rungs 1-3
            progress(f"rung4_dist failed: {ex!r}")
            failed.append(f"rung4_dist: {ex!r}")

    # ---- rung5_recovery (ISSUE 16): the crash-consistent recovery rung.
    # Two deliverables: (a) journalOverheadPct — the SAME hot-path query
    # (no materialized exchange) timed with the query journal on vs off,
    # min-of-repeats, bench_gate pins the delta <= 2%; (b) the kill-at-
    # 50% story — a checkpointing multi-stage query crashed right after
    # its FIRST durable stage commit, then resumed (the committed stage
    # is SERVED, stages_recovered >= 1) with the resume wall reported
    # next to a cold full re-run.  BENCH_RUNG5_RECOVERY=0 disables. -------
    def run_rung5_recovery():
        import shutil
        import tempfile

        import numpy as np

        from spark_rapids_tpu import perfcounters as PC
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.lifecycle import journal as JM
        from spark_rapids_tpu.session import TpuSession, sum_

        n_fact = int(os.environ.get("BENCH_REC_ROWS", 200_000))
        n_dim = 2000
        rng = np.random.default_rng(31)
        fk = rng.integers(0, n_dim, n_fact).astype(np.int32)
        fv = rng.integers(-1000, 1000, n_fact)
        dk = np.arange(n_dim, dtype=np.int32)
        dg = (dk % 31).astype(np.int32)
        data_bytes = float(fk.nbytes + fv.nbytes)
        root = tempfile.mkdtemp(prefix="srt_bench_rec_")

        def build(sess):
            fact = _df(sess, {"k": fk, "v": fv}, [T.INT, T.LONG])
            dim = _df(sess, {"k": dk, "g": dg}, [T.INT, T.INT])
            return (fact.join(dim, on="k", how="inner")
                    .group_by("g").agg(sum_("v", "sv")))

        def conf_of(rec_on, checkpointing=False):
            c = {"spark.rapids.sql.enabled": True,
                 **_diag_conf(), **_profile_conf()}
            if rec_on:
                c.update({"spark.rapids.tpu.recovery.enabled": True,
                          "spark.rapids.tpu.recovery.dir": root})
            if checkpointing:
                # real multi-partition exchanges on the single bench
                # device, so stage boundaries materialize and commit
                c.update({
                    "spark.rapids.tpu.shuffle.singleDeviceCoalesce":
                        False,
                    "spark.sql.shuffle.partitions": 8,
                    "spark.sql.autoBroadcastJoinThreshold": "-1",
                    "spark.sql.adaptive.enabled": False})
            return c

        def timed(conf):
            t0 = time.perf_counter()
            build(TpuSession(conf)).collect()
            return time.perf_counter() - t0

        try:
            # (a) journal overhead A/B on the hot path
            timed(conf_of(False))                 # warm the compiles
            off_s = min(timed(conf_of(False)) for _ in range(repeats))
            # warm the recovery-on path too: the first journaled query
            # pays one-time costs (module import, recovery-root mkdir,
            # WAL open + replay) that are startup, not per-query
            timed(conf_of(True))
            snap_ab = PC.snapshot()
            on_s = min(timed(conf_of(True)) for _ in range(repeats))
            d_ab = PC.since(snap_ab)
            overhead_pct = ((on_s - off_s) * 100.0 / off_s
                            if off_s > 0 else 0.0)

            # (b) cold wall, then crash-at-50% (right after the first of
            # the stage commits) and the resumed wall
            cold_s = timed(conf_of(True, checkpointing=True))

            class _Die(BaseException):
                # unswallowable like a real SIGKILL: the commit
                # protocol's `except Exception` must not eat it
                pass

            state = {"n": 0}

            def hook(kind, n):
                if kind == "ckpt":
                    state["n"] += 1
                    if state["n"] == 1:
                        raise _Die()

            orig_end = JM.journal_end
            JM.TEST_RECORD_HOOK = hook
            JM.journal_end = lambda *a, **k: None
            died = False
            try:
                try:
                    build(TpuSession(conf_of(True, checkpointing=True))
                          ).collect()
                except _Die:
                    died = True
            finally:
                JM.TEST_RECORD_HOOK = None
                JM.journal_end = orig_end
            if not died:
                raise RuntimeError(
                    "rung5_recovery: the mid-commit kill never fired — "
                    "the plan stopped materializing stage boundaries")
            JM.reset_journal()                    # the "restart"
            snap = PC.snapshot()
            t0 = time.perf_counter()
            build(TpuSession(conf_of(True, checkpointing=True))
                  ).collect()
            resume_s = time.perf_counter() - t0
            d = PC.since(snap)
            if not d["stages_recovered"]:
                raise AssertionError(
                    "rung5_recovery: the resumed run adopted no "
                    "committed stage — recovery re-executed everything")
            queries["rung5_recovery"] = dict(
                tpu_s=on_s, cpu_vec_s=0.0, cpu_oracle_s=0.0,
                rows_per_s=n_fact / on_s,
                eff_gbps=data_bytes / on_s / 1e9,
                vs_vec=0.0, vs_oracle=0.0, dataBytes=data_bytes,
                journalOnWall_s=on_s, journalOffWall_s=off_s,
                journalOverheadPct=overhead_pct,
                journalRecordsWritten=float(
                    d_ab["journal_records_written"]),
                coldWall_s=cold_s, resumeWall_s=resume_s,
                stagesRecovered=float(d["stages_recovered"]),
                queriesResumed=float(d["queries_resumed"]),
                recoveryDiscards=float(d["journal_recovery_discards"]))
            stream()
            progress(
                f"rung5_recovery: journal overhead {overhead_pct:+.2f}% "
                f"({off_s:.3f}s off / {on_s:.3f}s on), kill-at-50% "
                f"resume {resume_s:.3f}s vs cold {cold_s:.3f}s "
                f"({d['stages_recovered']:.0f} stages served)")
        finally:
            JM.reset_journal(purge=True)
            shutil.rmtree(root, ignore_errors=True)

    if os.environ.get("BENCH_RUNG5_RECOVERY", "1") != "0" \
            and not over_budget():
        try:
            run_rung5_recovery()
        except TimeoutError:
            abort("rung5_recovery")
            return emitted["rc"]
        except Exception as ex:   # additive: never lose rungs 1-4
            progress(f"rung5_recovery failed: {ex!r}")
            failed.append(f"rung5_recovery: {ex!r}")

    # ---- q6 over real snappy parquet files through the device decode path
    #.  Scan-inclusive by construction: every run re-reads, decodes
    # and uploads the pages; the counters tell the program/round-trip
    # story. -----------------------------------------------------------------
    def run_q6_parquet():
        import shutil
        import tempfile

        import pyarrow as pa
        import pyarrow.parquet as pq

        # 1M default: the page pipeline dispatches many small eager ops
        # per page, so the scan-inclusive decode is dispatch- not
        # bandwidth-bound; the counters are the deliverable
        n_pq = int(os.environ.get("BENCH_PARQUET_ROWS",
                                  min(n, 1_000_000)))
        li_pq = make_lineitem(n_pq)
        tmp = tempfile.mkdtemp(prefix="bench_q6_parquet_")
        try:
            tbl = pa.table({
                "l_extendedprice": li_pq["l_extendedprice"],
                "l_discount": li_pq["l_discount"],
                "l_quantity": li_pq["l_quantity"],
                "l_shipdate_days": li_pq["l_shipdate_days"],
            })
            nfiles = 4
            step = -(-n_pq // nfiles)
            paths = []
            for i in range(nfiles):
                p = os.path.join(tmp, f"part-{i}.parquet")
                pq.write_table(tbl.slice(i * step, step), p,
                               compression="snappy",
                               use_dictionary=True,
                               data_page_version="1.0")
                paths.append(p)
            file_bytes = float(sum(os.path.getsize(p) for p in paths))

            def pyarrow_q6():
                cols = pq.ParquetDataset(tmp).read().to_pydict()
                arrs = {k: np.asarray(v) for k, v in cols.items()}
                return cpu_q6_vectorized(arrs)

            t_vec, vec_res = _time_repeats(pyarrow_q6, 1)

            def build_q6_scan(session):
                from spark_rapids_tpu.session import col, lit, sum_

                df = session.read.parquet(*paths)
                return (df.filter(
                    (col("l_shipdate_days") >= lit(8766))
                    & (col("l_shipdate_days") < lit(9131))
                    & (col("l_discount") >= lit(5))
                    & (col("l_discount") <= lit(7))
                    & (col("l_quantity") < lit(2400)))
                    .select((col("l_extendedprice") * col("l_discount"))
                            .alias("revenue"))
                    .agg(sum_("revenue", "revenue")))

            from spark_rapids_tpu.session import TpuSession

            s = TpuSession({
                "spark.rapids.sql.enabled": True,
                "spark.rapids.sql.format.parquet.decode.device": True,
                "spark.rapids.sql.format.parquet.reader.type": "PERFILE",
                **_NO_CPU_FALLBACK, **_diag_conf(), **_profile_conf(),
            })
            df = build_q6_scan(s)
            t_tpu, rows, ctr = _time_repeats(df.collect, 1, counters=True)
            got = int(rows[0][0])
            assert got == vec_res, f"q6_parquet mismatch: {got} vs {vec_res}"
            progress(f"q6_parquet: tpu {t_tpu:.2f}s over "
                     f"{file_bytes / 1e6:.0f}MB snappy "
                     f"(programs={ctr['nProgramsLaunched']:.0f})")
            queries["q6_parquet"] = dict(
                tpu_s=t_tpu, cpu_vec_s=t_vec, cpu_oracle_s=0.0,
                rows_per_s=n_pq / t_tpu,
                eff_gbps=file_bytes / t_tpu / 1e9,
                vs_vec=t_vec / t_tpu, vs_oracle=0.0,
                fileBytes=file_bytes, eventLog=_event_log_of(df), **ctr)
            stream()
            # hot-table cache variant (ISSUE 6): same files, cache on —
            # the warm repeat skips read+decode+transfer entirely, so
            # nHotCacheHits > 0 and bytesH2D ~ 0 on the timed run
            if over_budget():
                skipped.append("q6_parquet_hot")
            else:
                s_hot = TpuSession({
                    "spark.rapids.sql.enabled": True,
                    "spark.rapids.sql.format.parquet.decode.device": True,
                    "spark.rapids.sql.format.parquet.reader.type":
                        "PERFILE",
                    "spark.rapids.tpu.scan.hotTableCache.enabled": True,
                    **_NO_CPU_FALLBACK, **_diag_conf(), **_profile_conf(),
                })
                df_hot = build_q6_scan(s_hot)
                t_hot2, rows_hot, ctr_hot2 = _time_repeats(
                    df_hot.collect, 1, counters=True)
                assert int(rows_hot[0][0]) == vec_res
                queries["q6_parquet_hot"] = dict(
                    tpu_s=t_hot2, cpu_vec_s=t_vec, cpu_oracle_s=0.0,
                    rows_per_s=n_pq / t_hot2,
                    eff_gbps=file_bytes / t_hot2 / 1e9,
                    vs_vec=t_vec / t_hot2, vs_oracle=0.0,
                    fileBytes=file_bytes, eventLog=_event_log_of(df_hot),
                    **ctr_hot2)
                s_hot.close(check_leaks=False)
                stream()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    if os.environ.get("BENCH_PARQUET", "1") != "0" and not over_budget():
        try:
            run_q6_parquet()
        except TimeoutError:
            abort("q6_parquet")
            return emitted["rc"]
        except Exception as ex:   # additive: never lose rung 1-2
            progress(f"q6_parquet failed: {ex!r}")
            failed.append(f"q6_parquet: {ex!r}")

    # progressOverhead (ISSUE 12 satellite): a small hot-aggregate A/B
    # right before the final emit — additive, never loses rung 1-2
    if os.environ.get("BENCH_PROGRESS_OVERHEAD", "1") != "0" \
            and not over_budget():
        try:
            progress_box.update(measure_progress_overhead())
            progress(
                f"progressOverhead: disabled "
                f"{progress_box['disabled_s']:.4f}s -> enabled "
                f"{progress_box['enabled_s']:.4f}s "
                f"({progress_box['overhead_pct']:+.1f}%)")
        except TimeoutError:
            abort("progress_overhead")
            return emitted["rc"]
        except Exception as ex:
            progress(f"progressOverhead failed: {ex!r}")
            failed.append(f"progressOverhead: {ex!r}")

    # accountingOverhead (ISSUE 18 satellite): the bill-charging tax on
    # the same hot aggregate, min-of-repeats A/B — additive as above
    if os.environ.get("BENCH_ACCOUNTING_OVERHEAD", "1") != "0" \
            and not over_budget():
        try:
            accounting_box.update(measure_accounting_overhead())
            progress(
                f"accountingOverhead: disabled "
                f"{accounting_box['disabled_s']:.4f}s -> enabled "
                f"{accounting_box['enabled_s']:.4f}s "
                f"({accounting_box['overhead_pct']:+.1f}%)")
        except TimeoutError:
            abort("accounting_overhead")
            return emitted["rc"]
        except Exception as ex:
            progress(f"accountingOverhead failed: {ex!r}")
            failed.append(f"accountingOverhead: {ex!r}")

    emit()
    return emitted["rc"]


if __name__ == "__main__":
    raise SystemExit(main())
