#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the query path runs on the chip.

One process drives ``TpuSession`` -> ``overrides/`` -> ``exec/`` -> XLA
programs on the attached TPU and checks every answer:

  q6_parquet    TPC-H Q6 over a parquet file written here (cold scan path)
  q6_hot        TPC-H Q6 over device-resident batches (scan cache on)
  qa_join_agg   broadcast join + group-by        (bench.build_qa)
  qb_left_join  shuffled left join + group-by    (bench.build_qb)
  qc_window     group-by + rank() over a window  (bench.build_qc)
  decode        a small uncompressed parquet scan with
                spark.rapids.sql.format.parquet.decode.device=true, so the
                Pallas bit-unpack kernel runs COMPILED (tpu_custom_call)

Q6 runs at 50 M rows; the rung-2 queries at 8,192 (cut from 20 M: a cold
run is compile-bound — see SMALL_ROWS).  Every query is compared with
bench.py's hand-vectorised numpy reference at full size and with the row
oracle (spark.rapids.sql.enabled=false) at 8,192 rows, is collected twice
(adaptive execs change strategy on the second run), must run the plan
shape its phase names (the execs are asserted and explain() is printed)
and must leave every fallback counter at 0 with the CPU stage fallback
switched off.  Every phase starts cold: its line carries its own compile
wall.  One JSON line per phase, then the verdict:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs ONLY the mesh path (spark.rapids.shuffle.mode=ICI) and
its single-device comparison.  Without an accelerator the script exits
non-zero with ``"ok": false``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

Q6_ROWS = 50_000_000       # BASELINE.md rung 1
# BASELINE.md's rung 2 is 20 M rows.  Cut (PR 23) to the 8,192-row bucket
# of columnar.column.DEFAULT_ROW_BUCKETS: the TPU compiler's time for a
# sort-bearing program has a cliff between that bucket and the next
# (bounded group-by, compiled for a described v5e: 10.81 s at 2^13 rows,
# 187 s at 2^16, 352 s at 2^22; PERF.md).  At 20 M rows one rung-2 query
# had not finished compiling in 24 minutes; at 65,536 the three queries
# hold ~26 minutes of compiles, which fit the 1200 s this script may take
# only when compiled concurrently.  Below the cliff a cold run compiles
# every phase in turn and still ends well inside the limit.
SMALL_ROWS = 8_192         # rung 2, decode, and Q6's row-oracle pass
# ISSUE 23 asked 20 M for the mesh path.  Its epoch program for 2^25 rows
# did not compile in 12 minutes for a described v5e:2x2 (PERF.md, PR 23)
# and a four-chip minute costs four: 1 M rows fill the 2^20-row bucket,
# whose eight programs compile in ~5 minutes of the chip host's time.
MESH_ROWS = 1_000_000

# a chip run in which any of these moved did not run (only) on the chip
FALLBACK_COUNTERS = (
    "runtime_fallbacks", "query_fallbacks", "breaker_plan_fallbacks",
    "advisor_plan_fallbacks", "file_decoder_fallbacks",
    "chunk_decode_fallbacks")

BASE_CONF = {
    "spark.rapids.sql.enabled": True,
    # a compile or lowering error must fail the smoke, not become a
    # CPU-oracle answer with rc 0
    "spark.rapids.tpu.resilience.runtimeFallbackEnabled": False,
}
ORACLE_CONF = {"spark.rapids.sql.enabled": False}


def require_tpu() -> None:
    import jax

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; jax.default_backend() is "
            f"{jax.default_backend()!r}")


def require_compiled_kernel(program_text: str) -> None:
    if "tpu_custom_call" not in program_text:
        raise AssertionError(
            "the bit-unpack program holds no tpu_custom_call: the Pallas "
            "kernel ran interpreted, not compiled")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_device_bytes():
    import jax

    stats = jax.local_devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


_T0 = time.perf_counter()


def _emit(record: dict) -> None:
    if "phase" in record:
        record["elapsed_s"] = round(time.perf_counter() - _T0, 1)
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def _same_value(a, b) -> bool:
    if isinstance(a, float) or isinstance(b, float):
        # the v5e emulates f64: ~1e-15 relative error per op
        return bool(np.isclose(a, b, rtol=1e-12, atol=0.0, equal_nan=True))
    return a == b


def assert_rows_equal(got, want, what: str) -> None:
    got, want = sorted(got, key=repr), sorted(want, key=repr)
    if len(got) != len(want):
        raise AssertionError(
            f"{what}: {len(got)} rows vs {len(want)} expected")
    for g, w in zip(got, want):
        if len(g) != len(w) or not all(map(_same_value, g, w)):
            raise AssertionError(f"{what}: row {g} vs expected {w}")


def _assert_device_only(df, counters: dict, what: str) -> None:
    """No fallback counter moved, and the executed plan is all-TPU."""
    from spark_rapids_tpu.exec.base import TpuExec

    moved = {k: counters[k] for k in FALLBACK_COUNTERS if counters[k]}
    if moved:
        raise AssertionError(f"{what}: fallback counters moved: {moved}")
    root, meta = df._planned()
    if not isinstance(root, TpuExec):
        raise AssertionError(f"{what}: plan root is not a TPU exec")
    not_on_tpu = meta.explain(only_fallback=True) if meta is not None else ""
    if not_on_tpu:
        raise AssertionError(
            f"{what}: plan has CPU-oracle nodes:\n{not_on_tpu}")


# ---------------------------------------------------------------------------
# one measured phase
# ---------------------------------------------------------------------------

def measure(name: str, rows: int, df, check) -> tuple:
    """Collect ``df`` twice on the device, check both results, hold the
    run to zero fallbacks.  Returns (record, first rows)."""
    from spark_rapids_tpu import perfcounters as PC

    print(f"--- {name} explain ---\n{df.explain()}", flush=True)
    snap = PC.snapshot()
    t0 = time.perf_counter()
    first = df.collect()
    t1 = time.perf_counter()
    second = df.collect()
    t2 = time.perf_counter()
    d = PC.since(snap)
    check(first)
    check(second)
    _assert_device_only(df, d, name)
    return {
        "phase": name, "rows": rows,
        "first_collect_s": t1 - t0, "second_collect_s": t2 - t1,
        "nProgramsLaunched": d["programs_launched"],
        "nHostSyncs": d["host_syncs"],
        "bytesH2D": d["bytes_h2d"], "bytesD2H": d["bytes_d2h"],
        # inline on the query thread + the background AOT pool
        "compileWall_s": (d["compile_wall_ns"]
                          + d["aot_compile_wall_ns"]) / 1e9,
        "nCompiles": d["compiles"] + d["aot_compiles"],
        "peakDeviceBytes": _peak_device_bytes(),
        **{k: d[k] for k in FALLBACK_COUNTERS},
    }, first


def _assert_plan_shape(name: str, root, expect, forbid) -> None:
    """The executed plan holds every exec class of ``expect`` and none of
    ``forbid``: a phase must run the shape it is named for."""
    for cls in expect:
        assert _find_exec(root, cls) is not None, \
            f"{name}: no {cls.__name__} in the executed plan\n{root.pretty()}"
    for cls in forbid:
        assert _find_exec(root, cls) is None, \
            f"{name}: {cls.__name__} in the executed plan\n{root.pretty()}"


def run_phase(name: str, rows: int, build, check, *, conf=None, small=None,
              expect=(), forbid=(), after=None) -> None:
    """One measured one-chip phase, cold: two collects on the device,
    then the row oracle.  ``small`` is ``(build_small, n_small)`` when the
    phase's own data is more than the row oracle can take; without it the
    oracle runs ``build`` itself and is compared with the first collect.
    ``conf`` adds to BASE_CONF for the device sessions."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.session import TpuSession

    dev_conf = {**BASE_CONF, **(conf or {})}
    df = build(TpuSession(dict(dev_conf)))
    rec, dev_rows = measure(name, rows, df, check)
    root = df._planned()[0]
    # after the run: adaptive execs print what they decided
    print(f"--- {name} executed plan ---\n{root.pretty()}", flush=True)
    _assert_plan_shape(name, root, expect, forbid)

    oracle_build, n_oracle = small if small is not None else (build, rows)
    if small is not None:
        dev_df = oracle_build(TpuSession(dict(dev_conf)))
        snap = PC.snapshot()
        dev_rows = dev_df.collect()
        _assert_device_only(dev_df, PC.since(snap), f"{name}@{n_oracle}")
    assert_rows_equal(
        dev_rows, oracle_build(TpuSession(dict(ORACLE_CONF))).collect(),
        f"{name}@{n_oracle} vs row oracle")
    rec["oracleRows"] = n_oracle
    if after is not None:
        rec.update(after(df))
    _emit(rec)


# ---------------------------------------------------------------------------
# the queries (bench.py's generators, builders and numpy references)
# ---------------------------------------------------------------------------

def _q6_parquet_build(path):
    from spark_rapids_tpu.session import col, lit, sum_

    def build(session):
        df = session.read.parquet(path)
        return (df.filter(
            (col("l_shipdate_days") >= lit(8766))
            & (col("l_shipdate_days") < lit(9131))
            & (col("l_discount") >= lit(5))
            & (col("l_discount") <= lit(7))
            & (col("l_quantity") < lit(2400)))
            .select((col("l_extendedprice") * col("l_discount"))
                    .alias("revenue"))
            .agg(sum_("revenue", "revenue")))

    return build


def _write_parquet(path: str, cols: dict, **kw) -> None:
    import pyarrow as pa
    import pyarrow.parquet as pq

    pq.write_table(pa.table(cols), path, data_page_version="1.0", **kw)


def _phases_q6(n: int, n_small: int, tmp: str) -> None:
    import bench
    from spark_rapids_tpu.io.scan import TpuFileSourceScanExec

    li = bench.make_lineitem(n)
    want = bench.cpu_q6_vectorized(li)
    li_small = bench.make_lineitem(n_small)

    big, small = (os.path.join(tmp, f) for f in ("q6.parquet",
                                                 "q6_small.parquet"))
    _write_parquet(big, li, compression="snappy")
    _write_parquet(small, li_small, compression="snappy")

    def check_ints(rows):
        assert int(rows[0][0]) == want, f"Q6 {rows[0][0]} vs numpy {want}"

    run_phase("q6_parquet", n, _q6_parquet_build(big), check_ints,
              small=(_q6_parquet_build(small), n_small),
              expect=(TpuFileSourceScanExec,))

    def check_dec(rows):
        assert int(rows[0][0].scaleb(4)) == want, \
            f"Q6 {rows[0][0]} vs numpy {want}"

    def hot(data):
        def build(session):
            session.set_conf("spark.rapids.tpu.scan.cacheDeviceBatches",
                             True)
            return bench.build_q6(session, data)
        return build

    run_phase("q6_hot", n, hot(li), check_dec,
              small=(hot(li_small), n_small))


def _phases_rung2(n: int) -> None:
    """n is within the row oracle's reach: each query's oracle comparison
    runs on the very data of its measured phase."""
    import bench
    from spark_rapids_tpu.exec.exchange import (
        TpuBroadcastExchangeExec,
        TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.exec.fused import TpuWindowChainFusedExec
    from spark_rapids_tpu.exec.join import (
        TpuAdaptiveJoinExec,
        TpuBroadcastHashJoinExec,
    )

    ss = bench.make_store_sales(n)
    dd = bench.make_date_dim()
    sr = bench.make_store_returns(ss, n // 10)
    want_a = bench.cpu_qa_vectorized(ss, dd)
    want_b = bench.cpu_qb_vectorized(ss, sr)
    want_c = bench.cpu_qc_vectorized(ss)

    def check_qa(rows):
        got = {(int(r[0]), int(r[1])): int(r[2].scaleb(2)) for r in rows}
        assert got == want_a, "qa mismatch vs numpy reference"

    def check_qb(rows):
        got = {int(r[0]): int(r[1].scaleb(2)) for r in rows}
        assert got == want_b, "qb mismatch vs numpy reference"

    def check_qc(rows):
        got = {(int(r[0]), int(r[1]), int(r[2].scaleb(2)), int(r[3]))
               for r in rows}
        assert got == want_c, "qc mismatch vs numpy reference"

    run_phase("qa_join_agg", n, lambda s: bench.build_qa(s, ss, dd),
              check_qa, expect=(TpuBroadcastExchangeExec,),
              forbid=(TpuShuffleExchangeExec,))
    # at the ladder's 20 M rows store_returns (~40 MB) is past
    # spark.sql.autoBroadcastJoinThreshold and the planner shuffles both
    # sides; cut to n rows it would be broadcast.  Pin the shape the
    # phase is named for, as bench.py's out-of-core rung does.
    run_phase("qb_left_join", n, lambda s: bench.build_qb(s, ss, sr),
              check_qb,
              conf={"spark.sql.autoBroadcastJoinThreshold": "-1"},
              # the adaptive exec wraps the shuffled join;
              # _joined_shuffled checks what it decided at run time
              expect=(TpuShuffleExchangeExec, TpuAdaptiveJoinExec),
              forbid=(TpuBroadcastExchangeExec, TpuBroadcastHashJoinExec),
              after=_joined_shuffled)
    run_phase("qc_window", n, lambda s: bench.build_qc(s, ss), check_qc,
              expect=(TpuWindowChainFusedExec,))


def _joined_shuffled(df) -> dict:
    """qb's adaptive join must have kept the shuffled plan at run time."""
    from spark_rapids_tpu.exec.join import TpuAdaptiveJoinExec

    aj = _find_exec(df._planned()[0], TpuAdaptiveJoinExec)
    assert (aj.decision or "").startswith("shuffled"), \
        f"qb_left_join: the adaptive join decided {aj.decision!r}"
    return {"joinDecision": aj.decision}


def _find_exec(root, cls):
    if isinstance(root, cls):
        return root
    for c in getattr(root, "children", []):
        found = _find_exec(c, cls)
        if found is not None:
            return found
    return None


def _phase_decode(n: int, tmp: str) -> None:
    """Uncompressed dictionary-encoded integer columns: their index
    streams are bit-packed runs, which only the Pallas kernel unpacks."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.io.scan import TpuFileSourceScanExec
    from spark_rapids_tpu.pallas import decode as PD
    from spark_rapids_tpu.session import col, lit, sum_

    rng = np.random.default_rng(20260930)
    k = rng.integers(0, 31, n).astype(np.int32)        # 5-bit indices
    v = rng.integers(0, 1000, n).astype(np.int64)      # 10-bit indices
    path = os.path.join(tmp, "decode.parquet")
    _write_parquet(path, {"k": k, "v": v}, compression="NONE",
                   use_dictionary=True)
    keep = k > 5
    sums = np.bincount(k[keep], weights=v[keep].astype(np.float64),
                       minlength=31)
    want = {int(i): int(sums[i]) for i in np.nonzero(sums)[0]}

    def build(session):
        session.set_conf("spark.rapids.sql.format.parquet.decode.device",
                         True)
        df = session.read.parquet(path)
        return df.filter(col("k") > lit(5)).group_by("k").agg(
            sum_("v", "s"))

    def check(rows):
        got = {int(r[0]): int(r[1]) for r in rows}
        assert got == want, "decode-phase group sums vs numpy reference"

    def after(df):
        scan = _find_exec(df._planned()[0], TpuFileSourceScanExec)
        decode_ns = scan.metric("gpuDecodeTime").value
        assert decode_ns > 0, "the scan reports no device-decode time"
        assert PD._UNPACK_JITS, "the Pallas bit-unpack kernel never ran"
        # lower the very program the scan ran, as it ran it (x64 off:
        # Mosaic refuses i64 grid scalars)
        (tiles, bw), fn = next(iter(PD._UNPACK_JITS.items()))
        with jax.enable_x64(False):
            text = fn.lower(jax.ShapeDtypeStruct(
                (tiles * PD._TILE, PD._LANES), jnp.uint32)).as_text()
        require_compiled_kernel(text)
        return {"deviceDecode_s": decode_ns / 1e9,
                "unpackPrograms": sorted(PD._UNPACK_JITS)}

    run_phase("decode", n, build, check,
              expect=(TpuFileSourceScanExec,), after=after)


def run_single_chip(q6_rows: int, small_rows: int) -> None:
    """Every one-chip phase; raises on the first failure.  ``small_rows``
    (what the row oracle can take) sizes the rung-2 and decode phases and
    Q6's oracle pass."""
    from spark_rapids_tpu import native

    _emit({"phase": "setup", "device": device_info(),
           "nativeHostKernelsLoaded": native.get_lib() is not None,
           "compileCacheDir": _compile_cache_dir()})
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        _phases_q6(q6_rows, min(small_rows, q6_rows), tmp)
        _phases_rung2(small_rows)
        _phase_decode(small_rows, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _compile_cache_dir():
    import jax

    from spark_rapids_tpu.session import TpuSession

    TpuSession(dict(BASE_CONF))      # applies the one cache rule
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# --chips 4: the mesh path and what it is compared with, nothing else
# ---------------------------------------------------------------------------

MESH_CONF = {
    **BASE_CONF,
    "spark.rapids.shuffle.mode": "ICI",
    "spark.rapids.tpu.mesh.enabled": True,
}


def _record_shards(agg_exec, log: list) -> None:
    """Note, for every input the mesh exec shards, how many rows each
    device holds (from the arrays' own addressable_shards)."""
    inner = agg_exec._shard_batch

    def recording(batch):
        cols = inner(batch)
        for c in cols:
            arr = c.data if c.data is not None else c.validity
            log.append({s.device.id: int(s.data.shape[0])
                        for s in arr.addressable_shards})
        return cols

    agg_exec._shard_batch = recording


def run_mesh(n: int, n_devices: int) -> None:
    import bench
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exec.ici import TpuIciShuffleAggExec
    from spark_rapids_tpu.session import TpuSession, count_, sum_

    info = device_info()
    assert info["count"] == n_devices, \
        f"--chips {n_devices} but jax sees {info['count']} devices"
    _emit({"phase": "setup", "device": info})

    li = bench.make_lineitem(n)
    want_q6 = bench.cpu_q6_vectorized(li)
    rng = np.random.default_rng(20260930)
    gk = rng.integers(0, 37, n)
    gv = rng.integers(0, 1000, n)
    sums = np.bincount(gk, weights=gv.astype(np.float64), minlength=37)
    cnts = np.bincount(gk, minlength=37)
    want_g = {int(i): (int(sums[i]), int(cnts[i])) for i in range(37)
              if cnts[i]}

    def grouped(session):
        df = bench._df(session, {"k": gk, "v": gv}, [T.LONG, T.LONG])
        return df.group_by("k").agg(sum_("v", "s"), count_(None, "c"))

    def check_q6(rows):
        assert int(rows[0][0].scaleb(4)) == want_q6, \
            f"Q6 {rows[0][0]} vs numpy {want_q6}"

    def check_g(rows):
        got = {int(r[0]): (int(r[1]), int(r[2])) for r in rows}
        assert got == want_g, "grouped sum/count vs numpy reference"

    queries = (("q6", lambda s: bench.build_q6(s, li), check_q6),
               ("grouped_sum_count", grouped, check_g))
    for name, build, check in queries:
        results = {}
        for side, conf in (("mesh", MESH_CONF), ("single", BASE_CONF)):
            df = build(TpuSession(dict(conf)))
            root, _ = df._planned()
            ici = _find_exec(root, TpuIciShuffleAggExec)
            shards: list = []
            if side == "mesh":
                assert ici is not None, \
                    f"{name}: no TpuIciShuffleAggExec in\n{root.pretty()}"
                _record_shards(ici, shards)
            else:
                assert ici is None, f"{name}: mesh exec in the mesh-off plan"
            snap = PC.snapshot()
            rec, results[side] = measure(f"{name}_{side}", n, df, check)
            d = PC.since(snap)
            rec["iciEpochs"] = d["ici_epochs"]
            rec["iciRowsExchanged"] = d["ici_rows_exchanged"]
            if side == "mesh":
                assert shards, f"{name}: the mesh exec sharded no input"
                for per_dev in shards:
                    assert len(per_dev) == n_devices \
                        and min(per_dev.values()) > 0, \
                        f"{name}: rows not spread over {n_devices} " \
                        f"devices: {per_dev}"
                rec["inputsSharded"] = len(shards)
                rec["rowsPerDevice"] = shards[0]
            _emit(rec)
        assert_rows_equal(results["mesh"], results["single"],
                          f"{name}: mesh vs single-device")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ICI mesh path and its "
                         "single-device comparison")
    args = ap.parse_args(argv)
    device = None
    try:
        require_tpu()
        device = device_info()
        if args.chips == 4:
            run_mesh(MESH_ROWS, 4)
        else:
            run_single_chip(Q6_ROWS, SMALL_ROWS)
    except Exception as e:            # the verdict line must still print
        import traceback

        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", flush=True)
        _emit({"ok": False, "device": device})
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
