#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the attached TPU.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s`` from the process's start): the cell's
tables from ``--seed``, a ``TpuSession`` with the configuration's and the
traffic's conf, the frames by residency, the query, a fixed number of
warm-up collects.  Then the window: a closed loop of one client over
``DataFrame.collect()`` for ``--seconds``.  After it has closed: counters,
peak memory, the trace reduction (``--trace 1``), and the numpy reference
against the answer of every timed collect.  The last line of standard
output is the result; the numbers compared, each beside its limit, are
its last key and the last lines of standard error.

The run fails, printing no result, when jax finds no TPU or fewer chips
than the cell asks for."""
from __future__ import annotations

import time

_T0 = time.time()           # the process's start, before any other import

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SPAN = "collect"            # the host span around every traced collect


@dataclass
class Run:
    """What the per-layer readers read (layer_metrics/__init__.py)."""
    window: object
    counters: dict
    setup_counters: dict
    trace: dict | None
    memory_peak_bytes: int | None
    min_bytes: int | None
    peaks: dict | None


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def measure(manifest, cell, seed: int, seconds: float, trace: bool, *,
            t0: float, need_chip: bool = True, dump: str | None = None):
    """Set-up, window, comparison; returns the result line as a dict.
    ``need_chip=False`` is for the CPU rehearsal in the tests alone: the
    command has no option that waives the look for a chip."""
    from benchmark.harness import cell as C
    from benchmark.harness import check, stats
    from benchmark.harness.loop import closed_loop
    from benchmark.harness.peaks import peaks_of

    import jax

    if need_chip:
        C.require_chips(cell.chips)
    device = C.device_record()
    peaks = peaks_of(device["kind"]) if need_chip else None

    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.session import TpuSession

    data_dir = os.path.join(manifest.bench_dir, ".data", f"{cell.name}-{seed}")
    trace_dir = os.path.join(manifest.bench_dir, ".trace",
                             f"{cell.name}-{seed}")
    setup_snap = PC.snapshot()
    tables = C.make_tables(cell, seed)
    try:
        session = TpuSession(dict(cell.conf))
        frames = C.make_frames(cell, session, tables, data_dir)
        # one DataFrame a client: each plans and holds its own exec tree
        dfs = [cell.query.build(frames)
               for _ in range(cell.traffic["clients"])]
        _log(f"--- {cell.name} explain ---\n{dfs[0].explain()}")
        for df in dfs:
            for _ in range(cell.traffic["warmup_collects"]):
                df.collect()
            C.check_plan(cell, df)
        _log(f"--- {cell.name} executed plan ---\n"
             f"{dfs[0]._planned()[0].pretty()}")
        setup_counters = PC.since(setup_snap)
        # what set-up built lives as long as the process: the collector
        # need not walk it inside the window (measured, PR 25: without
        # the freeze p95 is 1.9 % higher and the rate 1 % lower)
        gc.collect()
        gc.freeze()
        try:
            span = None
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                span = lambda: jax.profiler.TraceAnnotation(SPAN)  # noqa: E731
            snap = PC.snapshot()
            setup_s = time.time() - t0
            cpu0 = time.thread_time()
            window, results = closed_loop(dfs, seconds, cell.fact_rows,
                                          span=span)
            client_cpu_s = time.thread_time() - cpu0
            counters = PC.since(snap)
            if trace:
                jax.profiler.stop_trace()
        finally:
            gc.unfreeze()
        peak = C.memory_peak_bytes()
        if window.collects == 0:
            raise RuntimeError(f"{cell.name}: no collect completed")
        summary = None
        if trace:
            from benchmark.harness.trace import summarize

            summary = summarize(trace_dir, SPAN)
            if summary is None and need_chip:
                raise RuntimeError("the trace holds no device operation")
        del df, dfs, frames, session
    finally:
        # parquet files and the trace are a run's own: nothing is kept
        shutil.rmtree(data_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the window has closed and the peak is read: now the plain reference
    want = cell.query.reference(tables)
    answers = [cell.query.answer(rows) for rows in results]
    fallbacks = sum(setup_counters[k] + counters[k]
                    for k in check.FALLBACK_COUNTERS)
    compared = check.compare(
        answers, want, failed=window.failed, fallbacks=fallbacks,
        compiles_in_window=counters["compiles"] + counters["aot_compiles"])

    run = Run(window=window, counters=counters, setup_counters=setup_counters,
              trace=summary, memory_peak_bytes=peak,
              min_bytes=cell.query.min_bytes(cell.table_rows), peaks=peaks)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {
                "value": stats.END_TO_END[m["name"]](window, setup_s),
                "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    result = {"correct": check.is_correct(compared),
              "attempted": window.attempted, "failed": window.failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result.update(workload=cell.name, seed=seed, collects=window.collects,
                  window_s=window.elapsed_s, setup_s=setup_s,
                  compared=compared)
    if dump:
        os.makedirs(dump, exist_ok=True)
        path = os.path.join(
            dump, f"{cell.name}-seed{seed}-trace{int(trace)}-{os.getpid()}.json")
        with open(path, "w") as f:
            json.dump({"result": result, "client_cpu_s": client_cpu_s,
                       "latencies_s": window.latencies_s.tolist(),
                       "counters": counters,
                       "setup_counters": setup_counters}, f)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", help="directory that gets every collect's "
                    "wall and the counters, written after the window has "
                    "closed (noise diagnosis)")
    args = ap.parse_args(argv)

    from benchmark.harness import check
    from benchmark.harness.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    result = measure(manifest, cell, args.seed, args.seconds,
                     bool(args.trace), t0=_T0, dump=args.dump)
    sys.stdout.flush()
    for line in check.lines(result["compared"]):
        _log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
