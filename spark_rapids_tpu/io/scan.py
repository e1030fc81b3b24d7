"""TPU file scans — Parquet/CSV/JSON readers with the reference's 3 modes.

Reference analog (SURVEY.md §2.6): GpuParquetScan + GpuMultiFileReader with
PERFILE / COALESCING / MULTITHREADED reader types, host-side footer parsing
and row-group pruning with predicate pushdown, then device decode.

TPU adaptation: the host decode stage uses pyarrow (footer parse, row-group
pruning, predicate pushdown, dictionary/RLE decode) on background threads —
playing the role of the reference's host-side fetch+filter threads — and the
"device decode" step is the host->HBM upload into padded columns.  The
Pallas on-device Parquet decode (io/parquet_device.py) replaces that upload
with a COMPRESSED-page transfer where eligible (decompress + decode at HBM
bandwidth; ``spark.rapids.sql.format.parquet.transfer.compressed``),
mirroring how the reference moved decode from host to cuDF kernels.

Transport-aware pipeline (ISSUE 6):

  * an async double-buffered H2D prefetch ring
    (``spark.rapids.tpu.scan.prefetch.depth``) overlaps the upload of
    batch N+1 with query compute on batch N for the COALESCING and
    MULTITHREADED modes — ``bytes_h2d_overlapped`` / ``prefetch_stall_ns``
    and the ``scan_prefetch`` diagnostics event expose the overlap;
  * a device-resident hot-table cache
    (``spark.rapids.tpu.scan.hotTableCache.enabled``, io/hot_cache.py)
    lets a repeated query over an unchanged table skip the
    read+decode+transfer entirely (spill-integrated, dropped at session
    close).

Reader mode selection matches the reference:
  * PERFILE       — one file at a time, simple.
  * COALESCING    — many small files/row-groups stitched into one batch
                    before upload (fewer, larger HBM transfers).
  * MULTITHREADED — a host thread pool fetches/decodes files ahead while
    the device consumes (cloud-storage latency hiding).
  * AUTO          — MULTITHREADED for >1 file else COALESCING.

I/O fault domain (ISSUE 5, io/faults.py): every per-file read routes its
escaping errors through per-FILE classification — corrupt / truncated /
missing / schema-drifted files are skipped (with counters, an io_fault
event, and a quarantine-manifest entry) when the
``spark.sql.files.ignoreCorruptFiles`` / ``ignoreMissingFiles`` confs (or
their ``spark.rapids.tpu.files.*`` aliases) say so, and the COALESCING /
MULTITHREADED modes re-drive the surviving file set instead of aborting
the batch stitch.  A DEVICE-decode failure on one file retries that file
only on the native (host) decoder (``file_decoder_fallbacks``), and a
systematically-failing device decoder trips a per-format circuit-breaker
entry that routes the whole scan to the native decoder at plan time.
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import time
from struct import error as struct_error
from typing import Iterator, List, Optional

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import HostColumn
from spark_rapids_tpu.config import (
    MAX_READER_BATCH_SIZE_ROWS,
    PARQUET_DEVICE_DECODE,
    PARQUET_MULTITHREAD_READ_NUM_THREADS,
    PARQUET_READER_TYPE,
    SCAN_HOT_CACHE,
    SCAN_HOT_CACHE_MAX_BYTES,
    SCAN_PREFETCH_DEPTH,
    TpuConf,
)
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.io import faults as IOF
from spark_rapids_tpu.plan.nodes import FileSourceScan
from spark_rapids_tpu.resilience import faults as chaos


def _filters_to_arrow(pushed) -> Optional[list]:
    """Convert pushed-down predicates to pyarrow filter tuples (row-group
    pruning; ParquetFileFilterHandler analog).  Conservative: only simple
    col-op-literal comparisons are pushed; everything else is re-checked by
    the TpuFilterExec above the scan anyway."""
    from spark_rapids_tpu.expr import base as E
    from spark_rapids_tpu.expr import predicates as P

    out = []
    for f in pushed or []:
        try:
            op_map = {P.EqualTo: "==", P.LessThan: "<",
                      P.LessThanOrEqual: "<=", P.GreaterThan: ">",
                      P.GreaterThanOrEqual: ">="}
            op = op_map.get(type(f))
            if op is None:
                continue
            l, r = f.children
            if isinstance(l, E.AttributeReference) and isinstance(r, E.Literal):
                out.append((l.colname, op, r.value))
        # tpulint: disable=cancel-swallow (pure expression translation;
        # an untranslatable predicate is re-checked by the filter above)
        except Exception:
            continue
    return out or None


def read_parquet_file(path: str, columns, filters=None):
    """Single-FILE parquet read (shared with the CPU oracle and the MOR
    reader).  Without pushdown filters it bypasses pyarrow's dataset
    layer: dataset discovery infers hive partitioning from ``k=1/`` path
    segments and then fails to merge a partition column that ALSO exists
    in the file (the iceberg/delta identity-partition layout).  Missing
    columns raise a typed SchemaMismatch (ParquetFile.read would silently
    drop them)."""
    import pyarrow.parquet as pq

    if filters is not None:
        # filters need the dataset reader; partitioning=None keeps the
        # hive inference off for this single-file path too
        return pq.read_table(path, columns=columns, filters=filters,
                             partitioning=None)
    pf = pq.ParquetFile(path)
    have = set(pf.schema_arrow.names)
    missing = [c for c in (columns or []) if c not in have]
    if missing:
        raise IOF.SchemaMismatch(
            path, f"columns {missing} not in file schema "
                  f"{sorted(have)[:8]}", "parquet")
    return pf.read(columns=columns)


def _decode_breaker_key(fmt: str):
    """Per-FORMAT breaker key for the device decoder: a decoder that
    fails file after file (a systematic kernel/parser bug, not one bad
    file) should stop being tried at all — plan-time consult routes the
    format to the native decoder until the TTL re-probe."""
    return ("TpuFileSourceScanExec.deviceDecode", fmt)


class TpuFileSourceScanExec(TpuExec):
    # GpuFileSourceScanExec metric set (bufferTime/gpuDecodeTime), plus
    # the ISSUE 6 transport-pipeline signals surfaced per-operator so
    # explain("analyze") shows them as per-query deltas like the other
    # operator metrics (ISSUE 7 satellite): hot-cache hit/miss,
    # overlapped H2D bytes, prefetch stall wall, and per-chunk
    # compressed->decoded decode fallbacks
    EXTRA_METRICS = {"bufferTime": "MODERATE",
                     "gpuDecodeTime": "MODERATE",
                     "hotCacheHits": "MODERATE",
                     "hotCacheMisses": "MODERATE",
                     "bytesH2DOverlapped": "MODERATE",
                     "prefetchStallTime": "MODERATE",
                     "chunkDecodeFallbacks": "MODERATE"}

    def __init__(self, plan: FileSourceScan, conf: TpuConf):
        super().__init__([])
        self.plan = plan
        self.conf = conf
        self.reader_type = conf.get(PARQUET_READER_TYPE).upper()
        self.num_threads = conf.get(PARQUET_MULTITHREAD_READ_NUM_THREADS)
        self.max_rows = conf.get(MAX_READER_BATCH_SIZE_ROWS)

    @property
    def output(self):
        return self.plan.output

    def describe(self):
        return (f"TpuFileSourceScan {self.plan.fmt} "
                f"{len(self.plan.paths)} files mode={self._mode()}")

    def _mode(self) -> str:
        if self.reader_type != "AUTO":
            return self.reader_type
        return "MULTITHREADED" if len(self.plan.paths) > 1 else "COALESCING"

    # -- device decode (Pallas) -----------------------------------------
    def _device_decode_conf_on(self) -> bool:
        from spark_rapids_tpu.config import ORC_DEVICE_DECODE

        if self.plan.fmt == "parquet":
            return bool(self.conf.get(PARQUET_DEVICE_DECODE))
        if self.plan.fmt == "orc":
            return bool(self.conf.get(ORC_DEVICE_DECODE))
        return False

    def _decode_breaker_open(self) -> bool:
        """True when the per-format decode breaker holds this scan on the
        native decoder (the plan-time trip of a systematically-failing
        device decoder)."""
        from spark_rapids_tpu.config import RESILIENCE_BREAKER_TTL_SEC
        from spark_rapids_tpu.resilience.breaker import get_breaker

        breaker = get_breaker()
        if not breaker.has_entries():
            return False
        why = breaker.consult(
            _decode_breaker_key(self.plan.fmt),
            float(self.conf.get(RESILIENCE_BREAKER_TTL_SEC)))
        if why is not None:
            self._log_decode_fallback("(all files)",
                                      f"decode breaker: {why}")
            return True
        return False

    def _log_decode_fallback(self, path: str, why: str) -> None:
        from spark_rapids_tpu.config import DECODE_LOG_FALLBACK

        if self.conf.get(DECODE_LOG_FALLBACK):
            import sys

            print(f"[spark-rapids-tpu] device decode fallback for "
                  f"{path}: {why}", file=sys.stderr)

    def _try_device_decode(self, path: str, file_index: int = 0,
                           blocked: bool = False):
        """Pallas decode path; None -> retry THIS FILE on the native
        (host) decoder.  An error outside the expected unsupported-subset
        set counts as a decoder failure (``file_decoder_fallbacks``) and
        feeds the per-format decode breaker; it never escalates to the
        stage fault domain — the host decoder owns the file from here.
        Programming errors (Import/Attribute/Name/TypeError) re-raise.
        ``blocked`` is the per-SCAN breaker decision (consulted once in
        execute_columnar, not per file)."""
        import os

        if blocked or os.path.isdir(path):
            return None
        if not self._device_decode_conf_on():
            return None
        from spark_rapids_tpu import perfcounters as PC
        from spark_rapids_tpu.config import RESILIENCE_BREAKER_THRESHOLD
        from spark_rapids_tpu.io.parquet_native import _Unsupported
        from spark_rapids_tpu.io.parquet_device import read_parquet_device
        from spark_rapids_tpu.resilience import classify as CL
        from spark_rapids_tpu.resilience.breaker import get_breaker

        key = _decode_breaker_key(self.plan.fmt)
        # per-chunk compressed->decoded fallbacks happen inside
        # parquet_device without operator context; the counter delta
        # across this file's decode attributes them to this scan
        # (advisory under concurrent scans, like every TpuMetric)
        pre_chunk_falls = PC.COUNTERS.get("chunk_decode_fallbacks", 0)
        try:
            chaos.check_decode_fault(self.node_name, file_index)
            with self.metric("gpuDecodeTime").timed(), \
                    PC.span("srt.scan.device_decode"):
                if self.plan.fmt == "orc":
                    from spark_rapids_tpu.io.orc_device import (
                        read_orc_device)

                    out = read_orc_device(path, self.plan.output)
                else:
                    out = read_parquet_device(path, self.plan.output)
        except (_Unsupported, KeyError, ValueError, IndexError,
                struct_error) as ex:
            # the documented unsupported-subset fallback: expected,
            # silent, not a decoder failure
            self._log_decode_fallback(path, f"{type(ex).__name__}: {ex}")
            return None
        except (ImportError, AttributeError, NameError, TypeError):
            # a programming error in the decoder (a jax API that moved, a
            # wrong call) is not "a file outside the supported subset":
            # falling to the host decoder would answer from pyarrow with
            # rc 0 and hide that the device path is broken
            raise
        except Exception as ex:
            kind = CL.classify_failure(ex)
            if kind == CL.PROPAGATE:
                raise
            if kind in (CL.TRANSIENT, CL.DEVICE_OOM):
                # infrastructure pressure, not a decoder bug: the native
                # decoder still reads this file, but the event must not
                # feed the per-format breaker or misreport a
                # systematically-failing decoder
                self._log_decode_fallback(
                    path, f"{kind} during device decode "
                          f"({type(ex).__name__}: {ex}); using native "
                          f"decoder for this file")
                return None
            if IOF.to_scan_fault(ex, path, self.plan.fmt) is not None:
                # a vanished/corrupt/drifted FILE is not a decoder
                # failure: the host path re-derives the fault and the
                # tolerance confs own it — bad data must not indict the
                # decoder (or trip its breaker)
                return None
            PC.bump("file_decoder_fallbacks")
            self.metric("fileDecoderFallbacks").add(1)
            if get_breaker().record_failure(
                    key,
                    int(self.conf.get(RESILIENCE_BREAKER_THRESHOLD)),
                    reason=f"device decode: {type(ex).__name__}: {ex}"):
                PC.bump("breaker_trips")
            self._log_decode_fallback(
                path, f"decoder FAILURE {type(ex).__name__}: {ex} "
                      f"(retrying on native decoder)")
            return None
        falls = PC.COUNTERS.get("chunk_decode_fallbacks", 0) \
            - pre_chunk_falls
        if falls > 0:
            self.metric("chunkDecodeFallbacks").add(falls)
        if get_breaker().has_entries():
            get_breaker().record_success(key)
        return out

    # -- host decode ----------------------------------------------------
    def _read_file_host(self, path: str):
        import pyarrow as pa

        import os

        with self.metric("bufferTime").timed(), PC.span("srt.scan.read"):
            if os.path.isdir(path):
                # hive-partitioned directory: dataset read (partition
                # columns materialize from the directory names)
                import pyarrow.dataset as ds

                dset = ds.dataset(path, format=self.plan.fmt,
                                  partitioning="hive",
                                  exclude_invalid_files=True)
                tbl = dset.to_table(
                    columns=[f.name for f in self.plan.output.fields])
            elif self.plan.fmt == "parquet":
                cols = [f.name for f in self.plan.output.fields]
                tbl = read_parquet_file(
                    path, cols,
                    filters=_filters_to_arrow(self.plan.pushed_filters))
            elif self.plan.fmt == "orc":
                import pyarrow.orc as paorc

                tbl = paorc.ORCFile(path).read(
                    columns=[f.name for f in self.plan.output.fields])
            elif self.plan.fmt in ("csv", "json"):
                # Spark-strict parse (PERMISSIVE/_corrupt_record etc.) —
                # io/text.py, shared with the CPU oracle
                from spark_rapids_tpu.io.text import (read_csv_spark,
                                                      read_json_spark)

                rd = (read_csv_spark if self.plan.fmt == "csv"
                      else read_json_spark)
                cols, _ = rd(path, self.plan.output, self.plan.options)
                tbl = pa.table(
                    {f.name: c.to_arrow()
                     for f, c in zip(self.plan.output.fields, cols)})
            elif self.plan.fmt == "avro":
                from spark_rapids_tpu.io.avro import read_avro_columns

                cols, struct = read_avro_columns(path, self.plan.output)
                tbl = pa.table(
                    {f.name: c.to_arrow()
                     for f, c in zip(struct.fields, cols)})
            else:
                raise NotImplementedError(self.plan.fmt)
        return tbl

    def _read_host_checked(self, path: str, file_index: int, mode: str):
        """One per-file host read under the I/O fault domain: the chaos
        ``file_corrupt`` hook fires here, and every escaping error is
        wrapped/annotated with the file path + reader mode."""
        with IOF.file_context(path, self.plan.fmt, mode):
            chaos.check_file_fault(self.node_name, file_index, path)
            return self._read_file_host(path)

    def _table_or_skip(self, thunk, path: str, mode: str,
                       tol: IOF.ScanTolerance):
        """Run ``thunk`` (a per-file read, or a future's result) under
        the tolerate/skip contract: -> arrow table, or None when the
        file was tolerated away (counted, quarantined); raises the
        typed/annotated fault otherwise."""
        try:
            return thunk()
        except Exception as e:
            # handle_scan_error returns True (tolerated) or raises
            IOF.handle_scan_error(e, path, self.plan.fmt, mode, tol,
                                  self.conf)
            self.metric("filesSkipped").add(1)
            return None

    def _host_table_or_skip(self, path: str, file_index: int, mode: str,
                            tol: IOF.ScanTolerance):
        return self._table_or_skip(
            lambda: self._read_host_checked(path, file_index, mode),
            path, mode, tol)

    def _table_to_host_cols(self, tbl) -> List[HostColumn]:
        with PC.span("srt.scan.to_columns"):
            return [HostColumn.from_arrow(tbl.column(f.name), f.dataType)
                    for f in self.plan.output.fields]

    def _upload(self, tbl) -> ColumnarBatch:
        with self.metric("gpuDecodeTime").timed():  # name kept for parity
            cols = self._table_to_host_cols(tbl)
            names = self.plan.output.field_names()
            # transfer-wall attribution (ISSUE 6 satellite): time the
            # pad+device_put only — the arrow->HostColumn conversion
            # above is host decode, not link time
            with PC.span("srt.scan.h2d", feeds="scan_transfer_ns"):
                return ColumnarBatch.from_host_columns(cols, names)

    # -- modes ----------------------------------------------------------
    @staticmethod
    def _stamp(batch: ColumnarBatch, path: str) -> ColumnarBatch:
        """Record the source file on the batch and in the process-wide
        holder (InputFileName reads them — Spark's InputFileBlockHolder
        analog; pull execution processes each batch before the next
        yield, so the holder tracks the right file)."""
        from spark_rapids_tpu.expr.misc import CURRENT_INPUT_FILE

        batch.input_file = path
        CURRENT_INPUT_FILE[0] = path
        return batch

    # -- hot-table cache (ISSUE 6) --------------------------------------
    def _hot_cache_key(self) -> Optional[str]:
        from spark_rapids_tpu.io.hot_cache import HotTableCache

        return HotTableCache.scan_key(
            self.plan.fmt, self.plan.paths,
            [f.name for f in self.plan.output.fields],
            repr(_filters_to_arrow(self.plan.pushed_filters)),
            self.plan.options, self.max_rows)

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        mode = self._mode()
        tol = IOF.scan_tolerance(self.conf)
        # ONE breaker consult per scan (an open breaker would otherwise
        # be re-consulted and re-logged for every one of N files)
        dev_blocked = (self._device_decode_conf_on()
                       and self._decode_breaker_open())
        cache = key = None
        collected: Optional[list] = None
        cacheable = [True]
        if self.conf.get(SCAN_HOT_CACHE):
            from spark_rapids_tpu.io.hot_cache import get_hot_cache

            key = self._hot_cache_key()
            if key is not None:
                cache = get_hot_cache()
                hit = cache.get(key)
                if hit is not None:
                    PC.bump("hot_cache_hits")
                    self.metric("hotCacheHits").add(1)
                    for b, p in hit:
                        yield self._stamp(self._count_output(b), p)
                    return
                PC.bump("hot_cache_misses")
                self.metric("hotCacheMisses").add(1)
                collected = []

        def note_skip():
            # a tolerated-away file means this scan's output is a
            # conf-dependent SUBSET of the key's file set — never cache
            cacheable[0] = False

        for b, p in self._execute_scan(mode, tol, dev_blocked,
                                       note_skip):
            if collected is not None:
                collected.append((b, p))
            yield self._stamp(self._count_output(b), p)
        # reached ONLY on full completion: an abandoned generator
        # (limit) or an escaping fault must not publish partial output
        if cache is not None and collected is not None and cacheable[0]:
            cache.put(key, collected,
                      int(self.conf.get(SCAN_HOT_CACHE_MAX_BYTES)))

    def _execute_scan(self, mode: str, tol: IOF.ScanTolerance,
                      dev_blocked: bool, note_skip):
        """Per-mode read pipeline yielding (batch, source-path) pairs
        (stamping/caching happen in execute_columnar)."""
        if mode == "PERFILE":
            for i, p in enumerate(self.plan.paths):
                dev = self._try_device_decode(p, i, dev_blocked)
                if dev is not None:
                    yield dev, p
                    continue
                tbl = self._host_table_or_skip(p, i, mode, tol)
                if tbl is None:
                    note_skip()
                    continue
                yield self._upload(tbl), p
        elif mode == "COALESCING":
            import pyarrow as pa

            host_paths = []
            for i, p in enumerate(self.plan.paths):
                dev = self._try_device_decode(p, i, dev_blocked)
                if dev is not None:
                    yield dev, p
                else:
                    host_paths.append((i, p))
            # the batch stitch re-drives the SURVIVING file set: a
            # tolerated-away file drops out of the concat instead of
            # aborting it
            tbls = []
            surviving = []
            for i, p in host_paths:
                tbl = self._host_table_or_skip(p, i, mode, tol)
                if tbl is not None:
                    tbls.append(tbl)
                    surviving.append(p)
                else:
                    note_skip()
            if not tbls:
                return
            tbl = pa.concat_tables(tbls)
            one = surviving[0] if len(surviving) == 1 else ""

            def jobs():
                for chunk in self._row_chunks(tbl):
                    yield (lambda ch=chunk: [(self._upload(ch), one)])

            yield from self._prefetched(jobs())
        else:  # MULTITHREADED
            with cf.ThreadPoolExecutor(self.num_threads) as pool:
                # device decode is a single-threaded device pipeline; host
                # fallbacks keep the thread pool
                host_futs = []  # (index, path, future) — dups preserved
                for i, p in enumerate(self.plan.paths):
                    dev = self._try_device_decode(p, i, dev_blocked)
                    if dev is not None:
                        yield dev, p
                    else:
                        host_futs.append(
                            (i, p,
                             pool.submit(
                                 PC.bind_owner(self._read_host_checked),
                                 p, i, mode)))

                def jobs():
                    for i, p, fut in host_futs:
                        # the pyarrow struct_error that named no file
                        # now does: the wrap happened on the pool
                        # thread, the tolerate/raise decision happens
                        # here.  ONE upload job per CHUNK — a per-file
                        # job would materialize whole files in HBM and
                        # defeat the bounded ring
                        tbl = self._table_or_skip(fut.result, p, mode,
                                                  tol)
                        if tbl is None:
                            note_skip()
                            continue
                        for chunk in self._row_chunks(tbl):
                            yield (lambda ch=chunk, pp=p:
                                   [(self._upload(ch), pp)])

                yield from self._prefetched(jobs())

    # -- async H2D prefetch ring (ISSUE 6) ------------------------------
    def _prefetched(self, jobs):
        """Bounded staging ring: run up to ``prefetch.depth`` upload
        jobs ahead on a staging thread so the transfer of batch N+1
        overlaps the query's compute on batch N.  Each job returns a
        list of (batch, path) pairs.  CancelToken-aware: the consumer
        wait polls the query's cooperative cancel; overlap efficiency
        lands in ``bytes_h2d_overlapped`` / ``prefetch_stall_ns`` and a
        ``scan_prefetch`` diagnostics event."""
        depth = int(self.conf.get(SCAN_PREFETCH_DEPTH))
        if depth <= 0:
            for job in jobs:
                yield from job()
            return
        from spark_rapids_tpu.diagnostics import context as DIAG_CTX
        from spark_rapids_tpu.lifecycle import check_cancel
        from spark_rapids_tpu.lifecycle.context import current as _cur
        from spark_rapids_tpu.progress import context as PROG_CTX

        stats = {"batches": 0, "overlapped_bytes": 0, "stall_ns": 0}
        ring: collections.deque = collections.deque()
        pool = cf.ThreadPoolExecutor(
            1, thread_name_prefix="srt-scan-prefetch")
        jobs_it = iter(jobs)
        # progress attribution (ISSUE 12): the owning query id is
        # captured HERE on the query thread — the staging thread has no
        # query contextvar of its own, and its decode+upload wall must
        # show up under this query, not nowhere
        _ctx = _cur()
        owner_qid = _ctx.query_id if _ctx is not None else None

        # the staging thread's spans are roots of their own: they carry
        # this thread's ids, captured here as well
        @PC.bind_owner
        def run_job(job):
            if PROG_CTX.TRACKER is None or owner_qid is None:
                return job()
            t0 = time.perf_counter_ns()
            out = job()
            PROG_CTX.TRACKER.add_background(
                owner_qid, "scan_prefetch",
                time.perf_counter_ns() - t0)
            return out

        from spark_rapids_tpu.governor import context as _GOV

        def fill():
            # overload governor (ISSUE 13): under YELLOW/RED the ring
            # stops running ahead — speculative uploads spend exactly
            # the HBM pressure needs back; in-flight jobs still drain
            # and remaining jobs run inline on the consumer thread
            gov = _GOV.GOVERNOR
            if gov is not None and gov.pause_background():
                return
            while len(ring) < depth:
                try:
                    job = next(jobs_it)
                except StopIteration:
                    return
                ring.append(pool.submit(run_job, job))

        try:
            fill()
            while True:
                if ring:
                    fut = ring.popleft()
                    fill()
                    overlapped = fut.done()
                    if not overlapped:
                        with PC.span("srt.scan.prefetch_wait",
                                     feeds="prefetch_stall_ns") as wait:
                            while True:
                                check_cancel()
                                try:
                                    items = fut.result(timeout=0.05)
                                    break
                                except cf.TimeoutError:
                                    continue
                        self.metric("prefetchStallTime").add(wait.ns)
                        stats["stall_ns"] += wait.ns
                    else:
                        items = fut.result()
                else:
                    # ring empty: either the governor paused run-ahead
                    # or every job is consumed — run the next inline
                    try:
                        job = next(jobs_it)
                    except StopIteration:
                        break
                    check_cancel()
                    overlapped = False
                    items = run_job(job)
                for b, p in items:
                    stats["batches"] += 1
                    if overlapped:
                        nb = b.nbytes()
                        PC.bump("bytes_h2d_overlapped", nb)
                        self.metric("bytesH2DOverlapped").add(nb)
                        stats["overlapped_bytes"] += nb
                    yield b, p
                fill()
        finally:
            for f in ring:
                f.cancel()
            pool.shutdown(wait=True)
            rec = DIAG_CTX.RECORDER
            if rec is not None:
                rec.scan_prefetch(depth, stats["batches"],
                                  stats["overlapped_bytes"],
                                  stats["stall_ns"])

    def _row_chunks(self, tbl):
        n = tbl.num_rows
        if n <= self.max_rows:
            yield tbl
            return
        start = 0
        while start < n:
            yield tbl.slice(start, self.max_rows)
            start += self.max_rows
