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

  * the unit of a host read is a run of whole parquet row groups
    (``_open_units``: one file is one unit for every other format, and
    for any file under the top rung of the row ladder), and read,
    to_columns and H2D each run on a thread of their own joined by
    queues of ``spark.rapids.tpu.scan.prefetch.depth`` units
    (``_prefetched``): unit N+2 is read while N+1 becomes host columns,
    N is uploaded and the query computes on N-1 —
    ``scan_units`` / ``scan_files_streamed``, ``bytes_h2d_overlapped`` /
    ``prefetch_stall_ns`` and the ``scan_prefetch`` diagnostics event
    expose it;
  * a device-resident hot-table cache
    (``spark.rapids.tpu.scan.hotTableCache.enabled``, io/hot_cache.py)
    lets a repeated query over an unchanged table skip the
    read+decode+transfer entirely (spill-integrated, dropped at session
    close).

Reader mode selection matches the reference:
  * PERFILE       — one file at a time, simple.
  * COALESCING    — many small files/row-groups stitched into one batch
                    before upload (fewer, larger HBM transfers).
  * MULTITHREADED — a host thread pool fetches/decodes units ahead while
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

import concurrent.futures as cf
import os
import queue
import threading
import time
from struct import error as struct_error
from typing import Iterator, List, Optional

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DEFAULT_ROW_BUCKETS, HostColumn
from spark_rapids_tpu.config import (
    BATCH_SIZE_BYTES,
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
    if filters is not None:
        import pyarrow.parquet as pq

        # filters need the dataset reader; partitioning=None keeps the
        # hive inference off for this single-file path too
        return pq.read_table(path, columns=columns, filters=filters,
                             partitioning=None)
    return _open_parquet(path, columns).read(columns=columns)


def _open_parquet(path: str, columns):
    """The file's footer, checked for ``columns``: a missing one raises
    the typed SchemaMismatch."""
    import pyarrow.parquet as pq

    pf = pq.ParquetFile(path)
    have = set(pf.schema_arrow.names)
    missing = [c for c in (columns or []) if c not in have]
    if missing:
        raise IOF.SchemaMismatch(
            path, f"columns {missing} not in file schema "
                  f"{sorted(have)[:8]}", "parquet")
    return pf


_END = object()     # the last item of a stage's queue


class _Failed:
    """What the scan's pipeline hands on in the place of units it could
    not make: the error, for the client's thread to raise or, where it
    is a file's read (``path``) and the confs tolerate it, to skip."""

    __slots__ = ("exc", "path")

    def __init__(self, exc: BaseException, path: Optional[str] = None):
        self.exc = exc
        self.path = path


def _put(q: "queue.Queue", item, stop: threading.Event) -> bool:
    """``item`` into the bounded ``q``; False once the scan is closed."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.05)
            return True
        except queue.Full:
            continue
    return False


def _taken(q: "queue.Queue", stop: threading.Event):
    """The items of ``q`` up to its end, or to the scan's being closed."""
    while not stop.is_set():
        try:
            item = q.get(timeout=0.05)
        except queue.Empty:
            continue
        if item is _END:
            return
        yield item


def _pump(items, fn, out: "queue.Queue", stop: threading.Event) -> None:
    """One stage's thread: ``fn`` of every item of ``items``, in order,
    into ``out``.  A ``_Failed`` passes through; an error of this stage
    becomes one and ends it (the client raises it and closes the scan)."""
    try:
        for item in items:
            if fn is not None and not isinstance(item, _Failed):
                try:
                    item = fn(item)
                # tpulint: disable=cancel-swallow (nothing is swallowed:
                # the client's thread raises what this hands it)
                except Exception as e:
                    item = _Failed(e)
            if not _put(out, item, stop) or (
                    isinstance(item, _Failed) and item.path is None):
                return
    # tpulint: disable=cancel-swallow (as above, for the reader itself)
    except Exception as e:
        _put(out, _Failed(e), stop)
    finally:
        _put(out, _END, stop)
        items.close()           # the reader's pool goes with its thread


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
        # a unit of the host reader: the engine's batch goal in rows of
        # this schema, within the reader's row cap and the row ladder (a
        # unit past its top rung would pad to the next power of two)
        from spark_rapids_tpu.exec.partition_sizing import row_width_bytes

        self.unit_rows = max(1, min(
            DEFAULT_ROW_BUCKETS[-1], self.max_rows,
            int(conf.get(BATCH_SIZE_BYTES)) // row_width_bytes(plan.output)))
        self._units: Optional[int] = None   # of the last run

    @property
    def output(self):
        return self.plan.output

    def describe(self):
        took = "" if self._units is None else f" units={self._units}"
        return (f"TpuFileSourceScan {self.plan.fmt} "
                f"{len(self.plan.paths)} files mode={self._mode()}{took}")

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
    def _read_whole(self, path: str):
        """One file (or hive directory) as one arrow table."""
        import pyarrow as pa

        names = self.plan.output.field_names()
        if os.path.isdir(path):
            # hive-partitioned directory: dataset read (partition
            # columns materialize from the directory names)
            import pyarrow.dataset as ds

            dset = ds.dataset(path, format=self.plan.fmt,
                              partitioning="hive",
                              exclude_invalid_files=True)
            return dset.to_table(columns=names)
        if self.plan.fmt == "parquet":
            return read_parquet_file(
                path, names,
                filters=_filters_to_arrow(self.plan.pushed_filters))
        if self.plan.fmt == "orc":
            import pyarrow.orc as paorc

            return paorc.ORCFile(path).read(columns=names)
        if self.plan.fmt in ("csv", "json"):
            # Spark-strict parse (PERMISSIVE/_corrupt_record etc.) —
            # io/text.py, shared with the CPU oracle
            from spark_rapids_tpu.io.text import (read_csv_spark,
                                                  read_json_spark)

            rd = (read_csv_spark if self.plan.fmt == "csv"
                  else read_json_spark)
            cols, _ = rd(path, self.plan.output, self.plan.options)
            return pa.table(
                {f.name: c.to_arrow()
                 for f, c in zip(self.plan.output.fields, cols)})
        if self.plan.fmt == "avro":
            from spark_rapids_tpu.io.avro import read_avro_columns

            cols, struct = read_avro_columns(path, self.plan.output)
            return pa.table(
                {f.name: c.to_arrow()
                 for f, c in zip(struct.fields, cols)})
        raise NotImplementedError(self.plan.fmt)

    def _row_group_runs(self, path: str):
        """(footer, runs of row-group ordinals) of a parquet file the
        host decoder reads with no pushed filter: consecutive whole row
        groups while a run stays within ``unit_rows`` (a larger row
        group is a run of its own).  None for what is read whole: every
        other format, a hive directory, the filtered dataset read."""
        if self.plan.fmt != "parquet" or os.path.isdir(path) \
                or _filters_to_arrow(self.plan.pushed_filters) is not None:
            return None
        md = _open_parquet(path, self.plan.output.field_names()).metadata
        runs, rows = [[]], 0    # a file of no row group is one empty run
        for g in range(md.num_row_groups):
            n = md.row_group(g).num_rows
            if runs[-1] and rows + n > self.unit_rows:
                runs.append([])
                rows = 0
            runs[-1].append(g)
            rows += n
        return md, runs

    def _open_units(self, path: str, file_index: int, mode: str):
        """The reads that make up one file, in file order, each giving
        an arrow table: its runs of row groups, or the file whole.
        Opening and every read stand under the I/O fault domain: the
        chaos ``file_corrupt`` hook fires here, once a file, and every
        escaping error is wrapped/annotated with the file path + reader
        mode."""
        def checked(read):
            with IOF.file_context(path, self.plan.fmt, mode), \
                    self.metric("bufferTime").timed(), \
                    PC.span("srt.scan.read"):
                return read()

        def opened():
            chaos.check_file_fault(self.node_name, file_index, path)
            return self._row_group_runs(path)

        cut = checked(opened)
        if cut is None:
            return [lambda: checked(lambda: self._read_whole(path))]
        md, runs = cut
        if len(runs) > 1:
            PC.bump("scan_files_streamed")
        import pyarrow.parquet as pq

        cols = self.plan.output.field_names()
        # a handle a unit over the one parsed footer: no reader state is
        # shared between the threads of the MULTITHREADED pool
        return [lambda run=run: checked(
            lambda: pq.ParquetFile(path, metadata=md).read_row_groups(
                run, columns=cols)) for run in runs]

    def _host_tables(self, files, mode: str, tol: IOF.ScanTolerance, work):
        """``(table, path)`` of every host unit of ``files`` in scan
        order, and a ``_Failed`` in the place of a file that could not
        be read.  Runs on the read stage's thread; ``work(fn, *args)``
        calls ``fn`` for the owning query.  MULTITHREADED opens the
        files and reads their units on its pool, all submitted at once."""
        # a tolerance on: a file is whole or absent in the output, so no
        # unit of it is released before its last unit has decoded
        hold = tol.ignore_corrupt or tol.ignore_missing
        pool = plans = None
        if mode == "MULTITHREADED":
            pool = cf.ThreadPoolExecutor(
                self.num_threads, thread_name_prefix="srt-scan-pool")

            def plan(path, i):
                return [pool.submit(work, unit)
                        for unit in self._open_units(path, i, mode)]

            plans = {i: pool.submit(work, plan, p, i) for i, p in files}

        def units_of(i, path):
            if pool is not None:
                return (fut.result() for fut in plans[i].result())
            return (work(unit)
                    for unit in work(self._open_units, path, i, mode))

        try:
            for i, path in files:
                try:
                    units = units_of(i, path)
                    for tbl in (list(units) if hold else units):
                        PC.bump("scan_units")
                        self._units += 1
                        yield tbl, path
                # tpulint: disable=cancel-swallow (the client's thread
                # raises or tolerates what this hands it)
                except Exception as e:
                    yield _Failed(e, path)
                    if not hold:
                        return
        finally:
            if pool is not None:
                pool.shutdown(wait=True, cancel_futures=True)

    def _batched(self, tables, stitch: bool):
        """The reader's units as the tables the scan uploads: under
        COALESCING (``stitch``) consecutive units, of one file or of
        several, are stitched into one table while it stays within
        ``unit_rows`` (its path is "" when it holds several files'); a
        table past ``reader.batchSizeRows`` is cut by ``_row_chunks``."""
        import pyarrow as pa

        acc, paths, rows = [], set(), 0

        def flush():
            nonlocal acc, paths, rows
            if acc:
                tbl = acc[0] if len(acc) == 1 else pa.concat_tables(acc)
                one = paths.pop() if len(paths) == 1 else ""
                acc, paths, rows = [], set(), 0
                for chunk in self._row_chunks(tbl):
                    yield chunk, one

        try:
            for item in tables:
                if isinstance(item, _Failed):
                    yield from flush()
                    yield item
                    continue
                tbl, path = item
                if not stitch or rows + tbl.num_rows > self.unit_rows:
                    yield from flush()
                acc.append(tbl)
                paths.add(path)
                rows += tbl.num_rows
            yield from flush()
        finally:
            tables.close()

    def _table_to_host_cols(self, tbl) -> List[HostColumn]:
        with self.metric("gpuDecodeTime").timed(), \
                PC.span("srt.scan.to_columns"):
            return [HostColumn.from_arrow(tbl.column(f.name), f.dataType)
                    for f in self.plan.output.fields]

    def _to_device(self, cols: List[HostColumn]) -> ColumnarBatch:
        # transfer-wall attribution (ISSUE 6 satellite): the pad and the
        # device_put only — the arrow->HostColumn conversion is host
        # decode, not link time ("gpuDecodeTime": name kept for parity)
        with self.metric("gpuDecodeTime").timed(), \
                PC.span("srt.scan.h2d", feeds="scan_transfer_ns"):
            return ColumnarBatch.from_host_columns(
                cols, self.plan.output.field_names())

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
        (stamping/caching happen in execute_columnar).  A file the
        device decoder takes is one batch; the others go through the
        host pipeline (``_prefetched``): PERFILE file by file in scan
        order, COALESCING and MULTITHREADED all of them behind the
        device-decoded ones (device decode is a single-threaded device
        pipeline; host reads keep the stage threads and the pool)."""
        self._units = 0
        host_files = []
        for i, p in enumerate(self.plan.paths):
            dev = self._try_device_decode(p, i, dev_blocked)
            if dev is not None:
                yield dev, p
            elif mode == "PERFILE":
                yield from self._prefetched([(i, p)], mode, tol, note_skip)
            else:
                host_files.append((i, p))
        if host_files:
            yield from self._prefetched(host_files, mode, tol, note_skip)

    # -- the host pipeline: read | to_columns | h2d (ISSUE 6, 32) --------
    def _prefetched(self, files, mode: str, tol: IOF.ScanTolerance,
                    note_skip):
        """The host units of ``files`` as device batches.  Three stage
        threads — ``srt.scan.read`` (a unit's read), ``srt.scan.to_columns``
        and ``srt.scan.h2d`` — joined by queues of ``prefetch.depth``
        units each, so a unit is read while its predecessors become host
        columns, cross the link and feed the query; the client's thread
        only takes finished batches (``srt.scan.prefetch_wait`` while it
        waits, polling the query's cooperative cancel) and settles a
        failed file (raise, or skip it whole under the tolerance confs).
        Depth 0 runs the stages one after the other on the client's
        thread.  Overlap efficiency lands in ``bytes_h2d_overlapped`` /
        ``prefetch_stall_ns`` and a ``scan_prefetch`` diagnostics event.
        Closing the generator (a limit, a cancel, an escaping fault)
        stops the threads: no further unit is read, none is left alive."""
        from spark_rapids_tpu.lifecycle import check_cancel

        def settle(failed: _Failed):
            if failed.path is None:
                raise failed.exc
            # handle_scan_error returns True (tolerated) or raises
            IOF.handle_scan_error(failed.exc, failed.path, self.plan.fmt,
                                  mode, tol, self.conf)
            self.metric("filesSkipped").add(1)
            note_skip()

        def tables(work):
            return self._batched(self._host_tables(files, mode, tol, work),
                                 stitch=mode == "COALESCING")

        depth = int(self.conf.get(SCAN_PREFETCH_DEPTH))
        if depth <= 0:
            for item in tables(lambda fn, *a: fn(*a)):
                check_cancel()
                if isinstance(item, _Failed):
                    settle(item)
                    continue
                tbl, p = item
                yield self._to_device(self._table_to_host_cols(tbl)), p
            return
        from spark_rapids_tpu.diagnostics import context as DIAG_CTX
        from spark_rapids_tpu.governor import context as _GOV
        from spark_rapids_tpu.lifecycle.context import current as _cur
        from spark_rapids_tpu.progress import context as PROG_CTX

        stats = {"batches": 0, "overlapped_bytes": 0, "stall_ns": 0}
        # progress attribution (ISSUE 12): the owning query id is
        # captured HERE on the query thread — the stage threads have no
        # query contextvar of their own, and their read, decode and
        # upload wall must show up under this query, not nowhere
        _ctx = _cur()
        owner_qid = _ctx.query_id if _ctx is not None else None

        # the stage threads' spans are roots of their own: they carry
        # this thread's ids, captured here as well
        @PC.bind_owner
        def work(fn, *args):
            if PROG_CTX.TRACKER is None or owner_qid is None:
                return fn(*args)
            t0 = time.perf_counter_ns()
            out = fn(*args)
            PROG_CTX.TRACKER.add_background(
                owner_qid, "scan_prefetch",
                time.perf_counter_ns() - t0)
            return out

        stop = threading.Event()        # the scan is closed
        wanted = threading.Event()      # the client waits for a batch

        def to_columns(item):
            tbl, p = item
            return work(self._table_to_host_cols, tbl), p

        def upload(item):
            # overload governor (ISSUE 13): under YELLOW/RED no upload
            # runs ahead of the client — speculative uploads spend
            # exactly the HBM pressure needs back; a unit crosses the
            # link when the client waits for it
            gov = _GOV.GOVERNOR
            while gov is not None and gov.pause_background():
                # in this order: all this stage made is taken (the
                # client acknowledges after it clears ``wanted``), THEN
                # ``wanted`` is set, so it is a wait for this unit
                if stop.is_set() or (q_dev.unfinished_tasks == 0
                                     and wanted.wait(0.05)):
                    break
                stop.wait(0.005)
            cols, p = item
            return work(self._to_device, cols), p

        q_tbl, q_cols, q_dev = (queue.Queue(depth) for _ in range(3))
        stages = [
            ("srt-scan-read", tables(work), None, q_tbl),
            ("srt-scan-to-columns", _taken(q_tbl, stop), to_columns, q_cols),
            ("srt-scan-h2d", _taken(q_cols, stop), upload, q_dev)]
        threads = [threading.Thread(target=_pump, name=name, daemon=True,
                                    args=(items, fn, out, stop))
                   for name, items, fn, out in stages]
        try:
            for t in threads:
                t.start()
            while True:
                try:
                    item = q_dev.get_nowait()
                    overlapped = True
                except queue.Empty:
                    overlapped = False
                    wanted.set()
                    with PC.span("srt.scan.prefetch_wait",
                                 feeds="prefetch_stall_ns") as wait:
                        while True:
                            check_cancel()
                            try:
                                item = q_dev.get(timeout=0.05)
                                break
                            except queue.Empty:
                                continue
                    wanted.clear()
                    self.metric("prefetchStallTime").add(wait.ns)
                    stats["stall_ns"] += wait.ns
                # after the clear: a paused upload stage that finds all
                # it made taken and ``wanted`` set sees a NEW wait
                q_dev.task_done()
                if item is _END:
                    break
                if isinstance(item, _Failed):
                    settle(item)
                    continue
                b, p = item
                stats["batches"] += 1
                if overlapped:
                    nb = b.nbytes()
                    PC.bump("bytes_h2d_overlapped", nb)
                    self.metric("bytesH2DOverlapped").add(nb)
                    stats["overlapped_bytes"] += nb
                yield b, p
        finally:
            stop.set()
            for t in threads:
                t.join()
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
