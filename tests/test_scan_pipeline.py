"""Transport-aware scan pipeline tests (ISSUE 6): compressed-page
device transfer, H2D prefetch overlap, and the device-resident
hot-table cache, plus the acceptance pins —

  (a) physical H2D bytes for a snappy parquet scan stay within the
      compressed file size + metadata slack,
  (b) a second scan of a cached hot table transfers ZERO bytes and
      leaks nothing at session close,
  (c) a prefetched multi-batch scan's wall beats the no-overlap
      transfer+compute sum.
"""
import os
import time

import numpy as np
import pytest

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, sum_

DEV_CONF = {"spark.rapids.sql.format.parquet.decode.device": "true"}


@pytest.fixture(autouse=True)
def _clean_hot_cache():
    from spark_rapids_tpu.io.hot_cache import clear_hot_cache

    clear_hot_cache()
    yield
    clear_hot_cache()


def _write_numeric(tmp_path, codec, dict_on, n=6000, name="t"):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(7)
    tbl = pa.table({
        "a": rng.integers(0, 40, n).astype(np.int64),
        "b": pa.array(np.where(rng.random(n) < 0.15, None,
                               rng.integers(-10**9, 10**9, n)),
                      type=pa.int32()),
        "c": rng.random(n),
        "d": rng.integers(0, 2, n).astype(bool),
    })
    p = str(tmp_path / f"{name}_{codec}_{dict_on}.parquet")
    pq.write_table(tbl, p, compression=codec, use_dictionary=dict_on,
                   data_page_version="1.0")
    return p, tbl


_NUM_SCHEMA = T.StructType([
    T.StructField("a", T.LONG, True), T.StructField("b", T.INT, True),
    T.StructField("c", T.DOUBLE, True),
    T.StructField("d", T.BooleanType(), True)])


# ---------------------------------------------------------------------------
# device-decode parity: encoding x compression matrix, bit-identical to
# the native pyarrow decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["NONE", "SNAPPY"])
@pytest.mark.parametrize("dict_on", [True, False])
def test_device_decode_matrix_parity(tmp_path, codec, dict_on):
    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    p, tbl = _write_numeric(tmp_path, codec, dict_on)
    batch = read_parquet_device(p, _NUM_SCHEMA)
    got = batch.to_pydict()
    want = tbl.to_pydict()
    for k in ("a", "b", "c", "d"):
        assert got[k] == want[k], f"{codec}/{dict_on}: column {k}"


@pytest.mark.parametrize("codec", ["NONE", "SNAPPY"])
def test_device_decode_strings_compressed(tmp_path, codec):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    rng = np.random.default_rng(9)
    n = 3000
    vals = [None if rng.random() < 0.1 else f"s{v}"
            for v in rng.integers(0, 60, n)]
    tbl = pa.table({"s": pa.array(vals, type=pa.string()),
                    "x": rng.integers(0, 50, n).astype(np.int64)})
    p = str(tmp_path / f"s_{codec}.parquet")
    pq.write_table(tbl, p, compression=codec, use_dictionary=True,
                   data_page_version="1.0")
    schema = T.StructType([T.StructField("s", T.STRING, True),
                           T.StructField("x", T.LONG, True)])
    got = read_parquet_device(p, schema).to_pydict()
    want = tbl.to_pydict()
    assert got["s"] == want["s"]
    assert got["x"] == want["x"]


def test_compressed_path_engages_and_counts(tmp_path):
    """Snappy pages route through the device decompressor; for
    compressible data the physical H2D stays under logical (the
    transport win is real, not just counted)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    rng = np.random.default_rng(8)
    n = 20480
    base = rng.integers(0, 10**6, 512)
    tbl = pa.table({"a": np.tile(base, n // 512),
                    "b": np.tile(base * 3 + 1, n // 512)})
    p = str(tmp_path / "comp.parquet")
    pq.write_table(tbl, p, compression="SNAPPY", use_dictionary=False,
                   data_page_version="1.0")
    schema = T.StructType([T.StructField("a", T.LONG, True),
                           T.StructField("b", T.LONG, True)])
    snap = PC.snapshot()
    batch = read_parquet_device(p, schema)
    d = PC.since(snap)
    assert batch.num_rows == n
    assert np.asarray(batch.columns[0].data)[:n].tolist() == \
        tbl.column("a").to_pylist()
    assert d["pages_device_decompressed"] > 0
    assert 0 < d["bytes_h2d"] < d["bytes_h2d_logical"]


def test_chunk_fallback_mid_file_no_win_chunk(tmp_path):
    """A snappy chunk with no transport win (incompressible REQUIRED
    column: compressed bytes >= what the decoded path ships) falls back
    PER CHUNK to the decoded-transfer path while its compressible
    neighbor keeps the compressed path; results stay bit-identical."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    rng = np.random.default_rng(11)
    n = 5120
    good = np.tile(rng.integers(0, 10**6, 512), n // 512)
    noise = rng.integers(-2**62, 2**62, n)
    pa_schema = pa.schema([pa.field("a", pa.int64(), nullable=False),
                           pa.field("z", pa.int64(), nullable=False)])
    tbl = pa.table({"a": good, "z": noise}, schema=pa_schema)
    p = str(tmp_path / "mixed.parquet")
    pq.write_table(tbl, p, compression="SNAPPY",
                   use_dictionary=False, data_page_version="1.0")
    schema = T.StructType([T.StructField("a", T.LONG, False),
                           T.StructField("z", T.LONG, False)])
    snap = PC.snapshot()
    got = read_parquet_device(p, schema).to_pydict()
    d = PC.since(snap)
    want = tbl.to_pydict()
    assert got["a"] == want["a"] and got["z"] == want["z"]
    assert d["chunk_decode_fallbacks"] >= 1       # the incompressible chunk
    assert d["pages_device_decompressed"] >= 1    # the compressible chunk


def test_plain_string_page_mid_chunk_falls_back(tmp_path):
    """Encoding flips to PLAIN byte_array mid-chunk (dict-overflow
    spill): the chunk leaves the compressed path but decodes
    correctly."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    rng = np.random.default_rng(3)
    n = 4000
    # low-cardinality head (dict page) + unique tail (forces pyarrow's
    # dictionary-overflow spill to PLAIN pages mid column chunk)
    vals = [f"k{v}" for v in rng.integers(0, 8, n // 2)] + [
        f"unique-{i}-{'x' * 40}" for i in range(n // 2)]
    tbl = pa.table({"s": pa.array(vals, type=pa.string())})
    p = str(tmp_path / "spill.parquet")
    pq.write_table(tbl, p, compression="SNAPPY", use_dictionary=True,
                   data_page_version="1.0", dictionary_pagesize_limit=4096)
    schema = T.StructType([T.StructField("s", T.STRING, True)])
    snap = PC.snapshot()
    got = read_parquet_device(p, schema).to_pydict()
    d = PC.since(snap)
    assert got["s"] == tbl.to_pydict()["s"]
    assert d["chunk_decode_fallbacks"] >= 1


# ---------------------------------------------------------------------------
# acceptance pin (a): H2D bytes <= compressed file size + metadata slack
# ---------------------------------------------------------------------------

def test_snappy_scan_h2d_bounded_by_file_size(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    rng = np.random.default_rng(5)
    n = 51200
    # compressible numerics (a repeating high-entropy block, the
    # dimension-table / sorted-run shape): snappy emits long
    # same-distance matches, so the compressed pages + op descriptors
    # are the SMALLEST representation and must beat decoded transfer
    base = rng.integers(-2**62, 2**62, 512)
    tbl = pa.table({
        "a": np.tile(base, n // 512),
        "b": np.tile(base ^ 0x5A5A, n // 512),
    })
    p = str(tmp_path / "pin.parquet")
    pq.write_table(tbl, p, compression="SNAPPY", use_dictionary=False,
                   data_page_version="1.0")
    fsize = os.path.getsize(p)
    schema = T.StructType([T.StructField("a", T.LONG, True),
                           T.StructField("b", T.LONG, True)])
    snap = PC.snapshot()
    batch = read_parquet_device(p, schema)
    d = PC.since(snap)
    assert batch.num_rows == n
    decoded = 2 * 8 * n
    slack = 64 * 1024
    assert d["bytes_h2d"] <= fsize + slack, \
        f"physical H2D {d['bytes_h2d']} vs file {fsize} (+{slack} slack)"
    # and the transfer is a genuine win over shipping decoded columns
    assert d["bytes_h2d"] < decoded
    assert d["bytes_h2d_logical"] >= decoded


# ---------------------------------------------------------------------------
# acceptance pin (b): hot-table cache -> second scan moves zero bytes,
# session close leaks nothing
# ---------------------------------------------------------------------------

def test_hot_cache_second_scan_zero_h2d_and_clean_close(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(17)
    n = 30000
    paths = []
    for i in range(2):
        tbl = pa.table({
            "k": rng.integers(0, 12, n // 2).astype(np.int64),
            "v": rng.integers(0, 10**6, n // 2).astype(np.int64)})
        p = str(tmp_path / f"hot-{i}.parquet")
        pq.write_table(tbl, p, compression="snappy")
        paths.append(p)
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.tpu.scan.hotTableCache.enabled": True})

    def q():
        return sorted(s.read.parquet(*paths).group_by("k")
                      .agg(sum_("v", "sv")).collect())

    r1 = q()
    snap = PC.snapshot()
    r2 = q()
    d = PC.since(snap)
    assert r1 == r2
    assert d["bytes_h2d"] == 0, \
        f"cached re-read moved {d['bytes_h2d']} H2D bytes"
    assert d["hot_cache_hits"] == 1
    # oracle differential
    so = TpuSession({"spark.rapids.sql.enabled": False})
    assert sorted(so.read.parquet(*paths).group_by("k")
                  .agg(sum_("v", "sv")).collect()) == r1
    # close drops the cache: no device buffers left, persistent or not
    leaks = s.close()
    assert leaks == []
    from spark_rapids_tpu.memory.spill import peek_spill_framework

    fw = peek_spill_framework()
    assert fw is None or fw.leak_report(include_persistent=True) == []


def test_hot_cache_invalidates_on_file_rewrite(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    p = str(tmp_path / "inv.parquet")
    pq.write_table(pa.table({"v": np.arange(100, dtype=np.int64)}), p)
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.tpu.scan.hotTableCache.enabled": True})

    def total():
        rows = s.read.parquet(p).agg(sum_("v", "sv")).collect()
        return int(rows[0][0])

    assert total() == 4950
    # rewrite with different data (and nudge mtime past fs granularity)
    pq.write_table(pa.table({"v": np.arange(200, dtype=np.int64)}), p)
    st = os.stat(p)
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns + 10_000_000))
    assert total() == 19900, "stale hot-cache entry served after rewrite"
    s.close()


def test_hot_cache_skipped_scan_not_cached(tmp_path):
    """A scan that tolerated away a corrupt file must not publish its
    subset output into the cache."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    paths = []
    for i in range(2):
        p = str(tmp_path / f"sk-{i}.parquet")
        pq.write_table(pa.table(
            {"v": np.arange(50, dtype=np.int64) + 100 * i}), p)
        paths.append(p)
    with open(paths[1], "r+b") as f:   # truncate -> corrupt
        f.truncate(10)
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.tpu.scan.hotTableCache.enabled": True,
                    "spark.sql.files.ignoreCorruptFiles": "true"})
    rows = s.read.parquet(*paths).collect()
    assert len(rows) == 50
    from spark_rapids_tpu.io.hot_cache import peek_hot_cache

    cache = peek_hot_cache()
    assert cache is None or cache.stats()["entries"] == 0
    s.close()


# ---------------------------------------------------------------------------
# acceptance pin (c): prefetch overlap beats sequential transfer+compute
# ---------------------------------------------------------------------------

def _scan_exec(paths, schema, prefetch_depth):
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.io.scan import TpuFileSourceScanExec
    from spark_rapids_tpu.plan.nodes import FileSourceScan

    conf = TpuConf({
        "spark.rapids.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.sql.reader.batchSizeRows": "256",
        "spark.rapids.tpu.scan.prefetch.depth": str(prefetch_depth),
    })
    return TpuFileSourceScanExec(
        FileSourceScan("parquet", paths, schema), conf)


def test_prefetch_overlap_beats_sequential(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = 1024   # 4 chunks of 256
    p = str(tmp_path / "pf.parquet")
    pq.write_table(pa.table({"v": np.arange(n, dtype=np.int64)}), p)
    schema = T.StructType([T.StructField("v", T.LONG, True)])
    # compute slightly heavier than transfer: the prefetch of batch
    # N+1 finishes strictly inside compute on batch N, so overlap
    # detection is deterministic, not a scheduler coin flip
    t_upload = 0.10
    t_compute = 0.16

    def run(depth):
        ex = _scan_exec([p], schema, depth)
        real_upload = ex._to_device

        def slow_upload(cols):
            time.sleep(t_upload)
            return real_upload(cols)

        ex._to_device = slow_upload
        rows = 0
        t0 = time.perf_counter()
        for batch in ex.execute_columnar():
            time.sleep(t_compute)   # the consumer's per-batch compute
            rows += batch.num_rows
        return time.perf_counter() - t0, rows

    seq_wall, seq_rows = run(0)
    snap = PC.snapshot()
    ov_wall, ov_rows = run(2)
    d = PC.since(snap)
    assert seq_rows == ov_rows == n
    # 4 x (0.12 + 0.12) sequential vs 0.12 + 4 x 0.12 overlapped: demand
    # a decisive margin, not a lucky scheduler tick
    assert ov_wall < seq_wall - 0.15, (ov_wall, seq_wall)
    assert d["bytes_h2d_overlapped"] > 0


def test_prefetch_emits_diagnostics_event(tmp_path):
    import json

    import pyarrow as pa
    import pyarrow.parquet as pq

    p = str(tmp_path / "ev.parquet")
    pq.write_table(pa.table({"v": np.arange(2048, dtype=np.int64)}), p)
    log_dir = str(tmp_path / "logs")
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.format.parquet.reader.type": "COALESCING",
        "spark.rapids.sql.reader.batchSizeRows": "512",
        "spark.rapids.tpu.diagnostics.enabled": True,
        "spark.rapids.tpu.diagnostics.eventLogDir": log_dir,
    })
    s.read.parquet(p).agg(sum_("v", "sv")).collect()
    events = []
    for fn in os.listdir(log_dir):
        if fn.endswith(".jsonl"):
            with open(os.path.join(log_dir, fn)) as f:
                events += [json.loads(line) for line in f]
    pf = [e for e in events if e["ev"] == "scan_prefetch"]
    assert pf, "no scan_prefetch event recorded"
    assert pf[0]["depth"] == 2 and pf[0]["batches"] >= 1


# ---------------------------------------------------------------------------
# ISSUE 32: a parquet file streams by runs of row groups through three
# stage threads (read | to_columns | h2d)
# ---------------------------------------------------------------------------

_Q6_SCHEMA = T.StructType([
    T.StructField("id", T.LONG, True), T.StructField("price", T.LONG, True),
    T.StructField("disc", T.LONG, True), T.StructField("qty", T.LONG, True),
    T.StructField("ship", T.INT, True)])


def _write_q6(tmp_path, name, n=2048, row_group=256, first_id=0):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(32 + first_id)
    tbl = pa.table({
        "id": np.arange(first_id, first_id + n, dtype=np.int64),
        "price": rng.integers(90_000, 10_500_000, n).astype(np.int64),
        "disc": rng.integers(0, 11, n).astype(np.int64),
        "qty": rng.integers(100, 5100, n).astype(np.int64),
        "ship": rng.integers(8400, 9500, n).astype(np.int32)})
    p = str(tmp_path / f"{name}.parquet")
    pq.write_table(tbl, p, row_group_size=row_group)
    return p


def _q6_shaped(session, paths):
    from spark_rapids_tpu.session import col, lit

    return (session.read.parquet(*paths)
            .filter((col("ship") >= lit(8766)) & (col("ship") < lit(9131))
                    & (col("disc") >= lit(5)) & (col("disc") <= lit(7))
                    & (col("qty") < lit(2400)))
            .select((col("price") * col("disc")).alias("revenue"))
            .agg(sum_("revenue", "revenue")).collect())


def _unit_exec(paths, mode="COALESCING", depth=2, rows=512, **conf):
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.io.scan import TpuFileSourceScanExec
    from spark_rapids_tpu.plan.nodes import FileSourceScan

    return TpuFileSourceScanExec(
        FileSourceScan("parquet", paths, _Q6_SCHEMA), TpuConf({
            "spark.rapids.sql.format.parquet.reader.type": mode,
            "spark.rapids.sql.reader.batchSizeRows": str(rows),
            "spark.rapids.tpu.scan.prefetch.depth": str(depth), **conf}))


@pytest.fixture
def counted_reads(monkeypatch):
    """Every ``ParquetFile.read_row_groups`` call as its list of row
    groups; ``calls.hook`` runs first in each (a sleep, a fault)."""
    import pyarrow.parquet as pq

    real = pq.ParquetFile.read_row_groups

    class Calls(list):
        hook = staticmethod(lambda groups: None)

    calls = Calls()

    def read_row_groups(self, row_groups, *a, **kw):
        calls.append(list(row_groups))
        calls.hook(list(row_groups))
        return real(self, row_groups, *a, **kw)

    monkeypatch.setattr(pq.ParquetFile, "read_row_groups", read_row_groups)
    return calls


def _scan_threads():
    import threading

    return [t.name for t in threading.enumerate()
            if t.name.startswith("srt-scan")]


@pytest.mark.parametrize("mode,files", [
    ("COALESCING", 1), ("PERFILE", 1), ("MULTITHREADED", 2)])
def test_a_file_streams_by_runs_of_row_groups(tmp_path, mode, files,
                                              counted_reads):
    paths = [_write_q6(tmp_path, f"rg{k}", first_id=2048 * k)
             for k in range(files)]
    ex = _unit_exec(paths, mode)
    snap = PC.snapshot()
    batches = list(ex.execute_columnar())
    d = PC.since(snap)
    # 8 row groups of 256 rows under 512 rows a unit: 4 units a file of
    # two row groups each, handed on in file order
    assert [b.num_rows for b in batches] == [512] * (4 * files)
    ids = np.concatenate([np.asarray(b.columns[0].data)[:512]
                          for b in batches])
    assert ids.tolist() == list(range(2048 * files))
    assert sorted(counted_reads) == sorted(
        [[0, 1], [2, 3], [4, 5], [6, 7]] * files)
    assert d["scan_units"] == 4 * files
    assert d["scan_files_streamed"] == files
    assert f"units={4 * files}" in ex.describe()
    assert _scan_threads() == []
    # the Q6-shaped answer over the units is the one-batch answer and
    # the CPU oracle's
    conf = {"spark.rapids.sql.enabled": True,
            "spark.rapids.sql.format.parquet.reader.type": mode}
    units = _q6_shaped(TpuSession({
        **conf, "spark.rapids.sql.reader.batchSizeRows": "512"}), paths)
    snap = PC.snapshot()
    whole = _q6_shaped(TpuSession(conf), paths)
    assert PC.since(snap)["scan_units"] == files
    oracle = _q6_shaped(
        TpuSession({"spark.rapids.sql.enabled": False}), paths)
    assert units == whole == oracle and units[0][0] > 0


def test_a_file_of_one_row_group_reads_once(tmp_path, counted_reads):
    p = _write_q6(tmp_path, "one", n=600, row_group=600)
    ex = _unit_exec([p], rows=2147483647)
    snap = PC.snapshot()
    batches = list(ex.execute_columnar())
    d = PC.since(snap)
    assert [b.num_rows for b in batches] == [600]
    assert counted_reads == [[0]]
    assert d["scan_units"] == 1 and d["scan_files_streamed"] == 0
    assert "units=1" in ex.describe()


def test_the_three_stages_overlap(tmp_path, counted_reads):
    """Read, to_columns and upload each slowed (the idiom of
    test_prefetch_overlap_beats_sequential): N units take the slowest
    stage N times plus the other two once, not the sum N times; depth 0
    takes the sum."""
    p = _write_q6(tmp_path, "ov", n=6 * 256, row_group=256)
    t_read, t_cols, t_h2d, n = 0.06, 0.12, 0.08, 6
    counted_reads.hook = lambda groups: time.sleep(t_read)

    def run(depth):
        ex = _unit_exec([p], depth=depth, rows=256)
        to_cols, to_dev = ex._table_to_host_cols, ex._to_device

        def slow_cols(tbl):
            time.sleep(t_cols)
            return to_cols(tbl)

        def slow_dev(cols):
            time.sleep(t_h2d)
            return to_dev(cols)

        ex._table_to_host_cols, ex._to_device = slow_cols, slow_dev
        t0 = time.perf_counter()
        rows = sum(b.num_rows for b in ex.execute_columnar())
        return time.perf_counter() - t0, rows

    run(2)                                  # imports, the first upload
    seq_wall, seq_rows = run(0)
    snap = PC.snapshot()
    ov_wall, ov_rows = run(2)
    d = PC.since(snap)
    assert seq_rows == ov_rows == n * 256 and d["scan_units"] == n
    total = t_read + t_cols + t_h2d
    assert seq_wall >= n * total                        # 1.56 s
    # 6 x 0.12 + 0.06 + 0.08 = 0.86 s against 1.56: the margin is
    # decisive, not a scheduler tick
    assert ov_wall < n * t_cols + t_read + t_h2d + 0.3, (ov_wall, seq_wall)
    assert ov_wall < seq_wall - 0.4, (ov_wall, seq_wall)
    # the stages' spans are roots on their own threads
    for name in ("srt.scan.read", "srt.scan.to_columns", "srt.scan.h2d"):
        assert d[f"span_n|{name}"] >= n, name


def test_a_closed_scan_reads_no_further_unit_and_leaves_no_thread(
        tmp_path, counted_reads):
    p = _write_q6(tmp_path, "lim", n=32 * 64, row_group=64)
    ex = _unit_exec([p], depth=1, rows=64)       # 32 units
    it = ex.execute_columnar()
    assert next(it).num_rows == 64
    it.close()
    read = len(counted_reads)
    # what the three queues of depth 1 and the stages hold, not the file
    assert 1 <= read <= 8
    assert _scan_threads() == []
    time.sleep(0.3)
    assert len(counted_reads) == read
    # the same through a query: a limit stops pulling after one batch
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.sql.reader.batchSizeRows": "64"})
    assert len(s.read.parquet(p).limit(3).collect()) == 3
    assert _scan_threads() == []


@pytest.mark.parametrize("mode", ["COALESCING", "MULTITHREADED"])
def test_a_unit_that_fails_mid_file(tmp_path, counted_reads, mode):
    good = _write_q6(tmp_path, "good", n=1024)
    bad = _write_q6(tmp_path, "bad", n=2048, first_id=5000)

    def third_unit_of_bad_is_corrupt(groups):
        if groups == [4, 5]:        # good has four row groups, bad eight
            raise OSError("Corrupt snappy compressed data.")

    counted_reads.hook = third_unit_of_bad_is_corrupt
    from spark_rapids_tpu.io import faults as IOF

    conf = {"spark.rapids.sql.enabled": True,
            "spark.rapids.sql.format.parquet.reader.type": mode,
            "spark.rapids.sql.reader.batchSizeRows": "512",
            "spark.rapids.tpu.scan.hotTableCache.enabled": True}
    # default conf: the annotated fault names the file
    with pytest.raises(IOF.CorruptFile) as ei:
        TpuSession({**conf, "spark.rapids.tpu.resilience.enabled": "false"}
                   ).read.parquet(good, bad).collect()
    assert ei.value.path == bad
    assert _scan_threads() == []
    # tolerated: the file is absent as a whole, not cut at the fault
    snap = PC.snapshot()
    s = TpuSession({**conf, "spark.sql.files.ignoreCorruptFiles": "true"})
    df = s.read.parquet(good, bad)
    rows = df.collect()
    d = PC.since(snap)
    assert sorted(r[0] for r in rows) == list(range(1024))
    assert d["files_skipped_corrupt"] == 1
    assert d["scan_units"] == 2             # the good file's, no more
    from spark_rapids_tpu.io.hot_cache import peek_hot_cache

    cache = peek_hot_cache()
    assert cache is None or cache.stats()["entries"] == 0
    s.close()


def test_a_missing_column_is_still_a_schema_mismatch(tmp_path):
    from spark_rapids_tpu.io import faults as IOF

    p = _write_q6(tmp_path, "drift")
    schema = T.StructType(list(_Q6_SCHEMA.fields)
                          + [T.StructField("absent", T.LONG, True)])
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.io.scan import TpuFileSourceScanExec
    from spark_rapids_tpu.plan.nodes import FileSourceScan

    ex = TpuFileSourceScanExec(
        FileSourceScan("parquet", [p], schema),
        TpuConf({"spark.rapids.sql.reader.batchSizeRows": "512"}))
    with pytest.raises(IOF.SchemaMismatch) as ei:
        list(ex.execute_columnar())
    assert "absent" in str(ei.value) and ei.value.path == p
    assert _scan_threads() == []


def test_a_paused_governor_lets_no_upload_run_ahead(tmp_path):
    """Under YELLOW/RED a unit crosses the link when the client waits
    for it, never before: no batch is found ready."""
    from spark_rapids_tpu.governor import context as GOV

    class Paused:
        def pause_background(self):
            return True

        def batch_pull_checkpoint(self):    # the runtime's per-pull hook
            pass

    p = _write_q6(tmp_path, "gov", n=1024)
    ex = _unit_exec([p])
    prev, GOV.GOVERNOR = GOV.GOVERNOR, Paused()
    try:
        snap = PC.snapshot()
        n = 0
        for b in ex.execute_columnar():
            time.sleep(0.1)         # the stages could run ahead here
            n += b.num_rows
        d = PC.since(snap)
    finally:
        GOV.GOVERNOR = prev
    assert n == 1024 and d["scan_units"] == 2
    assert d["bytes_h2d_overlapped"] == 0


# ---------------------------------------------------------------------------
# chaos: decode fault through the compressed path falls back per file
# ---------------------------------------------------------------------------

def test_chaos_decode_through_compressed_path(tmp_path):
    from spark_rapids_tpu.resilience import inject_fault

    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(23)
    paths = []
    for i in range(3):
        p = str(tmp_path / f"ch-{i}.parquet")
        pq.write_table(pa.table(
            {"v": rng.integers(0, 50, 500).astype(np.int64)}), p,
            compression="snappy")
        paths.append(p)
    so = TpuSession({"spark.rapids.sql.enabled": False})
    want = sorted(so.read.parquet(*paths).collect())
    base = PC.snapshot()
    inject_fault("TpuFileSourceScanExec", "decode", count=1, at_batch=1)
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.sql.format.parquet.reader.type":
                        "PERFILE", **DEV_CONF})
    got = sorted(s.read.parquet(*paths).collect())
    d = PC.since(base)
    assert got == want
    assert d["file_decoder_fallbacks"] == 1
    assert d["runtime_fallbacks"] == 0


# ---------------------------------------------------------------------------
# snappy device decompressor: property test vs the host reference
# ---------------------------------------------------------------------------

def test_snappy_gather_resolution_property():
    from spark_rapids_tpu.native import snappy_compress
    from spark_rapids_tpu.pallas.decompress import (
        TooFragmented,
        decompress_to_host,
    )

    rng = np.random.default_rng(31)
    cases = [
        b"", b"x", b"ab" * 3000,
        bytes(rng.integers(0, 256, 30000, dtype=np.uint8)),
        bytes(rng.integers(0, 5, 20000, dtype=np.uint8)),
        b"".join(bytes([i % 11]) * int(r)
                 for i, r in enumerate(rng.integers(1, 120, 300))),
        bytes(np.sort(rng.integers(0, 10**5, 5000)).astype("<i8")
              .view(np.uint8)),
    ]
    try:
        import pyarrow as pa

        compressors = [snappy_compress,
                       lambda b: pa.compress(b, codec="snappy",
                                             asbytes=True)]
    except ImportError:
        compressors = [snappy_compress]
    for compress in compressors:
        for i, raw in enumerate(cases):
            comp = compress(raw)
            try:
                assert decompress_to_host(comp) == raw, i
            except TooFragmented:
                continue   # legal outcome: the chunk ships decoded


def test_snappy_device_matches_host():
    from spark_rapids_tpu.native import snappy_compress
    from spark_rapids_tpu.pallas.decompress import snappy_to_device

    rng = np.random.default_rng(37)
    raw = bytes(np.tile(rng.integers(0, 256, 256, dtype=np.uint8), 40))
    comp = snappy_compress(raw)
    dev = snappy_to_device(comp, decoded_cost=len(raw) * 4)
    assert bytes(np.asarray(dev)) == raw


# ---------------------------------------------------------------------------
# expand_runs host/device agreement, incl. the bw=0 all-dictionary case
# ---------------------------------------------------------------------------

def _encode_hybrid(runs, bw):
    """Build an RLE/bit-packed hybrid buffer from (is_packed, values)
    specs — the inverse of split_hybrid_runs for test streams."""
    out = bytearray()

    def varint(v):
        while True:
            b = v & 0x7F
            v >>= 7
            if v:
                out.append(b | 0x80)
            else:
                out.append(b)
                return

    total = []
    for is_packed, values in runs:
        if is_packed:
            groups = (len(values) + 7) // 8
            vals = list(values) + [0] * (groups * 8 - len(values))
            varint((groups << 1) | 1)
            for g in range(groups):
                bits = 0
                for k in range(8):
                    bits |= (vals[g * 8 + k] & ((1 << bw) - 1)) \
                        << (k * bw)
                out += bits.to_bytes(max(bw, 0), "little")
            total += vals
        else:
            count, value = values
            varint(count << 1)
            vbytes = (bw + 7) // 8
            out += int(value).to_bytes(vbytes, "little")
            total += [value] * count
    return bytes(out), total


@pytest.mark.parametrize("bw", [0, 1, 3, 7, 12])
def test_expand_runs_host_device_agree(bw):
    from spark_rapids_tpu.io.parquet_native import split_hybrid_runs
    from spark_rapids_tpu.pallas.decode import (
        expand_runs,
        expand_runs_host,
    )

    rng = np.random.default_rng(41 + bw)
    specs = []
    for _ in range(5):
        if bw == 0 or rng.random() < 0.5:
            specs.append((False, (int(rng.integers(1, 40)) * 8,
                                  0 if bw == 0 else
                                  int(rng.integers(0, 1 << bw)))))
        else:
            nv = int(rng.integers(1, 6)) * 8
            specs.append((True, [int(v) for v in
                                 rng.integers(0, 1 << bw, nv)]))
    buf, expected = _encode_hybrid(specs, bw)
    total = len(expected)
    runs = split_hybrid_runs(buf, bw, total)
    host = expand_runs_host(runs, buf, total, bw)
    dev = np.asarray(expand_runs(runs, buf, total, bw))
    assert host.dtype == np.uint32
    assert dev.dtype == np.uint32, \
        "device/host expand_runs dtype drift"
    assert host.tolist() == expected[:total]
    assert dev.tolist() == expected[:total]


def test_expand_runs_bw0_packed_run_host():
    """bw=0 PACKED runs (zero payload bytes): the host fallback used to
    divide by zero where the device path returned zeros — both must
    yield uint32 zeros now."""
    from spark_rapids_tpu.io.parquet_native import Run
    from spark_rapids_tpu.pallas.decode import (
        expand_runs,
        expand_runs_host,
    )

    runs = [Run(True, 16, 0, 0, 0), Run(False, 8, 0, 0, 0)]
    host = expand_runs_host(runs, b"", 24, 0)
    dev = np.asarray(expand_runs(runs, b"", 24, 0))
    assert host.dtype == dev.dtype == np.uint32
    assert host.tolist() == dev.tolist() == [0] * 24
