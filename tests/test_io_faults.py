"""I/O fault domain tests (ISSUE 5): per-file corrupt/missing-input
tolerance, per-file device->native decoder fallback, quarantine manifest,
and the writer's atomic staging/commit protocol.

Reference analogs: the reference plugin inherits Spark's
``spark.sql.files.ignoreCorruptFiles`` / ``ignoreMissingFiles`` handling
in GpuMultiFileReader and the task-commit protocol in
GpuFileFormatDataWriter (SURVEY.md §2.6)."""
import glob
import json
import os
import threading

import pytest

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession

from data_gen import (
    corrupt_delete,
    corrupt_flip,
    corrupt_truncate,
    write_multifile_dataset,
    write_schema_drifted,
)

SCHEMA = T.StructType([T.StructField("i", T.LONG),
                       T.StructField("v", T.DOUBLE),
                       T.StructField("s", T.STRING)])

MODES = ("PERFILE", "COALESCING", "MULTITHREADED")

TOL_ON = {"spark.sql.files.ignoreCorruptFiles": "true",
          "spark.sql.files.ignoreMissingFiles": "true"}


@pytest.fixture(autouse=True)
def _clean_io_state():
    from spark_rapids_tpu.io.faults import reset_quarantine

    reset_quarantine()
    yield
    reset_quarantine()


def _session(mode, extra=None):
    return TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.format.parquet.reader.type": mode,
        **(extra or {}),
    })


def _read(s, fmt, paths):
    rd = s.read.schema(SCHEMA)
    if fmt == "csv":
        rd = rd.option("header", "true")
    return getattr(rd, fmt)(*paths)


def _oracle_rows(fmt, paths):
    """CPU-oracle rows over an explicit (surviving) file set."""
    s = TpuSession({"spark.rapids.sql.enabled": False})
    return sorted(_read(s, fmt, paths).collect())


def _damage(paths, fmt):
    """Corrupt file 1, delete file 2 -> surviving paths."""
    corrupt_truncate(paths[1])
    corrupt_delete(paths[2])
    return [p for k, p in enumerate(paths) if k not in (1, 2)]


# ---------------------------------------------------------------------------
# tolerance matrix: format x reader mode x conf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("fmt", ["parquet", "orc", "avro", "csv"])
def test_tolerated_skip_matches_oracle(fmt, mode, tmp_path):
    """Binary formats: one truncated + one deleted file; text formats:
    one deleted file (byte damage in CSV parses permissively — Spark's
    record-level malformed-row semantics own that case, see
    docs/io_resilience.md)."""
    paths = write_multifile_dataset(tmp_path, fmt, n_files=4,
                                    rows_per_file=20)
    if fmt == "csv":
        corrupt_delete(paths[2])
        surviving = [p for k, p in enumerate(paths) if k != 2]
        expect_corrupt = 0
    else:
        surviving = _damage(paths, fmt)
        expect_corrupt = 1
    PC.reset()
    rows = sorted(_read(_session(mode, TOL_ON), fmt, paths).collect())
    assert rows == _oracle_rows(fmt, surviving)
    snap = PC.snapshot()
    assert snap["files_skipped_corrupt"] == expect_corrupt
    assert snap["files_skipped_missing"] == 1


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("fmt", ["parquet", "orc", "avro", "csv"])
def test_fail_fast_names_the_file(fmt, mode, tmp_path):
    from spark_rapids_tpu.io.faults import MissingFile, ScanFault

    paths = write_multifile_dataset(tmp_path, fmt, n_files=4,
                                    rows_per_file=20)
    bad = corrupt_delete(paths[1]) if fmt == "csv" \
        else corrupt_truncate(paths[1])
    s = _session(mode, {"spark.rapids.tpu.resilience.enabled": "false"})
    with pytest.raises(Exception) as ei:
        _read(s, fmt, paths).collect()
    exc = ei.value
    assert isinstance(exc, MissingFile if fmt == "csv" else ScanFault), exc
    assert bad in str(exc)
    assert mode in str(exc)


def test_csv_byte_damage_is_record_level_not_file_level(tmp_path):
    """Text-format byte damage parses under Spark's record-level
    malformed-row semantics (docs/io_resilience.md): the query succeeds
    regardless of ignoreCorruptFiles and nothing is counted as a
    file-level skip."""
    from data_gen import corrupt_garbage

    paths = write_multifile_dataset(tmp_path, "csv", n_files=3,
                                    rows_per_file=20)
    corrupt_garbage(paths[1])
    PC.reset()
    for extra in ({}, TOL_ON):
        rows = _read(_session("PERFILE", extra), "csv", paths)
        assert len(rows.collect()) >= 40   # good files' rows all present
    assert PC.snapshot()["files_skipped_corrupt"] == 0


def test_missing_only_conf_split(tmp_path):
    """ignoreMissingFiles alone tolerates the vanished file but still
    fails fast on the corrupt one (and names it)."""
    from spark_rapids_tpu.io.faults import CorruptFile

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=4,
                                    rows_per_file=20)
    corrupt_truncate(paths[1])
    corrupt_delete(paths[2])
    conf = {"spark.sql.files.ignoreMissingFiles": "true",
            "spark.rapids.tpu.resilience.enabled": "false"}
    with pytest.raises(CorruptFile) as ei:
        _read(_session("PERFILE", conf), "parquet", paths).collect()
    assert paths[1] in str(ei.value)


def test_tpu_alias_overrides_spark_conf(tmp_path):
    """spark.rapids.tpu.files.* wins over the spark.sql.files.* conf."""
    from spark_rapids_tpu.io.faults import ScanFault

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=3,
                                    rows_per_file=10)
    corrupt_truncate(paths[1])
    conf = {**TOL_ON,
            "spark.rapids.tpu.files.ignoreCorruptFiles": "false",
            "spark.rapids.tpu.resilience.enabled": "false"}
    with pytest.raises(ScanFault):
        _read(_session("PERFILE", conf), "parquet", paths).collect()
    # and the other direction: spark conf off, tpu alias on
    conf2 = {"spark.rapids.tpu.files.ignoreCorruptFiles": "true"}
    rows = sorted(_read(_session("PERFILE", conf2), "parquet",
                        paths).collect())
    assert rows == _oracle_rows("parquet", [paths[0], paths[2]])


@pytest.mark.parametrize("fmt", ["parquet", "orc"])
def test_schema_drifted_file(fmt, tmp_path):
    from spark_rapids_tpu.io.faults import SchemaMismatch

    paths = write_multifile_dataset(tmp_path, fmt, n_files=3,
                                    rows_per_file=10)
    write_schema_drifted(paths[1], fmt)
    PC.reset()
    rows = sorted(_read(_session("PERFILE", TOL_ON), fmt, paths).collect())
    assert rows == _oracle_rows(fmt, [paths[0], paths[2]])
    assert PC.snapshot()["files_skipped_corrupt"] == 1
    with pytest.raises(SchemaMismatch) as ei:
        _read(_session(
            "PERFILE",
            {"spark.rapids.tpu.resilience.enabled": "false"}),
            fmt, paths).collect()
    assert paths[1] in str(ei.value)


# ---------------------------------------------------------------------------
# acceptance pin: 20-file scan, 2 corrupt + 1 missing
# ---------------------------------------------------------------------------

def test_twenty_file_scan_acceptance(tmp_path):
    from spark_rapids_tpu.io.faults import quarantine_entries

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=20,
                                    rows_per_file=10)
    corrupt_truncate(paths[3])
    corrupt_flip(paths[7])
    corrupt_delete(paths[11])
    surviving = [p for k, p in enumerate(paths) if k not in (3, 7, 11)]
    expected = _oracle_rows("parquet", surviving)
    assert len(expected) == 17 * 10
    for mode in MODES:
        PC.reset()
        rows = sorted(_read(_session(mode, TOL_ON), "parquet",
                            paths).collect())
        assert rows == expected, mode
        snap = PC.snapshot()
        assert snap["files_skipped_corrupt"] == 2, mode
        assert snap["files_skipped_missing"] == 1, mode
        q = quarantine_entries()
        assert sorted(e["class"] for e in q) \
            == sorted(["truncated", "corrupt", "missing"]) \
            or len(q) == 3  # flip near the footer may classify truncated
        assert {e["path"] for e in q} == {paths[3], paths[7], paths[11]}
    # ignore off: file-attributed failure
    s = _session("MULTITHREADED",
                 {"spark.rapids.tpu.resilience.enabled": "false"})
    with pytest.raises(Exception) as ei:
        _read(s, "parquet", paths).collect()
    assert any(p in str(ei.value) for p in (paths[3], paths[7],
                                            paths[11]))


def test_eight_way_concurrent_tolerant_scan(tmp_path):
    """The acceptance stress pin: 8 concurrent collects over a damaged
    dataset all see exactly the surviving rows, with clean leak reports."""
    from spark_rapids_tpu.lifecycle import leak_report_all

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=8,
                                    rows_per_file=20)
    corrupt_truncate(paths[2])
    corrupt_delete(paths[5])
    surviving = [p for k, p in enumerate(paths) if k not in (2, 5)]
    expected = _oracle_rows("parquet", surviving)
    results, errors = [], []

    def worker():
        try:
            s = _session("MULTITHREADED", TOL_ON)
            results.append(sorted(_read(s, "parquet", paths).collect()))
        except Exception as e:   # noqa: BLE001 — collected for assert
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert all(r == expected for r in results)
    assert leak_report_all() == []


# ---------------------------------------------------------------------------
# quarantine manifest
# ---------------------------------------------------------------------------

def test_quarantine_manifest_written_next_to_event_log(tmp_path):
    paths = write_multifile_dataset(tmp_path / "data", "parquet",
                                    n_files=4, rows_per_file=10)
    corrupt_truncate(paths[1])
    corrupt_delete(paths[2])
    log_dir = str(tmp_path / "logs")
    conf = {**TOL_ON,
            "spark.rapids.tpu.diagnostics.eventLogDir": log_dir}
    _read(_session("PERFILE", conf), "parquet", paths).collect()
    manifests = glob.glob(os.path.join(log_dir, "quarantine-*.json"))
    assert len(manifests) == 1
    doc = json.load(open(manifests[0]))
    assert len(doc["files"]) == 2
    by_path = {e["path"]: e for e in doc["files"]}
    assert by_path[paths[1]]["class"] in ("truncated", "corrupt")
    assert by_path[paths[2]]["class"] == "missing"
    for e in doc["files"]:
        assert e["fmt"] == "parquet" and e["reader"] == "PERFILE"


def test_io_fault_diagnostics_event(tmp_path):
    paths = write_multifile_dataset(tmp_path, "parquet", n_files=3,
                                    rows_per_file=10)
    corrupt_truncate(paths[1])
    s = _session("COALESCING", {
        **TOL_ON, "spark.rapids.tpu.diagnostics.enabled": "true"})
    df = _read(s, "parquet", paths)
    df.collect()
    diag = df._last_diag
    evs = [e for e in diag.events if e["ev"] == "io_fault"]
    assert len(evs) == 1
    assert evs[0]["path"] == paths[1]
    assert evs[0]["kind"] in ("truncated", "corrupt")


# ---------------------------------------------------------------------------
# per-file device->native decoder fallback + per-format breaker
# ---------------------------------------------------------------------------

DEV_CONF = {"spark.rapids.sql.format.parquet.decode.device": "true"}


def test_decoder_fallback_single_file(tmp_path):
    from spark_rapids_tpu.resilience import inject_fault

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=3,
                                    rows_per_file=10)
    expected = _oracle_rows("parquet", paths)
    PC.reset()
    baseline = PC.snapshot()["file_decoder_fallbacks"]
    inject_fault("TpuFileSourceScanExec", "decode", count=1, at_batch=1)
    rows = sorted(_read(_session("PERFILE", DEV_CONF), "parquet",
                        paths).collect())
    assert rows == expected
    # that file only: exactly one fallback, the query still succeeded
    # without the stage fault domain (no retries / runtime fallbacks)
    snap = PC.snapshot()
    assert snap["file_decoder_fallbacks"] - baseline == 1
    assert snap["runtime_fallbacks"] == 0
    assert snap["transient_retries"] == 0


@pytest.mark.parametrize("error", [ImportError, AttributeError, NameError,
                                   TypeError])
def test_decoder_programming_error_reraises(tmp_path, monkeypatch, error):
    """A programming error out of the device decoder (a jax API that
    moved, a wrong call) must fail the scan — not count one
    file_decoder_fallbacks and answer from the host decoder with rc 0."""
    from spark_rapids_tpu.io import parquet_device

    def broken(path, schema):
        raise error("decoder bug")

    monkeypatch.setattr(parquet_device, "read_parquet_device", broken)
    paths = write_multifile_dataset(tmp_path, "parquet", n_files=1,
                                    rows_per_file=10)
    PC.reset()
    conf = {**DEV_CONF,
            "spark.rapids.tpu.resilience.runtimeFallbackEnabled": "false"}
    with pytest.raises(error, match="decoder bug"):
        _read(_session("PERFILE", conf), "parquet", paths).collect()
    assert PC.snapshot()["file_decoder_fallbacks"] == 0


def test_decode_breaker_trips_to_native_at_plan_time(tmp_path):
    from spark_rapids_tpu.resilience import active_faults, inject_fault
    from spark_rapids_tpu.resilience.breaker import get_breaker

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=1,
                                    rows_per_file=10)
    conf = {**DEV_CONF,
            "spark.rapids.tpu.resilience.breakerFailureThreshold": "2"}
    inject_fault("TpuFileSourceScanExec", "decode", count=2, at_batch=0)
    _read(_session("PERFILE", conf), "parquet", paths).collect()
    _read(_session("PERFILE", conf), "parquet", paths).collect()
    key = ("TpuFileSourceScanExec.deviceDecode", "parquet")
    assert get_breaker().state_of(key) == "OPEN"
    # with the breaker open the device decoder is not even tried: an
    # armed decode fault stays armed, rows still come from native
    inject_fault("TpuFileSourceScanExec", "decode", count=1, at_batch=0)
    rows = sorted(_read(_session("PERFILE", conf), "parquet",
                        paths).collect())
    assert rows == _oracle_rows("parquet", paths)
    assert ("TpuFileSourceScanExec", "decode", 1) in active_faults()


def test_corrupt_file_does_not_indict_device_decoder(tmp_path):
    """A corrupt FILE failing the device decoder is a data fault, not a
    decoder failure: no file_decoder_fallbacks, no decode-breaker food —
    the host path re-derives the fault and the tolerance confs own it."""
    from spark_rapids_tpu.resilience.breaker import get_breaker

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=3,
                                    rows_per_file=10)
    corrupt_truncate(paths[1])
    PC.reset()
    rows = sorted(_read(_session("PERFILE", {**DEV_CONF, **TOL_ON}),
                        "parquet", paths).collect())
    assert rows == _oracle_rows("parquet", [paths[0], paths[2]])
    snap = PC.snapshot()
    assert snap["file_decoder_fallbacks"] == 0
    assert snap["files_skipped_corrupt"] == 1
    key = ("TpuFileSourceScanExec.deviceDecode", "parquet")
    assert get_breaker().state_of(key) == "CLOSED"


def test_chaos_file_corrupt_injection_follows_conf_matrix(tmp_path):
    from spark_rapids_tpu.io.faults import CorruptFile
    from spark_rapids_tpu.resilience import clear_faults, inject_fault

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=3,
                                    rows_per_file=10)
    PC.reset()
    inject_fault("TpuFileSourceScanExec", "file_corrupt", count=1,
                 at_batch=1)
    rows = sorted(_read(_session("COALESCING", TOL_ON), "parquet",
                        paths).collect())
    assert rows == _oracle_rows("parquet", [paths[0], paths[2]])
    assert PC.snapshot()["files_skipped_corrupt"] == 1
    clear_faults()
    inject_fault("TpuFileSourceScanExec", "file_corrupt", count=1,
                 at_batch=1)
    s = _session("COALESCING",
                 {"spark.rapids.tpu.resilience.enabled": "false"})
    with pytest.raises(CorruptFile) as ei:
        _read(s, "parquet", paths).collect()
    assert paths[1] in str(ei.value)


# ---------------------------------------------------------------------------
# MOR (iceberg/delta shared) file-list tolerance
# ---------------------------------------------------------------------------

def test_mor_reader_tolerates_missing_data_file(tmp_path):
    from spark_rapids_tpu.io.faults import MissingFile
    from spark_rapids_tpu.io.mor import read_parquet_minus_rows

    paths = write_multifile_dataset(tmp_path, "parquet", n_files=3,
                                    rows_per_file=10)
    corrupt_delete(paths[1])
    files = [(p, None) for p in paths]
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.sql.files.ignoreMissingFiles": "true"})
    rows = sorted(read_parquet_minus_rows(s, files, SCHEMA).collect())
    assert rows == _oracle_rows("parquet", [paths[0], paths[2]])
    s2 = TpuSession({"spark.rapids.sql.enabled": True})
    with pytest.raises(MissingFile):
        read_parquet_minus_rows(s2, files, SCHEMA)


# ---------------------------------------------------------------------------
# writer: staging/commit protocol
# ---------------------------------------------------------------------------

def _no_visible_partial(out):
    """Zero visible output: no part files, no _SUCCESS, no _temporary."""
    if not os.path.exists(out):
        return True
    entries = os.listdir(out)
    assert "_temporary" not in entries, entries
    assert "_SUCCESS" not in entries, entries
    assert not [e for e in entries if e.startswith("part-")], entries
    return True


def test_commit_leaves_no_temporary_and_rolls_files(tmp_path):
    paths = write_multifile_dataset(tmp_path / "in", "parquet",
                                    n_files=2, rows_per_file=50)
    out = str(tmp_path / "out")
    s = _session("PERFILE", {"spark.sql.files.maxRecordsPerFile": "10"})
    _read(s, "parquet", paths).write.mode("overwrite").parquet(out)
    assert os.path.exists(os.path.join(out, "_SUCCESS"))
    assert not os.path.exists(os.path.join(out, "_temporary"))
    parts = glob.glob(os.path.join(out, "part-*.parquet"))
    assert len(parts) == 10  # 100 rows / maxRecordsPerFile=10
    back = sorted(r[:3] for r in _read(
        TpuSession({"spark.rapids.sql.enabled": False}), "parquet",
        sorted(parts)).collect())
    assert back == _oracle_rows("parquet", paths)


def test_kill_mid_write_leaves_zero_visible_output(tmp_path):
    """A deterministic scan failure mid-write (resilience off, corrupt
    second file) aborts the staged output: readers can never observe a
    half-written result."""
    paths = write_multifile_dataset(tmp_path / "in", "parquet",
                                    n_files=3, rows_per_file=30)
    corrupt_truncate(paths[1])
    out = str(tmp_path / "out")
    s = _session("PERFILE", {
        "spark.rapids.tpu.resilience.enabled": "false",
        "spark.sql.files.maxRecordsPerFile": "5"})
    with pytest.raises(Exception):
        _read(s, "parquet", paths).write.mode("overwrite").parquet(out)
    assert _no_visible_partial(out)
    from spark_rapids_tpu.lifecycle import leak_report_all

    assert leak_report_all() == []


def test_cancel_token_mid_write_cleans_staging(tmp_path):
    """CancelToken trip mid-write: the writer's unwind (plus the
    lifecycle cleanup hook backstop) deletes the staging dir and no
    partial output is visible."""
    from spark_rapids_tpu import lifecycle
    from spark_rapids_tpu.expr.udf import udf
    from spark_rapids_tpu.lifecycle import QueryCancelled
    from spark_rapids_tpu.session import col

    paths = write_multifile_dataset(tmp_path / "in", "parquet",
                                    n_files=4, rows_per_file=25)
    out = str(tmp_path / "out")
    calls = [0]

    def tripper(x):
        calls[0] += 1
        if calls[0] > 30:
            ctx = lifecycle.current()
            if ctx is not None:
                ctx.cancel("mid-write test cancel")
        return x

    s = _session("PERFILE", {
        "spark.rapids.sql.udfCompiler.enabled": "false",
        "spark.sql.files.maxRecordsPerFile": "5"})
    df = _read(s, "parquet", paths).with_column(
        "t", udf(tripper, T.LONG, "tripper")(col("i")))
    with pytest.raises(QueryCancelled):
        df.write.mode("overwrite").parquet(out)
    assert calls[0] > 30
    assert _no_visible_partial(out)
    from spark_rapids_tpu.lifecycle import leak_report_all

    assert leak_report_all() == []


def test_failed_overwrite_preserves_old_data(tmp_path):
    """Overwrite deletes the old output at COMMIT time: a write that
    dies mid-stream leaves the previous dataset fully readable."""
    paths = write_multifile_dataset(tmp_path / "in", "parquet",
                                    n_files=3, rows_per_file=20)
    out = str(tmp_path / "out")
    s = _session("PERFILE",
                 {"spark.rapids.tpu.resilience.enabled": "false"})
    _read(s, "parquet", [paths[0]]).write.mode("overwrite").parquet(out)
    old_rows = _oracle_rows("parquet", [paths[0]])
    corrupt_truncate(paths[2])
    with pytest.raises(Exception):
        _read(s, "parquet", paths).write.mode("overwrite").parquet(out)
    # old output intact: _SUCCESS still there, rows unchanged
    assert os.path.exists(os.path.join(out, "_SUCCESS"))
    assert not os.path.exists(os.path.join(out, "_temporary"))
    parts = sorted(glob.glob(os.path.join(out, "part-*.parquet")))
    assert sorted(r[:3] for r in _read(
        TpuSession({"spark.rapids.sql.enabled": False}), "parquet",
        parts).collect()) == old_rows


def test_staging_leak_gate_reports_and_recovers(tmp_path):
    from spark_rapids_tpu.io.writer import TaskCommit
    from spark_rapids_tpu.lifecycle import (
        leak_report_all,
        reset_leaked_state,
    )

    out = str(tmp_path / "out")
    os.makedirs(out)
    commit = TaskCommit(out)
    open(os.path.join(commit.stage_dir(), "part-junk.parquet"),
         "w").close()
    leaks = leak_report_all()
    assert any("staging dir" in l for l in leaks)
    reset_leaked_state()
    assert leak_report_all() == []
    assert not os.path.exists(os.path.join(out, "_temporary"))


def test_fsync_on_commit_conf(tmp_path):
    paths = write_multifile_dataset(tmp_path / "in", "parquet",
                                    n_files=1, rows_per_file=10)
    out = str(tmp_path / "out")
    s = _session("PERFILE",
                 {"spark.rapids.tpu.files.fsyncOnCommit": "true"})
    _read(s, "parquet", paths).write.mode("overwrite").parquet(out)
    assert os.path.exists(os.path.join(out, "_SUCCESS"))
    assert not os.path.exists(os.path.join(out, "_temporary"))


# ---------------------------------------------------------------------------
# error attribution (__notes__ / wrapped message) — satellite pin
# ---------------------------------------------------------------------------

def test_failfast_error_with_corruptish_user_data_still_propagates(
        tmp_path):
    """A FAILFAST parse error whose malformed ROW happens to contain a
    corruption-marker string ('corrupt', 'CRC', ...) must still raise —
    user data in an engine error message can never classify the file as
    corrupt and tolerate it away."""
    path = str(tmp_path / "d.csv")
    with open(path, "w") as f:
        f.write("i,v,s\n2,2.0,ok\nbadrow-corrupt-disk-CRC,3.0,b\n")
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.tpu.resilience.enabled": "false",
                    **TOL_ON})
    df = s.read.schema(SCHEMA).option("header", "true") \
        .option("mode", "FAILFAST").csv(path)
    PC.reset()
    with pytest.raises(Exception):
        df.collect()
    assert PC.snapshot()["files_skipped_corrupt"] == 0


def test_unclassified_errors_still_carry_file_notes(tmp_path):
    """Errors the classifier refuses to own (here: a semantic FAILFAST
    parse error) propagate with file context attached via __notes__."""
    path = str(tmp_path / "bad.csv")
    with open(path, "w") as f:
        f.write("i,v,s\n1,2.0,a\nnot_a_number,3.0,b\n")
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.tpu.resilience.enabled": "false",
                    **TOL_ON})
    df = s.read.schema(SCHEMA).option("header", "true") \
        .option("mode", "FAILFAST").csv(path)
    with pytest.raises(Exception) as ei:
        df.collect()
    # FAILFAST is the query's CORRECT behavior: never tolerated away
    # even with ignoreCorruptFiles on — but the file is named
    notes = getattr(ei.value, "__notes__", [])
    assert any(path in n for n in notes) or path in str(ei.value)
