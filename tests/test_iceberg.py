"""Iceberg scan tests over a spec-shaped synthetic table (reference:
iceberg integration tests / GpuIcebergParquetReader)."""
import json
import os

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.io.avro import write_avro_file
from spark_rapids_tpu.session import TpuSession, col, lit, sum_

from asserts import assert_tpu_and_cpu_are_equal_collect

_MANIFEST_FILE_SCHEMA = {
    "type": "record", "name": "manifest_file", "fields": [
        {"name": "manifest_path", "type": "string"},
        {"name": "manifest_length", "type": "long"},
        {"name": "content", "type": ["null", "int"], "default": None},
    ]}

_MANIFEST_ENTRY_SCHEMA = {
    "type": "record", "name": "manifest_entry", "fields": [
        {"name": "status", "type": "int"},
        {"name": "data_file", "type": {
            "type": "record", "name": "data_file", "fields": [
                {"name": "content", "type": ["null", "int"],
                 "default": None},
                {"name": "file_path", "type": "string"},
                {"name": "file_format", "type": "string"},
                {"name": "record_count", "type": "long"},
            ]}},
    ]}


def _build_iceberg_table(path, frames, deleted_paths=()):
    """frames: list of (parquet_name, pyarrow table). Spec-shaped layout:
    metadata json + manifest-list avro + manifest avro + parquet files."""
    import pyarrow.parquet as pq

    os.makedirs(os.path.join(path, "metadata"), exist_ok=True)
    os.makedirs(os.path.join(path, "data"), exist_ok=True)
    entries = []
    for name, tbl in frames:
        fp = os.path.join(path, "data", name)
        pq.write_table(tbl, fp)
        entries.append({"status": 1, "data_file": {
            "content": 0, "file_path": fp, "file_format": "PARQUET",
            "record_count": tbl.num_rows}})
    for dp in deleted_paths:
        entries.append({"status": 2, "data_file": {
            "content": 0, "file_path": dp, "file_format": "PARQUET",
            "record_count": 0}})
    manifest = os.path.join(path, "metadata", "manifest-1.avro")
    write_avro_file(manifest, _MANIFEST_ENTRY_SCHEMA, entries)
    mlist = os.path.join(path, "metadata", "snap-1-manifest-list.avro")
    write_avro_file(mlist, _MANIFEST_FILE_SCHEMA, [
        {"manifest_path": manifest,
         "manifest_length": os.path.getsize(manifest), "content": 0}])
    meta = {
        "format-version": 2,
        "table-uuid": "0000-test",
        "location": path,
        "current-schema-id": 0,
        "schemas": [{"schema-id": 0, "type": "struct", "fields": [
            {"id": 1, "name": "k", "required": True, "type": "int"},
            {"id": 2, "name": "v", "required": False, "type": "long"},
            {"id": 3, "name": "s", "required": False, "type": "string"},
        ]}],
        "current-snapshot-id": 99,
        "snapshots": [{"snapshot-id": 99, "manifest-list": mlist}],
    }
    with open(os.path.join(path, "metadata", "v2.metadata.json"),
              "w") as f:
        json.dump(meta, f)
    with open(os.path.join(path, "metadata", "version-hint.text"),
              "w") as f:
        f.write("2")


def _frames(n1=120, n2=80):
    import pyarrow as pa

    t1 = pa.table({"k": pa.array(range(n1), pa.int32()),
                   "v": pa.array([i * 10 for i in range(n1)], pa.int64()),
                   "s": pa.array([f"a{i}" for i in range(n1)])})
    t2 = pa.table({"k": pa.array(range(1000, 1000 + n2), pa.int32()),
                   "v": pa.array([None] * n2, pa.int64()),
                   "s": pa.array([f"b{i}" for i in range(n2)])})
    return [("f1.parquet", t1), ("f2.parquet", t2)]


def test_iceberg_scan_roundtrip(tmp_path):
    p = str(tmp_path / "tbl")
    _build_iceberg_table(p, _frames())
    s = TpuSession({"spark.rapids.sql.enabled": True})
    rows = s.read.iceberg(p).collect()
    assert len(rows) == 200
    ks = {r[0] for r in rows}
    assert 0 in ks and 1005 in ks


def test_iceberg_deleted_entries_skipped(tmp_path):
    p = str(tmp_path / "tbl")
    frames = _frames()
    _build_iceberg_table(p, frames[:1],
                         deleted_paths=[os.path.join(p, "data",
                                                     "f2.parquet")])
    s = TpuSession({"spark.rapids.sql.enabled": True})
    rows = s.read.iceberg(p).collect()
    assert len(rows) == 120


def test_iceberg_query_differential(tmp_path):
    p = str(tmp_path / "tbl")
    _build_iceberg_table(p, _frames())

    def build(sess):
        df = sess.read.iceberg(p)
        return df.filter(col("k") < lit(60)).group_by("s").agg(
            sum_("v", "sv"))

    assert_tpu_and_cpu_are_equal_collect(build)


def _add_delete_file(path, name, tbl, content, equality_ids=None):
    """Append a v2 delete file entry to the table's manifest."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.avro import read_avro_file

    fp = os.path.join(path, "data", name)
    pq.write_table(tbl, fp)
    manifest = os.path.join(path, "metadata", "manifest-1.avro")
    schema, entries = read_avro_file(manifest)
    e = {"status": 1, "data_file": {
        "content": content, "file_path": fp, "file_format": "PARQUET",
        "record_count": tbl.num_rows}}
    if equality_ids is not None:
        # extend the record schema with equality_ids for this write
        df_schema = schema["fields"][1]["type"]
        if not any(f["name"] == "equality_ids"
                   for f in df_schema["fields"]):
            df_schema["fields"].append(
                {"name": "equality_ids",
                 "type": ["null", {"type": "array", "items": "int"}],
                 "default": None})
        e["data_file"]["equality_ids"] = equality_ids
        for prev in entries:
            prev["data_file"].setdefault("equality_ids", None)
    write_avro_file(manifest, schema, entries + [e])


def test_iceberg_position_deletes(tmp_path):
    import pyarrow as pa

    p = str(tmp_path / "tbl")
    _build_iceberg_table(p, _frames())
    f1 = os.path.join(p, "data", "f1.parquet")
    dele = pa.table({"file_path": pa.array([f1, f1, f1]),
                     "pos": pa.array([0, 5, 119], pa.int64())})
    _add_delete_file(p, "del-pos.parquet", dele, content=1)
    s = TpuSession({"spark.rapids.sql.enabled": True})
    rows = s.read.iceberg(p).collect()
    ks = {r[0] for r in rows}
    assert len(rows) == 120 + 80 - 3
    assert 0 not in ks and 5 not in ks and 119 not in ks
    assert 1 in ks and 1000 in ks

    def build(sess):
        return sess.read.iceberg(p).filter(col("k") < lit(2000)) \
            .group_by().agg(sum_("v", "sv"))

    assert_tpu_and_cpu_are_equal_collect(build)


def test_iceberg_equality_deletes(tmp_path):
    import pyarrow as pa

    p = str(tmp_path / "tbl")
    _build_iceberg_table(p, _frames())
    dele = pa.table({"k": pa.array([2, 3, 1001], pa.int32())})
    _add_delete_file(p, "del-eq.parquet", dele, content=2,
                     equality_ids=[1])  # field id 1 = "k"
    s = TpuSession({"spark.rapids.sql.enabled": True})
    rows = s.read.iceberg(p).collect()
    ks = {r[0] for r in rows}
    assert len(rows) == 200 - 3
    assert ks.isdisjoint({2, 3, 1001})

    assert_tpu_and_cpu_are_equal_collect(
        lambda sess: sess.read.iceberg(p))


def test_iceberg_mixed_deletes(tmp_path):
    import pyarrow as pa

    p = str(tmp_path / "tbl")
    _build_iceberg_table(p, _frames())
    f2 = os.path.join(p, "data", "f2.parquet")
    _add_delete_file(p, "del-pos.parquet",
                     pa.table({"file_path": pa.array([f2]),
                               "pos": pa.array([0], pa.int64())}),
                     content=1)
    _add_delete_file(p, "del-eq.parquet",
                     pa.table({"s": pa.array(["a7", "a9"])}),
                     content=2, equality_ids=[3])  # field id 3 = "s"
    s = TpuSession({"spark.rapids.sql.enabled": True})
    rows = s.read.iceberg(p).collect()
    assert len(rows) == 200 - 3
    ss = {r[2] for r in rows}
    assert ss.isdisjoint({"a7", "a9", "b0"})


# -- round 4: write/commit path ------------------------


def _rows(df):
    return sorted(df.collect(), key=lambda r: tuple(
        (x is None, str(x)) for x in r))


def test_iceberg_write_read_roundtrip(tmp_path):
    from decimal import Decimal

    p = str(tmp_path / "t1")
    s = TpuSession({"spark.rapids.sql.enabled": True})
    schema = T.StructType([
        T.StructField("i", T.INT, False),
        T.StructField("t", T.STRING, True),
        T.StructField("d", T.DecimalType(10, 2), True),
        T.StructField("f", T.DOUBLE, True)])
    df = s.create_dataframe(
        {"i": [1, 2, 3], "t": ["a", None, "c"],
         "d": [Decimal("1.50"), Decimal("-2.25"), None],
         "f": [0.5, None, 2.5]}, schema)
    df.write.iceberg(p)
    back = s.read.iceberg(p)
    assert back.schema.field_names() == ["i", "t", "d", "f"]
    assert _rows(back) == _rows(df)


def test_iceberg_append_and_overwrite(tmp_path):
    p = str(tmp_path / "t2")
    s = TpuSession({"spark.rapids.sql.enabled": True})
    schema = T.StructType([T.StructField("v", T.LONG, False)])
    d1 = s.create_dataframe({"v": [1, 2]}, schema)
    d2 = s.create_dataframe({"v": [3]}, schema)
    d3 = s.create_dataframe({"v": [9]}, schema)
    d1.write.iceberg(p)
    d2.write.mode("append").iceberg(p)
    assert _rows(s.read.iceberg(p)) == [(1,), (2,), (3,)]
    d3.write.mode("overwrite").iceberg(p)
    assert _rows(s.read.iceberg(p)) == [(9,)]
    # snapshot chain survives: three snapshots recorded
    import json as _json
    import os as _os
    import re as _re

    mdir = _os.path.join(p, "metadata")
    latest = max(int(_re.match(r"v(\d+)", n).group(1))
                 for n in _os.listdir(mdir)
                 if _re.match(r"v(\d+)\.metadata\.json$", n))
    with open(_os.path.join(mdir, f"v{latest}.metadata.json")) as f:
        meta = _json.load(f)
    assert len(meta["snapshots"]) == 3
    assert meta["format-version"] == 2
    # time travel to the append snapshot
    sid = meta["snapshots"][1]["snapshot-id"]
    assert _rows(s.read.iceberg(p, snapshot_id=sid)) == [(1,), (2,), (3,)]


def test_iceberg_partitioned_write(tmp_path):
    import os as _os

    p = str(tmp_path / "t3")
    s = TpuSession({"spark.rapids.sql.enabled": True})
    schema = T.StructType([T.StructField("k", T.INT, False),
                           T.StructField("v", T.LONG, False)])
    df = s.create_dataframe({"k": [1, 2, 1, 2], "v": [10, 20, 30, 40]},
                            schema)
    df.write.partition_by("k").iceberg(p)
    assert _rows(s.read.iceberg(p)) == _rows(df)
    dirs = sorted(_os.listdir(_os.path.join(p, "data")))
    assert dirs == ["k=1", "k=2"], dirs


def test_iceberg_write_error_and_ignore(tmp_path):
    import pytest as _pt

    p = str(tmp_path / "t4")
    s = TpuSession({"spark.rapids.sql.enabled": True})
    schema = T.StructType([T.StructField("v", T.INT, False)])
    s.create_dataframe({"v": [1]}, schema).write.iceberg(p)
    # the writer's default mode is overwrite (matching the file writers);
    # explicit error/ignore modes follow Spark semantics
    with _pt.raises(FileExistsError):
        s.create_dataframe({"v": [2]}, schema).write.mode(
            "error").iceberg(p)
    s.create_dataframe({"v": [2]}, schema).write.mode("ignore").iceberg(p)
    assert _rows(s.read.iceberg(p)) == [(1,)]
