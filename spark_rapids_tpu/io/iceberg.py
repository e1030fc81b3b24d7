"""Iceberg table scan (read path) — the GpuIcebergParquetReader analog.

Reference analog: iceberg/ module (SURVEY.md §2.8, MED): the reference
accelerates Iceberg's parquet data-file reads.  This module walks the open
Iceberg v1/v2 table metadata directly: ``metadata/version-hint.text`` (or
the highest ``vN.metadata.json``), current snapshot -> manifest LIST
(Avro) -> manifests (Avro) -> live parquet data files; the engine's
regular parquet scan reads the data.

Supported subset: parquet data files, flat primitive schemas, v2
position deletes (file_path/pos parquet files, applied while assembling
the scan) and equality deletes (applied as device ANTI joins against the
delete rows).  Limits: equality deletes apply to the whole snapshot —
sequence-number scoping (re-inserts after a delete) is not implemented
and such tables read incorrectly (undetected); null values in equality
delete rows raise (the anti join cannot match null==null).
"""
from __future__ import annotations

import json
import os
import re
from typing import List, Optional, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.io.avro import read_avro_file

_PRIMS = {
    "boolean": T.BOOLEAN, "int": T.INT, "long": T.LONG, "float": T.FLOAT,
    "double": T.DOUBLE, "string": T.STRING, "date": T.DATE,
    "timestamp": T.TIMESTAMP, "timestamptz": T.TIMESTAMP,
    "binary": T.BINARY,
}


def _field_type(t) -> T.DataType:
    if isinstance(t, str):
        if t in _PRIMS:
            return _PRIMS[t]
        m = re.match(r"decimal\((\d+),\s*(\d+)\)", t)
        if m:
            return T.DecimalType(int(m.group(1)), int(m.group(2)))
        raise ValueError(f"unsupported iceberg type {t!r}")
    if isinstance(t, dict) and t.get("type") == "list":
        return T.ArrayType(_field_type(t["element"]),
                           not t.get("element-required", False))
    raise ValueError(f"unsupported iceberg type {t!r}")


def _schema_from_metadata(meta: dict) -> T.StructType:
    schemas = meta.get("schemas")
    if schemas:
        sid = meta.get("current-schema-id", 0)
        schema = next((s for s in schemas if s.get("schema-id") == sid),
                      schemas[-1])
    else:
        schema = meta["schema"]  # v1 single-schema layout
    return T.StructType([
        T.StructField(f["name"], _field_type(f["type"]),
                      not f.get("required", False))
        for f in schema["fields"]])


def _resolve(table_path: str, p: str) -> str:
    """Manifest paths may be absolute file URIs or table-relative."""
    if p.startswith("file://"):
        return p[len("file://"):]
    if os.path.isabs(p):
        return p
    return os.path.join(table_path, p)


def _latest_metadata(table_path: str) -> str:
    mdir = os.path.join(table_path, "metadata")
    hint = os.path.join(mdir, "version-hint.text")
    if os.path.isfile(hint):
        with open(hint) as f:
            v = f.read().strip()
        cand = os.path.join(mdir, f"v{v}.metadata.json")
        if os.path.isfile(cand):
            return cand
    best: Tuple[int, Optional[str]] = (-1, None)
    for name in os.listdir(mdir):
        m = re.match(r"v(\d+)\.metadata\.json$", name)
        if m and int(m.group(1)) > best[0]:
            best = (int(m.group(1)), os.path.join(mdir, name))
    if best[1] is None:
        raise FileNotFoundError(
            f"{table_path}: no iceberg metadata json found")
    return best[1]


def _field_id_names(meta: dict) -> dict:
    schemas = meta.get("schemas")
    if schemas:
        sid = meta.get("current-schema-id", 0)
        schema = next((s for s in schemas if s.get("schema-id") == sid),
                      schemas[-1])
    else:
        schema = meta["schema"]
    return {f["id"]: f["name"] for f in schema["fields"] if "id" in f}


def iceberg_data_files(table_path: str,
                       snapshot_id: Optional[int] = None):
    """-> (live data paths, position-delete paths, equality deletes as
    (path, [column names]) pairs, table schema)."""
    with open(_latest_metadata(table_path)) as f:
        meta = json.load(f)
    schema = _schema_from_metadata(meta)
    id_names = _field_id_names(meta)
    snaps = meta.get("snapshots", [])
    if not snaps:
        return [], [], [], schema
    sid = snapshot_id if snapshot_id is not None \
        else meta.get("current-snapshot-id")
    snap = next((s for s in snaps if s.get("snapshot-id") == sid),
                snaps[-1])
    from spark_rapids_tpu.io.faults import file_context

    mlist = _resolve(table_path, snap["manifest-list"])
    with file_context(mlist, "avro", "iceberg-manifest-list"):
        _, entries = read_avro_file(mlist)
    paths: List[str] = []
    pos_deletes: List[str] = []
    eq_deletes: List[Tuple[str, List[str]]] = []
    for entry in entries:
        mpath = _resolve(table_path, entry["manifest_path"])
        # metadata corruption is never tolerated away (skipping a
        # manifest silently drops an unknowable file set) — it only
        # gains file attribution here
        with file_context(mpath, "avro", "iceberg-manifest"):
            _, files = read_avro_file(mpath)
        for fe in files:
            status = fe.get("status", 1)
            if status == 2:  # DELETED
                continue
            df = fe["data_file"]
            fmt = (df.get("file_format") or "PARQUET")
            if str(fmt).upper() != "PARQUET":
                raise ValueError(f"iceberg {fmt} data files not supported")
            content = df.get("content") or 0
            fp = _resolve(table_path, df["file_path"])
            if content == 0:
                paths.append(fp)
            elif content == 1:  # position deletes
                pos_deletes.append(fp)
            elif content == 2:  # equality deletes
                ids = df.get("equality_ids") or []
                names = [id_names[i] for i in ids if i in id_names]
                if not names:
                    raise ValueError(
                        "iceberg equality delete without resolvable "
                        "equality_ids")
                eq_deletes.append((fp, names))
            else:
                raise ValueError(f"iceberg delete content {content}")
    # manifests replay newest-first; drop duplicates, keep order
    def uniq(seq):
        seen = set()
        out = []
        for x in seq:
            key = x if isinstance(x, str) else x[0]
            if key not in seen:
                seen.add(key)
                out.append(x)
        return out

    return uniq(paths), uniq(pos_deletes), uniq(eq_deletes), schema


def _apply_position_deletes(session, paths, pos_delete_paths, schema):
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.mor import read_parquet_minus_rows

    from spark_rapids_tpu.io.faults import file_context

    dropped = {}
    for dp in pos_delete_paths:
        # delete files are MOR metadata: never tolerated away (skipping
        # one would resurrect deleted rows) — attributed only
        with file_context(dp, "parquet", "iceberg-position-deletes"):
            t = pq.read_table(dp)
        for fp, pos in zip(t.column("file_path").to_pylist(),
                           t.column("pos").to_pylist()):
            dropped.setdefault(_norm_path(fp), set()).add(int(pos))
    return read_parquet_minus_rows(
        session, [(p, dropped.get(_norm_path(p))) for p in paths], schema)


def _norm_path(p: str) -> str:
    return p[len("file://"):] if p.startswith("file://") else p


def read_iceberg(session, table_path: str,
                 snapshot_id: Optional[int] = None):
    paths, pos_del, eq_del, schema = iceberg_data_files(
        table_path, snapshot_id)
    if not paths:
        return session.create_dataframe(
            {f.name: [] for f in schema.fields}, schema)
    if pos_del:
        df = _apply_position_deletes(session, paths, pos_del, schema)
    else:
        df = session.read.schema(schema).parquet(*paths)
    # equality deletes: device ANTI join against the delete rows (the
    # engine-join design Delta MERGE uses)
    for dp, names in eq_del:
        import pyarrow.parquet as pq

        from spark_rapids_tpu.io.faults import file_context

        with file_context(dp, "parquet", "iceberg-equality-deletes"):
            t = pq.read_table(dp, columns=names)
        dschema = T.StructType(
            [f for f in schema.fields if f.name in names])
        data = {f.name: t.column(f.name).to_pylist()
                for f in dschema.fields}
        if any(v is None for vals in data.values() for v in vals):
            # the spec matches null==null in equality deletes; the anti
            # join cannot, so reject rather than silently keep the rows
            raise ValueError(
                "iceberg equality deletes with null values are not "
                "supported")
        ddf = session.create_dataframe(data, dschema)
        df = df.join(ddf, on=names, how="left_anti")
    return df


# ---------------------------------------------------------------------------
# Write path.  Reference analog: the reference's
# Iceberg module is read-only too in most branches; Spark's Iceberg writes
# go through the iceberg-spark runtime (SURVEY.md §2.8 Iceberg).  This
# implements format-version-2 append/overwrite commits from scratch:
# data parquet files + manifest avro + manifest-list avro + metadata json,
# all round-tripping through this module's own reader and avro codec.
# ---------------------------------------------------------------------------

_ICEBERG_TYPE = {
    "BooleanType": "boolean", "IntegerType": "int", "LongType": "long",
    "FloatType": "float", "DoubleType": "double", "StringType": "string",
    "DateType": "date", "TimestampType": "timestamptz",
    "ByteType": "int", "ShortType": "int",
}


def _type_to_iceberg(dt) -> str:
    if isinstance(dt, T.DecimalType):
        return f"decimal({dt.precision}, {dt.scale})"
    name = type(dt).__name__
    if name not in _ICEBERG_TYPE:
        raise ValueError(f"iceberg write: unsupported type {dt.simpleString}")
    return _ICEBERG_TYPE[name]


def _schema_json(schema: T.StructType) -> dict:
    return {"type": "struct", "schema-id": 0,
            "fields": [{"id": i + 1, "name": f.name,
                        "required": not f.nullable,
                        "type": _type_to_iceberg(f.dataType)}
                       for i, f in enumerate(schema.fields)]}


_MANIFEST_SCHEMA = {
    "type": "record", "name": "manifest_entry", "fields": [
        {"name": "status", "type": "int"},
        {"name": "snapshot_id", "type": ["null", "long"], "default": None},
        {"name": "data_file", "type": {
            "type": "record", "name": "r2", "fields": [
                {"name": "content", "type": "int"},
                {"name": "file_path", "type": "string"},
                {"name": "file_format", "type": "string"},
                {"name": "record_count", "type": "long"},
                {"name": "file_size_in_bytes", "type": "long"},
            ]}},
    ]}

_MANIFEST_LIST_SCHEMA = {
    "type": "record", "name": "manifest_file", "fields": [
        {"name": "manifest_path", "type": "string"},
        {"name": "manifest_length", "type": "long"},
        {"name": "partition_spec_id", "type": "int"},
        {"name": "added_snapshot_id", "type": "long"},
    ]}


def write_iceberg(df, table_path: str, mode: str = "error",
                  partition_by=None) -> int:
    """Write a DataFrame as an iceberg v2 commit; returns the snapshot id.

    modes: error/ignore/append/overwrite.  ``partition_by`` uses identity
    transforms; data files land under data/<col>=<value>/ and the spec is
    recorded in the metadata (the scan reads files regardless of
    partition layout)."""
    import time
    import uuid

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.delta.table import _df_to_arrow
    from spark_rapids_tpu.io.avro import write_avro_file

    mdir = os.path.join(table_path, "metadata")
    ddir = os.path.join(table_path, "data")
    exists = os.path.isdir(mdir) and any(
        re.match(r"v(\d+)\.metadata\.json$", n)
        for n in os.listdir(mdir)) if os.path.isdir(mdir) else False
    if exists and mode in ("error", "errorifexists"):
        raise FileExistsError(f"iceberg table already exists: {table_path}")
    if exists and mode == "ignore":
        with open(_latest_metadata(table_path)) as f:
            return json.load(f).get("current-snapshot-id", -1)
    os.makedirs(mdir, exist_ok=True)
    os.makedirs(ddir, exist_ok=True)

    meta = None
    version = 0
    if exists:
        mpath = _latest_metadata(table_path)
        version = int(re.match(r"v(\d+)\.metadata\.json$",
                               os.path.basename(mpath)).group(1))
        with open(mpath) as f:
            meta = json.load(f)

    tbl = _df_to_arrow(df)
    snapshot_id = int(uuid.uuid4().int % (1 << 62))
    now_ms = int(time.time() * 1000)
    part_cols = list(partition_by or [])

    # -- data files (hive-style dirs for identity partitions) ----------
    data_files = []

    def _write_part(sub_tbl, subdir):
        os.makedirs(subdir, exist_ok=True)
        fp = os.path.join(subdir, f"{uuid.uuid4().hex}.parquet")
        pq.write_table(sub_tbl, fp)
        data_files.append({
            "status": 1, "snapshot_id": snapshot_id,
            "data_file": {
                "content": 0, "file_path": fp,
                "file_format": "PARQUET",
                "record_count": sub_tbl.num_rows,
                "file_size_in_bytes": os.path.getsize(fp)}})

    if tbl.num_rows:
        if part_cols:
            import pyarrow.compute as pc

            keys = [tbl.column(c) for c in part_cols]
            combos = {tuple(row) for row in zip(
                *[k.to_pylist() for k in keys])}
            for combo in sorted(combos, key=lambda t: tuple(map(str, t))):
                mask = None
                for c, v in zip(part_cols, combo):
                    m = (pc.is_null(tbl.column(c)) if v is None
                         else pc.equal(tbl.column(c), pa.scalar(v)))
                    mask = m if mask is None else pc.and_(mask, m)
                sub = tbl.filter(mask)
                subdir = os.path.join(ddir, *[
                    f"{c}={'null' if v is None else v}"
                    for c, v in zip(part_cols, combo)])
                _write_part(sub, subdir)
        else:
            _write_part(tbl, ddir)

    # -- manifest + manifest list --------------------------------------
    manifest_path = os.path.join(
        mdir, f"manifest-{uuid.uuid4().hex}.avro")
    write_avro_file(manifest_path, _MANIFEST_SCHEMA, data_files)
    manifests = [{"manifest_path": manifest_path,
                  "manifest_length": os.path.getsize(manifest_path),
                  "partition_spec_id": 0,
                  "added_snapshot_id": snapshot_id}]
    if meta is not None and mode == "append":
        cur = next((s for s in meta.get("snapshots", [])
                    if s.get("snapshot-id")
                    == meta.get("current-snapshot-id")), None)
        if cur is not None:
            old_list = _resolve(table_path, cur["manifest-list"])
            _, old = read_avro_file(old_list)
            manifests = list(old) + manifests
    mlist_path = os.path.join(
        mdir, f"snap-{snapshot_id}-{uuid.uuid4().hex}.avro")
    write_avro_file(mlist_path, _MANIFEST_LIST_SCHEMA, manifests)

    # -- metadata json v2 ----------------------------------------------
    schema_json = _schema_json(df.schema)
    name_to_id = {f["name"]: f["id"] for f in schema_json["fields"]}
    spec = {"spec-id": 0, "fields": [
        {"name": c, "transform": "identity",
         "source-id": name_to_id[c], "field-id": 1000 + i}
        for i, c in enumerate(part_cols)]}
    snapshot = {"snapshot-id": snapshot_id,
                "timestamp-ms": now_ms,
                "sequence-number": (meta or {}).get(
                    "last-sequence-number", 0) + 1,
                "summary": {"operation":
                            "append" if mode == "append" else "overwrite"},
                "manifest-list": mlist_path,
                "schema-id": 0}
    snapshots = list((meta or {}).get("snapshots", [])) \
        if mode == "append" and meta is not None else []
    if meta is not None and mode == "overwrite":
        snapshots = list(meta.get("snapshots", []))
    snapshots.append(snapshot)
    new_meta = {
        "format-version": 2,
        "table-uuid": (meta or {}).get("table-uuid",
                                       str(uuid.uuid4())),
        "location": table_path,
        "last-sequence-number": snapshot["sequence-number"],
        "last-updated-ms": now_ms,
        "last-column-id": len(schema_json["fields"]),
        "schemas": [schema_json],
        "current-schema-id": 0,
        "partition-specs": [spec],
        "default-spec-id": 0,
        "last-partition-id": 999 + len(part_cols),
        "sort-orders": [{"order-id": 0, "fields": []}],
        "default-sort-order-id": 0,
        "properties": {},
        "snapshots": snapshots,
        "current-snapshot-id": snapshot_id,
    }
    out_path = os.path.join(mdir, f"v{version + 1}.metadata.json")
    with open(out_path, "w") as f:
        json.dump(new_meta, f)
    with open(os.path.join(mdir, "version-hint.text"), "w") as f:
        f.write(str(version + 1))
    return snapshot_id
