"""Spark-strict CSV/JSON parse semantics (reference: csv_test.py,
json_test.py — PERMISSIVE / _corrupt_record / malformed handling)."""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, col, lit

from asserts import assert_tpu_and_cpu_are_equal_collect


def _schema(*fields):
    return T.StructType([T.StructField(n, t, True) for n, t in fields])


def _write(tmp_path, name, text):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        f.write(text)
    return p


CSV_BODY = """1,abc,1.5,true,2020-05-06
2,def,,false,2020-5-7
3,ghi,2.5,TRUE,bad-date
notanint,jkl,3.5,true,2020-01-01
4,mno,4.5,yes,2020-01-02
5,"quo,ted",5.5,false,2020-01-03
6,short
7,extra,1.0,true,2020-01-04,surplus
8,ok,inf,false,2020-01-05
"""

CSV_SCHEMA = _schema(("i", T.INT), ("s", T.STRING), ("d", T.DOUBLE),
                     ("b", T.BOOLEAN), ("dt", T.DATE),
                     ("_corrupt_record", T.STRING))


def test_csv_permissive_corrupt_record(tmp_path):
    path = _write(tmp_path, "t.csv", CSV_BODY)

    def build(s):
        return s.read.schema(CSV_SCHEMA).csv(path)

    assert_tpu_and_cpu_are_equal_collect(build)
    # pinned PERMISSIVE expectations
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert len(rows) == 9  # every physical record lands (PERMISSIVE)
    by_s = {r[1]: r for r in rows}
    assert by_s["abc"][5] is None                   # clean row
    assert by_s["ghi"][4] is None                   # bad date -> null field
    assert by_s["ghi"][5] is not None               # ...row marked corrupt
    assert by_s["jkl"][0] is None                   # bad int -> null field
    assert by_s["jkl"][5].startswith("notanint")    # corrupt keeps raw
    assert by_s["mno"][3] is None                   # 'yes' is not a bool
    assert by_s["quo,ted"][0] == 5                  # quoting respected
    assert by_s["short"][5] is not None             # token undercount
    assert by_s["def"][2] is None                   # empty token -> null
    assert by_s["ok"][2] == float("inf")


def test_csv_dropmalformed(tmp_path):
    path = _write(tmp_path, "t.csv", CSV_BODY)

    def build(s):
        return (s.read.schema(CSV_SCHEMA)
                .option("mode", "DROPMALFORMED").csv(path))

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert {r[1] for r in rows} == {"abc", "def", "quo,ted", "ok"}


def test_csv_failfast(tmp_path):
    path = _write(tmp_path, "t.csv", CSV_BODY)
    s = TpuSession({"spark.rapids.sql.enabled": True})
    with pytest.raises(RuntimeError, match="FAILFAST"):
        s.read.schema(CSV_SCHEMA).option("mode", "FAILFAST") \
            .csv(path).collect()


def test_csv_header_and_sep(tmp_path):
    path = _write(tmp_path, "t.csv", "i|s\n1|x\n2|y\n")
    sch = _schema(("i", T.INT), ("s", T.STRING))

    def build(s):
        return (s.read.schema(sch).option("header", "true")
                .option("sep", "|").csv(path))

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert rows == [(1, "x"), (2, "y")]


def test_csv_int_overflow_is_malformed(tmp_path):
    path = _write(tmp_path, "t.csv", "5000000000\n12\n")
    sch = _schema(("i", T.INT), ("_corrupt_record", T.STRING))

    def build(s):
        return s.read.schema(sch).csv(path)

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert rows[0][0] is None and rows[0][1] == "5000000000"
    assert rows[1] == (12, None)


JSON_BODY = """{"i": 1, "s": "abc", "d": 1.5, "b": true}
{"i": 2, "s": "def"}
{"i": "notanint", "s": "ghi", "d": 2.5}
not json at all
{"i": 4, "s": 5, "d": "str-not-num", "b": "true"}
[1, 2, 3]
{"i": 2147483648, "s": "ovf"}
"""

JSON_SCHEMA = _schema(("i", T.INT), ("s", T.STRING), ("d", T.DOUBLE),
                      ("b", T.BOOLEAN), ("_corrupt_record", T.STRING))


def test_json_permissive_corrupt_record(tmp_path):
    path = _write(tmp_path, "t.json", JSON_BODY)

    def build(s):
        return s.read.schema(JSON_SCHEMA).json(path)

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert len(rows) == 7
    assert rows[0] == (1, "abc", 1.5, True, None)
    assert rows[1] == (2, "def", None, None, None)       # missing -> null
    assert rows[2][0] is None                            # wrong type
    assert rows[2][4] is None                            # field-level only
    assert rows[3][4] == "not json at all"               # syntactic corrupt
    assert rows[4][1] == "5"                             # number -> string
    assert rows[4][3] is None                            # "true" str != bool
    assert rows[5][4] == "[1, 2, 3]"                     # non-object corrupt
    assert rows[6][0] is None                            # int32 overflow


def test_json_dropmalformed(tmp_path):
    path = _write(tmp_path, "t.json", JSON_BODY)

    def build(s):
        return (s.read.schema(JSON_SCHEMA)
                .option("mode", "DROPMALFORMED").json(path))

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert len(rows) == 5


def test_json_failfast(tmp_path):
    path = _write(tmp_path, "t.json", JSON_BODY)
    s = TpuSession({"spark.rapids.sql.enabled": True})
    with pytest.raises(RuntimeError, match="FAILFAST"):
        s.read.schema(JSON_SCHEMA).option("mode", "FAILFAST") \
            .json(path).collect()


def test_csv_date_timestamp_cast_grammar(tmp_path):
    path = _write(tmp_path, "t.csv",
                  "2020-05-06,2020-05-06 11:12:13.5\n"
                  "2020-5-7,2020-5-7T1:2:3\n")
    sch = _schema(("d", T.DATE), ("ts", T.TIMESTAMP))

    def build(s):
        return s.read.schema(sch).csv(path)

    assert_tpu_and_cpu_are_equal_collect(build)


def test_csv_pipeline_through_query(tmp_path):
    """The parsed scan composes with filters/aggregates on device."""
    from spark_rapids_tpu.session import sum_

    lines = "\n".join(f"{i % 7},{i}" for i in range(500)) + "\nbad,row\n"
    path = _write(tmp_path, "t.csv", lines)
    sch = _schema(("k", T.INT), ("v", T.LONG))

    def build(s):
        return (s.read.schema(sch).csv(path)
                .filter(col("v") > lit(100))
                .group_by("k").agg(sum_("v", "sv")))

    assert_tpu_and_cpu_are_equal_collect(build)


def test_csv_inference_honors_sep_and_headerless(tmp_path):
    path = _write(tmp_path, "t.csv", "10;x\n20;y\n")

    def build(s):
        return s.read.option("sep", ";").option("header", "false").csv(path)

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert rows == [(10, "x"), (20, "y")]


def test_csv_blank_lines_dropped(tmp_path):
    path = _write(tmp_path, "t.csv", "a,1\n\nb,2\n")
    sch = _schema(("s", T.STRING), ("i", T.INT))

    def build(s):
        return s.read.schema(sch).csv(path)

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert rows == [("a", 1), ("b", 2)]


def test_csv_corrupt_record_keeps_raw_quoting(tmp_path):
    path = _write(tmp_path, "t.csv", '"x,y",oops\n"p",3\n')
    sch = _schema(("s", T.STRING), ("i", T.INT),
                  ("_corrupt_record", T.STRING))

    def build(s):
        return s.read.schema(sch).csv(path)

    assert_tpu_and_cpu_are_equal_collect(build)
    rows = build(TpuSession({"spark.rapids.sql.enabled": True})).collect()
    assert rows[0][2] == '"x,y",oops'   # original quoting preserved
    assert rows[1] == ("p", 3, None)


def test_iceberg_equality_delete_nulls_rejected(tmp_path):
    import pyarrow as pa

    import sys
    sys.path.insert(0, "tests")
    from test_iceberg import _add_delete_file, _build_iceberg_table, _frames

    p = str(tmp_path / "tbl")
    _build_iceberg_table(p, _frames())
    dele = pa.table({"v": pa.array([None, 10], pa.int64())})
    _add_delete_file(p, "del-eq.parquet", dele, content=2,
                     equality_ids=[2])
    s = TpuSession({"spark.rapids.sql.enabled": True})
    with pytest.raises(ValueError, match="null values"):
        s.read.iceberg(p)


# -- round 4: vectorized fast path ---------------------


def _both_paths(path, schema, options):
    from spark_rapids_tpu.io.text import read_csv_spark

    fast = read_csv_spark(path, schema, dict(options))
    strict = read_csv_spark(path, schema,
                            dict(options, tpuFastParse="false"))
    return fast, strict


def _rows_of(cols_n):
    cols, n = cols_n
    return [tuple(c.to_pylist()[i] for c in cols) for i in range(n)]


def test_csv_fast_path_differential(tmp_path):
    """The vectorized fast path is bit-identical to the strict loop on a
    file mixing clean rows with every uncertain-grammar case."""
    import random

    from spark_rapids_tpu import types as T

    rng = random.Random(42)
    toks = ["1", "-7", "+00012", "2147483648", "  33 ", "4.5", "1e3",
            "", "abc", "true", "１２", "999999999999999999999", "0.07",
            "-12.345", "2023-01-31", "2023-2-3", "2023-02-31", "inf",
            "1_000", ".5", "5.", "12.999", "-0.005"]
    lines = []
    for _ in range(300):
        lines.append(",".join(rng.choice(toks) for _ in range(5)))
    p = tmp_path / "fuzz.csv"
    p.write_text("\n".join(lines) + "\n")
    schema = T.StructType([
        T.StructField("i", T.INT, True),
        T.StructField("l", T.LONG, True),
        T.StructField("d", T.DOUBLE, True),
        T.StructField("dec", T.DecimalType(10, 2), True),
        T.StructField("dt", T.DATE, True),
        T.StructField("_corrupt_record", T.STRING, True),
    ])
    for mode in ("PERMISSIVE", "DROPMALFORMED"):
        fast, strict = _both_paths(str(p), schema, {"mode": mode})
        assert _rows_of(fast) == _rows_of(strict), mode


def test_csv_fast_path_quoted_and_ragged(tmp_path):
    """Quoted fields parse identically; ragged rows force the strict loop
    and still agree."""
    from spark_rapids_tpu import types as T

    p = tmp_path / "q.csv"
    p.write_text('1,"a,b",2.5\n2,"x""y",7\n3,plain,9\n')
    schema = T.StructType([
        T.StructField("i", T.INT, True),
        T.StructField("s", T.STRING, True),
        T.StructField("d", T.DOUBLE, True)])
    fast, strict = _both_paths(str(p), schema, {})
    assert _rows_of(fast) == _rows_of(strict)
    p2 = tmp_path / "ragged.csv"
    p2.write_text("1,a,2\n5,b\n3,c,4,extra\n")
    fast, strict = _both_paths(str(p2), schema, {})
    assert _rows_of(fast) == _rows_of(strict)


def test_csv_fast_path_throughput(tmp_path):
    """2M-row clean numeric CSV parses within 5x of pyarrow's own typed
    parse."""
    import time

    import numpy as np
    import pyarrow.csv as pacsv

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.io.text import read_csv_spark

    n = 2_000_000
    rng = np.random.default_rng(0)
    import io
    buf = io.StringIO()
    a = rng.integers(0, 10**6, n)
    b = rng.integers(-50, 50, n)
    c = rng.random(n).round(6)
    np.savetxt(buf, np.column_stack([a, b, c]),
               fmt="%d,%d,%.6f", delimiter=",")
    p = tmp_path / "big.csv"
    p.write_text(buf.getvalue())
    schema = T.StructType([
        T.StructField("a", T.LONG, True),
        T.StructField("b", T.INT, True),
        T.StructField("c", T.DOUBLE, True)])
    t0 = time.perf_counter()
    pacsv.read_csv(str(p))
    t_pa = time.perf_counter() - t0
    t0 = time.perf_counter()
    cols, cnt = read_csv_spark(str(p), schema, {})
    t_fast = time.perf_counter() - t0
    assert cnt == n
    assert int(np.asarray(cols[0].data)[:5].sum()) == int(a[:5].sum())
    assert t_fast <= max(t_pa * 5, 2.0), (t_fast, t_pa)


def test_json_fast_path_differential(tmp_path):
    """The arrow JSON tier agrees with the strict loop on clean files;
    dirty files (coercions, bad lines) fall back and still agree."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.io.text import read_json_spark

    schema = T.StructType([
        T.StructField("i", T.INT, True),
        T.StructField("l", T.LONG, True),
        T.StructField("d", T.DOUBLE, True),
        T.StructField("s", T.STRING, True),
        T.StructField("b", T.BOOLEAN, True)])
    clean = tmp_path / "clean.json"
    clean.write_text(
        '{"i": 1, "l": 2, "d": 1.5, "s": "x", "b": true}\n'
        '{"i": null, "d": -2e3, "s": "y", "b": false}\n'
        '{"i": 2147483648, "l": 99, "s": "z"}\n')
    dirty = tmp_path / "dirty.json"
    dirty.write_text(
        '{"i": 1.5, "l": "nope", "d": true, "s": 42, "b": 1}\n'
        'not json at all\n'
        '{"i": 3}\n')
    for p in (clean, dirty):
        fast = read_json_spark(str(p), schema, {})
        strict = read_json_spark(str(p), schema, {"tpuFastParse": "false"})
        fr = [tuple(c.to_pylist()[k] for c in fast[0])
              for k in range(fast[1])]
        sr = [tuple(c.to_pylist()[k] for c in strict[0])
              for k in range(strict[1])]
        assert fr == sr, (p, fr, sr)
