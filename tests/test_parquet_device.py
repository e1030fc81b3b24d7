"""Pallas Parquet device-decode tests (reference: parquet_test.py reader
modes + cuDF decode kernels)."""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, col, lit, sum_

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import (
    BooleanGen,
    DateGen,
    DoubleGen,
    IntegerGen,
    LongGen,
    TimestampGen,
    gen_df,
)

_CONF = {"spark.rapids.sql.format.parquet.decode.device": "true"}


def _write(tmp_path, s, codec="NONE", dict_on=True, n=2000, seed=5):
    import pyarrow.parquet as pq

    df = gen_df(s, [LongGen(), IntegerGen(min_val=0, max_val=30),
                    DoubleGen(), BooleanGen(), DateGen(),
                    TimestampGen.ns_safe()],
                ["a", "b", "c", "d", "e", "f"], length=n, seed=seed)
    p = str(tmp_path / f"t_{codec}_{dict_on}.parquet")
    import pyarrow as pa

    from spark_rapids_tpu.columnar.column import HostColumn

    data = {}
    for name, f in zip(df.schema.field_names(), df.schema.fields):
        vals = [r[df.schema.field_names().index(name)]
                for r in df.collect()]
        data[name] = HostColumn.from_pylist(vals, f.dataType).to_arrow()
    tbl = pa.table(data)
    pq.write_table(tbl, p, compression=codec, use_dictionary=dict_on,
                   data_page_version="1.0")
    return p, df.schema


@pytest.mark.parametrize("codec,dict_on", [("NONE", True), ("ZSTD", True),
                                           ("NONE", False),
                                           ("ZSTD", False)])
def test_device_decode_differential(tmp_path, codec, dict_on):
    s = TpuSession({"spark.rapids.sql.enabled": True})
    p, schema = _write(tmp_path, s, codec, dict_on)

    def build(sess):
        return sess.read.schema(schema).parquet(p)

    assert_tpu_and_cpu_are_equal_collect(build, conf=_CONF)


def test_device_decode_through_query(tmp_path):
    s = TpuSession({"spark.rapids.sql.enabled": True})
    p, schema = _write(tmp_path, s, "ZSTD", True, n=4000)

    def build(sess):
        df = sess.read.schema(schema).parquet(p)
        return df.filter(col("b") > lit(5)).group_by("b").agg(
            sum_("a", "sa"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=_CONF)


def test_snappy_falls_back_to_host(tmp_path):
    """Unsupported codec: silent per-file host fallback, same results."""
    s = TpuSession({"spark.rapids.sql.enabled": True})
    p, schema = _write(tmp_path, s, "SNAPPY", True)

    def build(sess):
        return sess.read.schema(schema).parquet(p)

    assert_tpu_and_cpu_are_equal_collect(build, conf=_CONF)


def test_decode_metric_counts_device_path(tmp_path):
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    **_CONF})
    p, schema = _write(tmp_path, s, "NONE", True)
    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    batch = read_parquet_device(p, schema)
    assert batch.num_rows == 2000


# -- round 3: dictionary string columns + data page v2 ----------------------


def _write_with_strings(tmp_path, s, page_version="1.0", codec="NONE",
                        n=1500):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from data_gen import StringGen
    from spark_rapids_tpu.columnar.column import HostColumn

    df = gen_df(s, [LongGen(), StringGen(min_len=0, max_len=12),
                    StringGen(min_len=1, max_len=4, charset="abc"),
                    IntegerGen(min_val=0, max_val=50)],
                ["a", "s1", "s2", "b"], length=n, seed=11)
    data = {}
    names = df.schema.field_names()
    rows = df.collect()
    for i, (name, f) in enumerate(zip(names, df.schema.fields)):
        data[name] = HostColumn.from_pylist(
            [r[i] for r in rows], f.dataType).to_arrow()
    p = str(tmp_path / f"s_{page_version}_{codec}.parquet")
    pq.write_table(pa.table(data), p, compression=codec,
                   use_dictionary=True, data_page_version=page_version)
    return p, df.schema


@pytest.mark.parametrize("page_version", ["1.0", "2.0"])
@pytest.mark.parametrize("codec", ["NONE", "ZSTD"])
def test_device_decode_strings(tmp_path, page_version, codec):
    s = TpuSession(dict(_CONF, **{"spark.rapids.sql.enabled": True}))
    p, schema = _write_with_strings(tmp_path, s, page_version, codec)

    def build(sess):
        return sess.read.schema(schema).parquet(p)

    assert_tpu_and_cpu_are_equal_collect(build, conf=_CONF)


def test_device_decode_strings_through_query(tmp_path):
    s = TpuSession(dict(_CONF, **{"spark.rapids.sql.enabled": True}))
    p, schema = _write_with_strings(tmp_path, s)

    def build(sess):
        from spark_rapids_tpu.session import count_

        return (sess.read.schema(schema).parquet(p)
                .filter(col("b") > lit(10))
                .group_by("s2").agg(count_(None, "c"), sum_("a", "sa")))

    assert_tpu_and_cpu_are_equal_collect(build, conf=_CONF)


def test_device_decode_strings_uses_device_path(tmp_path):
    """The string file must actually take the device decode — calling the
    device reader directly raises _Unsupported on any fallback path."""
    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    s = TpuSession(dict(_CONF, **{"spark.rapids.sql.enabled": True}))
    p, schema = _write_with_strings(tmp_path, s)
    batch = read_parquet_device(p, schema)
    assert batch.num_rows == 1500
    scol = batch.columns[1]
    assert scol.is_string and scol.chars is not None


@pytest.mark.parametrize("page_version", ["1.0", "2.0"])
def test_device_decode_v2_pages_numerics(tmp_path, page_version):
    s = TpuSession(dict(_CONF, **{"spark.rapids.sql.enabled": True}))
    p, schema = _write(tmp_path, s)
    # rewrite with the requested page version
    import pyarrow.parquet as pq

    tbl = pq.read_table(p)
    p2 = str(tmp_path / f"v2_{page_version}.parquet")
    pq.write_table(tbl, p2, compression="NONE", use_dictionary=True,
                   data_page_version=page_version)

    def build(sess):
        return sess.read.schema(schema).parquet(p2).filter(
            col("b") > lit(5))

    assert_tpu_and_cpu_are_equal_collect(build, conf=_CONF)


# -- round 4: snappy + PLAIN byte_array pages ----------


def test_snappy_plain_string_pages(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    strs = ["alpha", None, "", "beéta", "y" * 33] * 60
    tbl = pa.table({"s": pa.array(strs, pa.string()),
                    "v": pa.array(range(300), pa.int64())})
    p = str(tmp_path / "sp.parquet")
    pq.write_table(tbl, p, compression="snappy", use_dictionary=False)
    schema = T.StructType([T.StructField("s", T.STRING, True),
                           T.StructField("v", T.LONG, False)])
    b = read_parquet_device(p, schema)
    host = b.columns[0].to_host(b.num_rows).to_pylist()
    assert host == strs
    assert b.columns[1].to_host(b.num_rows).to_pylist() == list(range(300))


def test_snappy_numeric_pages(tmp_path):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.parquet_device import read_parquet_device

    rng = np.random.default_rng(3)
    vals = rng.integers(-10**9, 10**9, 4000)
    fl = rng.random(4000)
    tbl = pa.table({"i": pa.array(vals, pa.int64()),
                    "f": pa.array(fl, pa.float64())})
    p = str(tmp_path / "sn.parquet")
    pq.write_table(tbl, p, compression="snappy")
    schema = T.StructType([T.StructField("i", T.LONG, False),
                           T.StructField("f", T.DOUBLE, False)])
    b = read_parquet_device(p, schema)
    import numpy as np2
    got = np2.asarray(b.columns[0].data)[:4000]
    assert (got == vals).all()


def test_snappy_through_scan_session(tmp_path):
    """The full scan path decodes a snappy file on device and matches
    the oracle."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect

    tbl = pa.table({"k": pa.array([1, 2, 1, 3, 2] * 40, pa.int32()),
                    "s": pa.array(["a", "bb", None, "dd", "e"] * 40,
                                  pa.string())})
    p = str(tmp_path / "scan.parquet")
    pq.write_table(tbl, p, compression="snappy", use_dictionary=False)

    def build(s):
        return s.read.parquet(p)

    assert_tpu_and_cpu_are_equal_collect(build)
