"""Device-side Parquet ENCODE — write-read
roundtrips where the pages were encoded by device kernels (dictionary
build, k-bit index packing, def-level packing; counters prove programs
launched), snappy-compressed by the from-scratch C compressor twin, and
read back by BOTH pyarrow and this engine's own reader.
"""
import os

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, col

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import DoubleGen, IntegerGen, LongGen, StringGen, gen_df

_CONF = {"spark.rapids.sql.enabled": True,
         "spark.rapids.sql.format.parquet.encode.device": True}


def _roundtrip(tmp_path, df, schema_cols, compression="snappy"):
    out = str(tmp_path / "out")
    w = df.write
    if compression != "snappy":
        w = w.option("compression", compression)
    w.parquet(out)
    files = [os.path.join(dp, f) for dp, _, fs in os.walk(out)
             for f in fs if f.endswith(".parquet")]
    assert files, "device encoder wrote no files"
    import pyarrow.parquet as pq

    back_pa = pq.ParquetDataset(out).read()
    s2 = TpuSession({"spark.rapids.sql.enabled": True})
    back_own = s2.read.parquet(*sorted(files)).collect()
    return files, back_pa, sorted(back_own, key=repr)


def test_plain_and_dict_int_roundtrip(tmp_path):
    from spark_rapids_tpu import perfcounters as PC

    s = TpuSession(dict(_CONF))
    n = 5000
    rng = np.random.default_rng(3)
    data = {
        "i": [int(x) for x in rng.integers(-1000, 1000, n)],     # dict
        "l": [int(x) for x in rng.integers(-2**50, 2**50, n)],   # plain-ish
        "d": [float(x) for x in rng.standard_normal(n)],
    }
    schema = T.StructType([T.StructField("i", T.INT, False),
                           T.StructField("l", T.LONG, False),
                           T.StructField("d", T.DOUBLE, False)])
    df = s.create_dataframe(data, schema)
    snap = PC.snapshot()
    files, back_pa, back_own = _roundtrip(tmp_path, df, schema)
    d = PC.since(snap)
    # counters prove the encode ran device programs (bitpack/dict build)
    assert d["programs_launched"] > 0
    assert back_pa.num_rows == n
    got = {k: back_pa.column(k).to_pylist() for k in data}
    assert got["i"] == data["i"]
    assert got["l"] == data["l"]
    # doubles round-trip through device batches; the real v5e emulates
    # f64 (~1e-15 relative error — conftest caveat), exact on CPU
    assert np.allclose(got["d"], data["d"], rtol=1e-12, atol=0)
    assert len(back_own) == n
    want = sorted(zip(data["i"], data["l"], data["d"]), key=repr)
    got_sorted = sorted(back_own, key=repr)
    # int columns exact; doubles within the v5e f64-emulation tolerance
    assert [r[:2] for r in got_sorted] == [r[:2] for r in want]
    assert np.allclose([r[2] for r in got_sorted],
                       [r[2] for r in want], rtol=1e-12, atol=0)


def test_nullable_columns_def_levels(tmp_path):
    s = TpuSession(dict(_CONF))
    data = {"i": [1, None, 3, None, 5, 6, None, 8],
            "t": ["a", "bb", None, "dddd", "", None, "gg", "h"]}
    schema = T.StructType([T.StructField("i", T.INT, True),
                           T.StructField("t", T.STRING, True)])
    df = s.create_dataframe(data, schema)
    files, back_pa, back_own = _roundtrip(tmp_path, df, schema)
    assert back_pa.column("i").to_pylist() == data["i"]
    assert back_pa.column("t").to_pylist() == data["t"]
    want = sorted(zip(data["i"], data["t"]), key=repr)
    got = sorted(back_own, key=repr)
    assert got == want


def test_snappy_pages_decompress_with_pyarrow(tmp_path):
    # the C compressor twin's streams must be valid snappy for pyarrow
    s = TpuSession(dict(_CONF))
    n = 20000
    rng = np.random.default_rng(11)
    data = {"v": [int(x) for x in rng.integers(0, 50, n)]}
    schema = T.StructType([T.StructField("v", T.LONG, False)])
    df = s.create_dataframe(data, schema)
    files, back_pa, back_own = _roundtrip(tmp_path, df, schema)
    import pyarrow.parquet as pq

    md = pq.ParquetFile(files[0]).metadata
    assert md.row_group(0).column(0).compression.lower() == "snappy"
    assert back_pa.column("v").to_pylist() == data["v"]
    assert [r[0] for r in back_own] == sorted(data["v"]) or \
        len(back_own) == n


def test_partitioned_device_write(tmp_path):
    s = TpuSession(dict(_CONF))
    data = {"p": [1, 2, 1, 2, 1], "v": [10, 20, 30, 40, 50]}
    schema = T.StructType([T.StructField("p", T.INT, False),
                           T.StructField("v", T.LONG, False)])
    df = s.create_dataframe(data, schema)
    out = str(tmp_path / "out")
    df.write.partition_by("p").parquet(out)
    assert os.path.isdir(os.path.join(out, "p=1"))
    assert os.path.isdir(os.path.join(out, "p=2"))
    import pyarrow.dataset as ds

    back = ds.dataset(out, format="parquet",
                      partitioning="hive").to_table().to_pydict()
    assert sorted(zip(back["p"], back["v"])) == sorted(
        zip(data["p"], data["v"]))


def test_unsupported_schema_falls_back_to_pyarrow(tmp_path):
    # array column -> host pyarrow encode; write still succeeds
    s = TpuSession(dict(_CONF))
    schema = T.StructType([
        T.StructField("a", T.ArrayType(T.INT), True)])
    df = s.create_dataframe({"a": [[1, 2], None, [3]]}, schema)
    out = str(tmp_path / "out")
    df.write.parquet(out)
    import pyarrow.parquet as pq

    back = pq.ParquetDataset(out).read()
    assert back.column("a").to_pylist() == [[1, 2], None, [3]]
