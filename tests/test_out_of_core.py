"""Out-of-core partitioned execution (ISSUE 10): size-aware exchange
partition sizing, spill-backed partition queues with bounded device
residency + the CRC-framed host boundary, AQE small-partition
coalescing, bench skip bookkeeping, and the pinned 10x-pool
hash-join + aggregation acceptance run.
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.session import TpuSession, col, sum_

_POOL = 512 << 10


def _ooc_conf(tmp_path=None, **extra):
    conf = {
        "spark.rapids.sql.enabled": True,
        # cap the pool via conf so the OOC machinery MUST engage
        "spark.rapids.tpu.test.deviceMemoryBytes": _POOL,
        "spark.rapids.sql.batchSizeBytes": 64 << 10,
        "spark.rapids.sql.reader.batchSizeRows": 4000,
        "spark.sql.autoBroadcastJoinThreshold": "-1",
        "spark.sql.adaptive.enabled": False,
        # bound read-side launches for test wall clock; sizing still
        # engages (wanted count is far above this cap)
        "spark.rapids.tpu.exchange.maxPartitions": 16,
    }
    if tmp_path is not None:
        conf["spark.rapids.memory.spillDir"] = str(tmp_path)
    conf.update(extra)
    return conf


def _fresh_frameworks(conf):
    from spark_rapids_tpu.memory.device_manager import reset_device_manager
    from spark_rapids_tpu.memory.spill import (
        get_spill_framework,
        reset_spill_framework,
    )

    reset_spill_framework()
    try:
        reset_device_manager()
    except Exception:
        pass
    return get_spill_framework(TpuConf(conf))


def _np_df(session, cols, types_):
    from spark_rapids_tpu.columnar.column import HostColumn
    from spark_rapids_tpu.plan.nodes import LocalTableScan
    from spark_rapids_tpu.session import DataFrame

    host = [HostColumn.from_numpy(np.ascontiguousarray(v), t)
            for (v, t) in zip(cols.values(), types_)]
    schema = T.StructType([T.StructField(name, t, False)
                           for name, t in zip(cols.keys(), types_)])
    return DataFrame(LocalTableScan(host, schema), session)


# ---------------------------------------------------------------------------
# planner: size-aware partition counts
# ---------------------------------------------------------------------------

def test_exchange_partition_sizing_grows_counts():
    """An exchange whose plan-static input estimate exceeds the
    per-partition pool budget grows its partition count (and is exempt
    from the single-device collapse)."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec

    conf = _ooc_conf()
    _fresh_frameworks(conf)
    s = TpuSession(conf)
    n = 120_000
    rng = np.random.default_rng(1)
    df = _np_df(s, {"k": rng.integers(0, 1000, n).astype(np.int32),
                    "v": rng.integers(-100, 100, n)}, [T.INT, T.LONG])
    snap = PC.snapshot()
    root, _ = df.repartition(2, "k")._planned()

    exchanges = []

    def find(node):
        if isinstance(node, TpuShuffleExchangeExec):
            exchanges.append(node)
        for c in node.children:
            if hasattr(c, "children"):
                find(c)

    find(root)
    assert exchanges, root.pretty()
    ex = exchanges[0]
    assert ex.num_partitions > 2, ex.describe()
    assert getattr(ex, "_ooc_sized", False)
    assert "sized" in ex.describe()
    assert PC.since(snap)["exchange_partitions_planned"] >= 1


def test_partition_sizing_leaves_small_inputs_alone():
    """A small input (estimate under one partition budget) keeps its
    planned count — sizing only ever grows."""
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec

    conf = _ooc_conf()
    conf.pop("spark.rapids.tpu.test.deviceMemoryBytes")
    _fresh_frameworks(conf)   # default (large) pool
    s = TpuSession(conf)
    df = _np_df(s, {"k": np.arange(100, dtype=np.int32),
                    "v": np.arange(100)}, [T.INT, T.LONG])
    root, _ = df.repartition(3, "k")._planned()

    found = []

    def find(node):
        if isinstance(node, TpuShuffleExchangeExec):
            found.append(node)
        for c in node.children:
            if hasattr(c, "children"):
                find(c)

    find(root)
    assert found and found[0].num_partitions == 3
    assert not getattr(found[0], "_ooc_sized", False)


def test_sized_exchange_matches_oracle():
    """The sized multi-partition exchange still answers correctly."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df

    conf = _ooc_conf()
    _fresh_frameworks(conf)

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=50),
                        IntegerGen(min_val=-100, max_val=100)],
                    ["k", "v"], length=3000)
        return df.repartition(4, "k").group_by("k").agg(sum_("v", "sv"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


# ---------------------------------------------------------------------------
# spill-backed partition queues
# ---------------------------------------------------------------------------

def _small_batch(n=200, seed=0):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    rng = np.random.default_rng(seed)
    schema = T.StructType([T.StructField("a", T.LONG),
                           T.StructField("s", T.STRING)])
    return ColumnarBatch.from_pydict(
        {"a": rng.integers(0, 1000, n).tolist(),
         "s": [f"row{i}" for i in range(n)]}, schema)


def test_partition_queues_host_boundary_blocks():
    """A zero device budget pushes every slice across the host boundary
    as a CRC-framed block; reads reassemble losslessly."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.shuffle.partition_queues import (
        SpillBackedPartitionQueues,
    )

    _fresh_frameworks(_ooc_conf())
    b = _small_batch()
    snap = PC.snapshot()
    q = SpillBackedPartitionQueues(2, b.schema, device_budget=0,
                                   codec="none")
    q.append(0, b)
    q.append(0, _small_batch(seed=7))
    assert q.host_blocks == 2
    d = PC.since(snap)
    assert d["exchange_host_blocks"] == 2
    assert d["exchange_host_block_bytes"] > 0
    out = q.read(0)
    assert out.num_rows == 400
    assert q.read(1) is None
    got = out.to_pydict()
    assert got["a"][:200] == _small_batch().to_pydict()["a"]
    q.close()


def test_partition_queues_crc_bit_flip_pins_shuffle_corruption():
    """A flipped bit in a queued host-boundary block surfaces as the
    deterministic ShuffleCorruption, never silent wrong rows."""
    from spark_rapids_tpu.shuffle.partition_queues import (
        SpillBackedPartitionQueues,
    )
    from spark_rapids_tpu.shuffle.serializer import ShuffleCorruption

    _fresh_frameworks(_ooc_conf())
    b = _small_batch()
    q = SpillBackedPartitionQueues(1, b.schema, device_budget=0,
                                   codec="none")
    q.append(0, b)
    kind, blob = q._queues[0][0]
    assert kind == "host"
    bad = bytearray(blob)
    bad[len(bad) // 2] ^= 0x40
    q._queues[0][0] = ("host", bytes(bad))
    with pytest.raises(ShuffleCorruption):
        q.read(0)
    q.close()


def test_ici_host_frame_round_trip_and_bit_flip():
    """The ONE host-boundary framing site (exec/ici.ici_host_frame):
    lossless round trip, CRC rejection on any flipped bit."""
    from spark_rapids_tpu.exec.ici import ici_host_frame, ici_host_unframe
    from spark_rapids_tpu.shuffle.serializer import ShuffleCorruption

    b = _small_batch()
    blob = ici_host_frame(b, codec="none")
    rt = ici_host_unframe(blob, b.schema, codec="none")
    assert rt.to_pydict() == b.to_pydict()
    for pos in (0, 6, len(blob) // 2, len(blob) - 1):
        bad = bytearray(blob)
        bad[pos] ^= 0x01
        with pytest.raises(ShuffleCorruption):
            ici_host_unframe(bytes(bad), b.schema, codec="none")


def test_exchange_streams_through_queues():
    """A direct multi-batch exchange run over a tiny device budget:
    results complete and host-boundary blocks flowed."""
    import sys
    sys.path.insert(0, "tests")
    from data_gen import IntegerGen, StringGen, gen_df
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.exec.basic import TpuLocalTableScanExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.plan.nodes import HashPartitioning
    from spark_rapids_tpu.session import col

    conf = _ooc_conf()
    conf["spark.rapids.tpu.exchange.deviceResidentBytes"] = 1
    _fresh_frameworks(conf)
    s = TpuSession(conf)
    df = gen_df(s, [IntegerGen(), StringGen()], ["k", "v"], length=500)
    scan = TpuLocalTableScanExec(df.plan.host_columns, df.plan.output)
    keys = [col("k").resolve(df.schema)]
    ex = TpuShuffleExchangeExec(HashPartitioning(keys, 5), scan,
                                conf=s.conf)
    snap = PC.snapshot()
    batches = list(ex.execute_columnar())
    assert sum(b.num_rows for b in batches) == 500
    d = PC.since(snap)
    assert d["exchange_host_blocks"] > 0
    assert d["exchange_partition_ns"] > 0
    assert d["exchange_spill_ns"] > 0
    from spark_rapids_tpu.lifecycle import leak_report_all

    assert leak_report_all() == []


# ---------------------------------------------------------------------------
# AQE shuffle-read small-partition coalescing
# ---------------------------------------------------------------------------

def test_adaptive_reader_coalesces_small_partitions_with_counter():
    """Adjacent small reduce partitions merge into one read window and
    bump partitions_coalesced; a right-sized partition emits alone."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.exec.exchange import TpuAdaptiveShuffleReaderExec

    schema = T.StructType([T.StructField("a", T.LONG)])

    def batch(n):
        return ColumnarBatch.from_pydict(
            {"a": list(range(n))}, schema)

    class _Fixed(TpuExec):
        def __init__(self, batches):
            super().__init__([])
            self._batches = batches

        @property
        def output(self):
            return schema

        def execute_columnar(self):
            yield from self._batches

    small = [batch(10) for _ in range(4)]     # ~tiny, below threshold
    big = batch(4096)                          # above small threshold
    reader = TpuAdaptiveShuffleReaderExec(
        _Fixed(small + [big] + [batch(10) for _ in range(3)]),
        target_bytes=1 << 30, small_bytes=big.nbytes())
    snap = PC.snapshot()
    out = list(reader.execute_columnar())
    # [4 smalls coalesced][big alone][3 smalls coalesced]
    assert [b.num_rows for b in out] == [40, 4096, 30]
    assert PC.since(snap)["partitions_coalesced"] == (4 - 1) + (3 - 1)
    assert reader.metric("partitionsCoalesced").value == 5
    assert "8->3" in reader.decision


# ---------------------------------------------------------------------------
# bench skip bookkeeping (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def test_bench_skip_bookkeeping_only_unfinished():
    import bench

    universe = {"qa_join_agg", "qb_left_join", "qc_window", "rung3",
                "rung3_ooc", "q6_parquet", "q6"}
    completed = {"q6_hot": {}, "qa_join_agg_hot": {},
                 "rung3_dec128_nested": {}, "q6_parquet": {}}
    # SIGKILL during rung3_ooc: rung3 and q6_parquet already streamed,
    # so ONLY rung3_ooc is skipped
    out = bench._not_finished(["rung3", "rung3_ooc", "q6_parquet"],
                              completed, universe=universe)
    assert out == ["rung3_ooc"]
    # a completed rung3_ooc must NOT vouch for rung3 (it is its own
    # tracked query, not a rung3 variant)
    out2 = bench._not_finished(["rung3"], {"rung3_ooc": {}},
                               universe=universe)
    assert out2 == ["rung3"]
    # q6 variants vouch for q6
    assert bench._not_finished(["q6"], completed, universe=universe) == []
    # dedupe
    assert bench._not_finished(["qb_left_join", "qb_left_join"],
                               completed, universe=universe) \
        == ["qb_left_join"]


# ---------------------------------------------------------------------------
# the acceptance pin: hash-join + aggregation at >= 10x the pool
# ---------------------------------------------------------------------------

# ISSUE 23: 80 s of the 1470 s tier-1 budget; the sized-exchange, spill
# and sub-partition paths it composes keep their own tier-1 tests here
@pytest.mark.slow
def test_ooc_hash_join_agg_10x_pool(tmp_path):
    """ISSUE 10 acceptance: a hash-join + aggregation whose input
    exceeds the (conf-capped) HBM pool by >= 10x completes correctly vs
    the CPU reference, spill traffic flowed, tracked device residency
    never exceeded the pool bound, and leak_report_all is clean."""
    from spark_rapids_tpu.lifecycle import leak_report_all

    conf = _ooc_conf(tmp_path)
    fw = _fresh_frameworks(conf)
    # >= 10x the 512KiB pool at ~20B/row flat; the pool itself must
    # exceed the platform's minimum batch capacity footprint (~264KiB
    # at 8192-row program capacity) or a single unspillable batch
    # busts the residency pin no matter how the exchange streams
    n_fact, n_dim = 280_000, 2000
    rng = np.random.default_rng(42)
    fk = rng.integers(0, n_dim, n_fact).astype(np.int32)
    fv = rng.integers(-1000, 1000, n_fact)
    fpad = rng.integers(0, 1 << 30, n_fact)
    dk = np.arange(n_dim, dtype=np.int32)
    dg = (dk % 17).astype(np.int32)
    data_bytes = fk.nbytes + fv.nbytes + fpad.nbytes
    assert data_bytes >= 10 * fw.pool_bytes, \
        f"fixture must exceed the pool 10x: {data_bytes} vs {fw.pool_bytes}"

    s = TpuSession(conf)
    fact = _np_df(s, {"k": fk, "v": fv, "pad": fpad},
                  [T.INT, T.LONG, T.LONG])
    dim = _np_df(s, {"k": dk, "g": dg}, [T.INT, T.INT])
    q = (fact.join(dim, on="k", how="inner")
         .group_by("g").agg(sum_("v", "sv")))
    rows = q.collect()

    # collect() rebuilds the framework singleton from the session conf
    # (session.py get_spill_framework(conf)); the metrics live there
    from spark_rapids_tpu.memory.spill import peek_spill_framework

    live = peek_spill_framework()
    assert live is not None and live.pool_bytes == fw.pool_bytes
    fw = live

    sums = np.bincount(dg[fk], weights=fv.astype(np.float64),
                       minlength=17)
    want = {int(i): int(sums[i]) for i in range(17)}
    got = {int(r[0]): int(r[1]) for r in rows}
    assert got == want

    # the out-of-core machinery actually engaged...
    assert fw.spill_to_host_count > 0, fw.metrics()
    # ...and tracked device residency stayed inside the pool bound
    # (register makes room BEFORE admitting — memory/spill.py)
    assert fw.device_used_peak <= fw.pool_bytes, fw.metrics()
    assert leak_report_all() == []
