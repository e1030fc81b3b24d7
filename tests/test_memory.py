"""Memory runtime tests: spill tiers, OOM retry/split, semaphore.

Reference analogs: WithRetrySuite / spill-framework suites (SURVEY.md §4),
which force OOMs via RmmSpark.forceRetryOOM / forceSplitAndRetryOOM and
check the work still completes correctly.
"""
import threading
import time

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.memory.retry import (
    TpuSplitAndRetryOOM,
    force_retry_oom,
    force_split_and_retry_oom,
    with_retry,
    with_retry_no_split,
)
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.memory.spill import SpillFramework
from spark_rapids_tpu.session import TpuSession, col, lit, sum_

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import IntegerGen, StringGen, gen_df


def _batch(n=1000, start=0):
    data = {"a": list(range(start, start + n)),
            "s": [f"row{i}" for i in range(n)]}
    schema = T.StructType([T.StructField("a", T.LONG),
                           T.StructField("s", T.STRING)])
    return ColumnarBatch.from_pydict(data, schema)


def _tiny_framework(pool=64 << 10, host=1 << 30, tmp=None):
    return SpillFramework(pool_bytes=pool, host_limit=host,
                          spill_dir=str(tmp) if tmp else None)


def test_spill_device_to_host_and_back():
    fw = _tiny_framework(pool=32 << 10)
    b1 = _batch(1000)
    h1 = fw.track(b1)          # ~22KiB: two batches exceed the 32KiB pool
    h2 = fw.track(_batch(1000, start=5000))
    # admitting h2 must have pushed h1 (LRU) off the device
    assert h1.state == "HOST"
    assert h2.state == "DEVICE"
    # materializing h1 back evicts h2
    rows = h1.get_batch().to_pydict()
    assert rows["a"][:3] == [0, 1, 2]
    assert h1.state == "DEVICE"
    assert fw.spill_to_host_count >= 1
    h1.close()
    h2.close()
    assert fw.device_used == 0


def test_spill_to_disk(tmp_path):
    fw = _tiny_framework(pool=32 << 10, host=16 << 10, tmp=tmp_path)
    handles = [fw.track(_batch(1000, start=i * 1000)) for i in range(4)]
    states = {h.state for h in handles}
    assert "DISK" in states, states
    # everything still materializes correctly
    for i, h in enumerate(handles):
        got = h.get_batch().to_pydict()["a"][0]
        assert got == i * 1000
        h.close()
    assert fw.spill_to_disk_count >= 1


def test_with_retry_injected_retry():
    from spark_rapids_tpu.memory import spill as spill_mod

    spill_mod.reset_spill_framework()
    fw = spill_mod.get_spill_framework(TpuConf(
        {"spark.rapids.tpu.test.deviceMemoryBytes": str(1 << 30)}))
    calls = []

    def fn(batch):
        calls.append(batch.num_rows)
        return batch.num_rows

    force_retry_oom(2)
    out = list(with_retry(fw.track(_batch(100)), fn))
    assert out == [100]


def test_with_retry_injected_split():
    from spark_rapids_tpu.memory import spill as spill_mod

    spill_mod.reset_spill_framework()
    fw = spill_mod.get_spill_framework(TpuConf(
        {"spark.rapids.tpu.test.deviceMemoryBytes": str(1 << 30)}))

    def fn(batch):
        return batch.num_rows

    force_split_and_retry_oom(1)
    out = list(with_retry(fw.track(_batch(100)), fn))
    assert out == [50, 50]   # split in half, both halves processed


def test_with_retry_split_exhausted():
    from spark_rapids_tpu.memory import spill as spill_mod

    spill_mod.reset_spill_framework()
    fw = spill_mod.get_spill_framework(TpuConf(
        {"spark.rapids.tpu.test.deviceMemoryBytes": str(1 << 30)}))
    force_split_and_retry_oom(1)
    with pytest.raises(TpuSplitAndRetryOOM):
        list(with_retry(fw.track(_batch(1)), lambda b: b.num_rows))


def test_with_retry_no_split():
    attempts = []

    def fn():
        attempts.append(1)
        return 42

    force_retry_oom(1)
    assert with_retry_no_split(fn) == 42
    assert len(attempts) == 1   # injection fires before fn on attempt 1


def test_semaphore_limits_concurrency():
    sem = TpuSemaphore(1)
    active = []
    peak = []

    def task():
        sem.acquire_if_necessary()
        active.append(1)
        peak.append(len(active))
        time.sleep(0.02)
        active.remove(1)
        sem.release_if_necessary()

    threads = [threading.Thread(target=task) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert max(peak) == 1
    assert sem.total_wait_ns > 0


def test_semaphore_reentrant():
    sem = TpuSemaphore(1)
    sem.acquire_if_necessary()
    sem.acquire_if_necessary()   # same thread passes through
    sem.release_if_necessary()
    assert sem.held_by_current_thread()
    sem.release_if_necessary()
    assert not sem.held_by_current_thread()


# ---- end-to-end: queries survive injected OOMs with correct results ------

_inject_confs = [
    {"spark.rapids.sql.test.injectRetryOOM": "RETRY:2"},
    {"spark.rapids.sql.test.injectRetryOOM": "SPLIT:1"},
]


@pytest.mark.parametrize("inject", _inject_confs,
                         ids=["retry", "split"])
def test_query_with_injected_oom(inject):
    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=10),
                        IntegerGen(min_val=-100, max_val=100)],
                    ["k", "v"], length=400)
        return df.group_by("k").agg(sum_("v", "sv"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=inject)


def test_query_under_tiny_pool():
    """The whole query runs with a pool smaller than the working set —
    forcing real spill traffic — and still matches the CPU oracle."""
    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=6),
                        StringGen(min_len=1, max_len=12)],
                    ["k", "v"], length=2000)
        u = df.union(df)
        return u.group_by("k").agg(("count", "v", "c"),
                                   ("max", "v", "mx"))

    assert_tpu_and_cpu_are_equal_collect(
        build,
        conf={"spark.rapids.tpu.test.deviceMemoryBytes": str(256 << 10),
              "spark.rapids.sql.batchSizeBytes": "64k"})


def test_multibatch_aggregate_merge_path():
    """union -> several input batches -> the pairwise merge tree runs."""
    def build(s):
        df1 = gen_df(s, [IntegerGen(min_val=0, max_val=5),
                         IntegerGen(min_val=-50, max_val=50)],
                     ["k", "v"], length=300, seed=1)
        df2 = gen_df(s, [IntegerGen(min_val=3, max_val=9),
                         IntegerGen(min_val=-50, max_val=50)],
                     ["k", "v"], length=300, seed=2)
        u = df1.union(df2).union(df1)
        return u.group_by("k").agg(sum_("v", "sv"), ("avg", "v", "av"),
                                   ("min", "v", "mn"), ("count", "v", "c"))

    assert_tpu_and_cpu_are_equal_collect(
        build, conf={"spark.rapids.sql.batchSizeBytes": "1k"})


# -- out-of-core operation under a tiny pool (SURVEY §5.7) -------------------

_OOC_CONF = {
    "spark.rapids.sql.enabled": True,
    # ~10x the data must not fit: tiny pool + forced multi-batch scan
    "spark.rapids.tpu.test.deviceMemoryBytes": 256 << 10,
    "spark.rapids.sql.batchSizeBytes": 64 << 10,
    "spark.rapids.sql.reader.batchSizeRows": 900,
}


def _fresh_frameworks(conf):
    from spark_rapids_tpu.memory.device_manager import reset_device_manager
    from spark_rapids_tpu.memory.spill import (
        get_spill_framework,
        reset_spill_framework,
    )
    from spark_rapids_tpu.config import TpuConf

    reset_spill_framework()
    try:
        reset_device_manager()
    except Exception:
        pass
    return get_spill_framework(TpuConf(conf))


def test_out_of_core_sort_matches_oracle_with_spill(tmp_path):
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, StringGen, gen_df
    from spark_rapids_tpu.session import col

    conf = dict(_OOC_CONF)
    conf["spark.rapids.memory.spill.dir"] = str(tmp_path)
    _fresh_frameworks(conf)

    def build(s):
        df = gen_df(s, [IntegerGen(), StringGen(min_len=1, max_len=24),
                        IntegerGen()], ["a", "t", "b"], length=6000)
        return df.order_by("a", "t")

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf,
                                         ignore_order=False)
    from spark_rapids_tpu.memory.spill import get_spill_framework

    fw = get_spill_framework()   # the one the collect actually used
    assert fw.spill_to_host_count > 0, "expected device->host spills"


def test_sub_partitioned_join_matches_oracle_with_spill(tmp_path):
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, StringGen, gen_df
    from spark_rapids_tpu.session import col

    conf = dict(_OOC_CONF)
    conf["spark.rapids.memory.spill.dir"] = str(tmp_path)
    _fresh_frameworks(conf)

    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=4000),
                          StringGen(min_len=4, max_len=20)],
                      ["k", "x"], length=5000)
        right = gen_df(s, [IntegerGen(min_val=0, max_val=4000),
                           StringGen(min_len=4, max_len=20)],
                       ["k", "y"], length=5000, seed=99)
        return left.join(right, on="k", how="inner")

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)
    from spark_rapids_tpu.memory.spill import get_spill_framework

    fw = get_spill_framework()
    assert fw.spill_to_host_count > 0, "expected device->host spills"


@pytest.mark.parametrize("how", ["left", "full", "semi", "anti"])
def test_sub_partitioned_join_types(how, tmp_path):
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df

    conf = dict(_OOC_CONF)
    conf["spark.rapids.memory.spill.dir"] = str(tmp_path)
    _fresh_frameworks(conf)

    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=2000),
                          IntegerGen()], ["k", "x"], length=3500)
        right = gen_df(s, [IntegerGen(min_val=0, max_val=2000),
                           IntegerGen()], ["k", "y"], length=3500, seed=5)
        return left.join(right, on="k", how=how)

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


def test_sub_partitioned_join_mismatched_key_ordinals(tmp_path):
    """Build and probe keys at different column ordinals: the bucketing jits
    must not be shared between sides (code-review regression)."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, StringGen, gen_df
    from spark_rapids_tpu.session import col

    conf = dict(_OOC_CONF)
    conf["spark.rapids.memory.spill.dir"] = str(tmp_path)
    _fresh_frameworks(conf)

    def build(s):
        left = gen_df(s, [StringGen(min_len=3, max_len=12),
                          IntegerGen(min_val=0, max_val=1500)],
                      ["pad", "k"], length=4000)       # key at ordinal 1
        right = gen_df(s, [IntegerGen(min_val=0, max_val=1500),
                           StringGen(min_len=3, max_len=12)],
                       ["k", "pad2"], length=4000, seed=11)  # key at ordinal 0
        return left.join(right, on="k", how="inner")

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


def test_out_of_core_sort_mixed_string_widths(tmp_path):
    """Runs whose string columns land in different width buckets: the merge
    must align key words across chunks (code-review regression)."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import SetValuesGen
    from spark_rapids_tpu import types as T

    conf = dict(_OOC_CONF)
    conf["spark.rapids.memory.spill.dir"] = str(tmp_path)
    conf["spark.rapids.sql.reader.batchSizeRows"] = 400
    _fresh_frameworks(conf)

    short = ["a", "bb", "cc", "d"]
    long_ = ["x" * 30, "y" * 25, "z" * 28, "w" * 31]

    def build(s):
        import random
        rng = random.Random(7)
        # first half short strings (width bucket 8), second half long (32):
        # consecutive scan batches land in different buckets
        vals = [rng.choice(short) for _ in range(1200)] \
            + [rng.choice(long_) for _ in range(1200)]
        nums = [rng.randint(0, 50) for _ in range(2400)]
        schema = T.StructType([T.StructField("t", T.STRING),
                               T.StructField("n", T.INT)])
        return s.create_dataframe({"t": vals, "n": nums}, schema) \
                .order_by("t", "n")

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf,
                                         ignore_order=False)


def test_metrics_report_surface():
    """df.metrics_report() renders per-operator metric rollups after
    execution (the SQL-UI metrics analog, SURVEY §5.5)."""
    from spark_rapids_tpu.session import TpuSession, col, lit, sum_

    s = TpuSession({"spark.rapids.sql.enabled": True})
    df = s.create_dataframe(
        {"k": [1, 2, 1, 2] * 50, "v": list(range(200))},
        T.StructType([T.StructField("k", T.INT),
                      T.StructField("v", T.LONG)]))
    q = df.filter(col("v") > lit(5)).group_by("k").agg(sum_("v", "sv"))
    q.collect()
    rep = q.metrics_report()
    assert "numOutputRows" in rep and "opTime" in rep
    assert "TpuHashAggregate" in rep


def test_device_manager_raises_on_accelerator_without_memory_stats(
        monkeypatch):
    """ISSUE 23: only the CPU backend may go without memory_stats(); on
    a TPU a missing report is an error, never an assumed 16 GiB."""
    import jax

    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.memory import device_manager as DM

    class _Dev:
        def __init__(self, platform, stats):
            self.platform, self.device_kind = platform, "TPU v5 lite"
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev("tpu", None)])
    with pytest.raises(RuntimeError, match="memory_stats"):
        DM.TpuDeviceManager(TpuConf({}))
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev("tpu", {"bytes_limit": 15 << 30})])
    assert DM.TpuDeviceManager(TpuConf({})).physical_bytes == 15 << 30
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev("cpu", None)])
    assert DM.TpuDeviceManager(
        TpuConf({})).physical_bytes == DM._CPU_BACKEND_MEMORY
