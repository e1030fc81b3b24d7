"""AQE runtime join re-planning + cost-based fallback tests
(reference: adaptive_query_test.py, CostBasedOptimizer suites)."""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, col, lit

from asserts import assert_tpu_and_cpu_are_equal_collect
from data_gen import IntegerGen, LongGen, StringGen, gen_df


def _find(root, cls_name):
    out = []

    def walk(n):
        if type(n).__name__ == cls_name:
            out.append(n)
        for c in getattr(n, "children", []):
            walk(c)
        sh = getattr(n, "shuffled", None)
        if sh is not None:
            walk(sh)

    walk(root)
    return out


def _join_df(s, n_right=20):
    big = gen_df(s, [IntegerGen(min_val=0, max_val=50, nullable=False),
                     LongGen()], ["k", "v"], length=2000)
    small = gen_df(s, [IntegerGen(min_val=0, max_val=50, nullable=False),
                       StringGen()], ["k", "s"], length=n_right, seed=9)
    # force the shuffled plan (small side is a local scan, so disable the
    # static broadcast threshold to exercise the RUNTIME decision)
    return big.join(small, on=["k"])


def test_adaptive_switches_to_broadcast_at_runtime():
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.sql.autoBroadcastJoinThreshold": "-1"})
    # static broadcast off -> planner emits exchanges + shuffled join;
    # re-enable the runtime threshold via a fresh conf on the adaptive node
    q = _join_df(s)
    root, meta = q._planned()
    adaptive = _find(root, "TpuAdaptiveJoinExec")
    if not adaptive:
        pytest.skip("static planner already broadcast this join")
    node = adaptive[0]
    node.threshold = 10 << 20  # runtime stats will be far below this
    rows = q.collect()
    assert node.decision and node.decision.startswith("broadcast"), \
        node.decision
    assert len(rows) > 0


def test_adaptive_keeps_shuffle_for_big_build():
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.sql.autoBroadcastJoinThreshold": "-1"})
    q = _join_df(s, n_right=1500)
    root, meta = q._planned()
    adaptive = _find(root, "TpuAdaptiveJoinExec")
    if not adaptive:
        pytest.skip("no adaptive node")
    node = adaptive[0]
    node.threshold = 16  # tiny: must stay shuffled
    rows = q.collect()
    assert node.decision and node.decision.startswith("shuffled"), \
        node.decision
    assert len(rows) > 0


def test_adaptive_results_match_oracle():
    def build(s):
        return _join_df(s)

    assert_tpu_and_cpu_are_equal_collect(
        build, conf={"spark.sql.autoBroadcastJoinThreshold": "-1"})


def test_adaptive_disabled_keeps_plain_shuffled_join():
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.sql.adaptive.enabled": "false",
                    "spark.sql.autoBroadcastJoinThreshold": "-1"})
    q = _join_df(s)
    root, meta = q._planned()
    assert not _find(root, "TpuAdaptiveJoinExec")
    assert _find(root, "TpuShuffledSymmetricHashJoinExec")


def test_cost_optimizer_keeps_tiny_plan_on_cpu():
    conf = {"spark.rapids.sql.enabled": True,
            "spark.rapids.sql.optimizer.enabled": "true"}
    s = TpuSession(conf)
    df = gen_df(s, [IntegerGen()], ["a"], length=10)
    q = df.select((col("a") + lit(1)).alias("r"))
    root, meta = q._planned()
    assert "cost-based optimizer" in meta.explain(only_fallback=False)
    # results still correct via CPU
    assert len(q.collect()) == 10


def test_cost_optimizer_lets_big_plans_through():
    conf = {"spark.rapids.sql.enabled": True,
            "spark.rapids.sql.optimizer.enabled": "true"}
    s = TpuSession(conf)
    df = gen_df(s, [IntegerGen(), StringGen(min_len=20, max_len=40)],
                ["a", "s"], length=5000)
    q = df.select((col("a") + lit(1)).alias("r"), col("s"))
    root, meta = q._planned()
    assert "cost-based optimizer" not in meta.explain(only_fallback=False)


def test_cost_optimizer_off_by_default():
    s = TpuSession({"spark.rapids.sql.enabled": True})
    df = gen_df(s, [IntegerGen()], ["a"], length=5)
    q = df.select((col("a") + lit(1)).alias("r"))
    root, meta = q._planned()
    assert "cost-based optimizer" not in meta.explain(only_fallback=False)


# -- round 4: general AQE beyond the broadcast-join case --------------------


def test_adaptive_shuffle_reader_coalesces_on_measured_stats():
    """The AQE shuffle reader records per-partition rows/bytes at
    execution and coalesces partitions on those MEASURED stats
    (GpuCustomShuffleReaderExec analog) — a runtime plan change beyond
    the broadcast-join case."""
    from spark_rapids_tpu.exec.exchange import TpuAdaptiveShuffleReaderExec
    from spark_rapids_tpu.session import TpuSession, sum_

    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        # many tiny reduce partitions + a tiny coalesce target would keep
        # them separate; default target merges them all
        "spark.sql.shuffle.partitions": 8,
        # keep the exchange alive (no single-device collapse)
        "spark.rapids.tpu.completeAggCollapse.enabled": False,
    })
    df = gen_df(s, [IntegerGen(min_val=0, max_val=30), IntegerGen()],
                ["k", "v"], length=500)
    q = df.group_by("k").agg(sum_("v", "s"))
    root, _ = q._planned()

    readers = []

    def find(n):
        if isinstance(n, TpuAdaptiveShuffleReaderExec):
            readers.append(n)
        for c in n.children:
            if hasattr(c, "children"):
                find(c)

    find(root)
    assert readers, f"no adaptive reader in plan: {root.pretty()}"
    rows = q.collect()
    assert rows
    r = readers[0]
    assert r.decision is not None and "->" in r.decision, r.decision
    n_in = int(r.decision.split()[1].split("->")[0])
    n_out = int(r.decision.split()[1].split("->")[1])
    assert n_in > n_out, r.decision          # stats-driven plan change
    assert len(r.stats) == n_in
    assert all(b > 0 for _, b in r.stats)


def test_adaptive_reader_disabled_falls_back_to_static_coalesce():
    from spark_rapids_tpu.exec.coalesce import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.exchange import TpuAdaptiveShuffleReaderExec
    from spark_rapids_tpu.session import TpuSession, sum_

    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.sql.adaptive.enabled": False,
        "spark.rapids.tpu.completeAggCollapse.enabled": False,
    })
    df = gen_df(s, [IntegerGen(min_val=0, max_val=5), IntegerGen()],
                ["k", "v"], length=100)
    root, _ = df.group_by("k").agg(sum_("v", "s"))._planned()

    def find(n, cls):
        if isinstance(n, cls):
            return True
        return any(find(c, cls) for c in n.children
                   if hasattr(c, "children"))

    assert not find(root, TpuAdaptiveShuffleReaderExec)
    assert find(root, TpuCoalesceBatchesExec)
