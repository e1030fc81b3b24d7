"""Whole-stage fusion + perf-counter tests.

Covers the three round-4 program-count reducers:
  * Complete-agg collapse (Final<-Exchange<-Partial => Complete)
  * join->agg fusion (TpuJoinAggFusedExec, incl. the unique-build path)
  * agg->window->stage chain fusion (TpuWindowChainFusedExec)
and the backend-independent perf counters that prove the program/sync
budget: steady-state rung-2 shapes must run in <=3 programs / <=2 host
syncs.
"""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu.session import TpuSession, col, lit, sum_


def _sessions(extra=None):
    on = {"spark.rapids.sql.enabled": True,
          "spark.rapids.tpu.scan.cacheDeviceBatches": True}
    on.update(extra or {})
    return TpuSession(on), TpuSession({"spark.rapids.sql.enabled": False})


def _tables(s):
    n = 4000
    facts = {
        "k": [i % 37 if i % 11 else None for i in range(n)],
        "v": [(i * 7) % 1000 - 300 for i in range(n)],
        "g": [i % 5 for i in range(n)],
    }
    dims = {"k": list(range(0, 37, 2)), "w": [i * 10 for i in range(0, 37, 2)]}
    fsch = T.StructType([T.StructField("k", T.INT, True),
                         T.StructField("v", T.INT),
                         T.StructField("g", T.INT)])
    dsch = T.StructType([T.StructField("k", T.INT),
                         T.StructField("w", T.INT)])
    return (s.create_dataframe(facts, fsch),
            s.create_dataframe(dims, dsch))


def _plan_names(df):
    root, _ = df._planned()
    out = []

    def walk(n):
        out.append(type(n).__name__)
        for c in getattr(n, "children", []):
            walk(c)

    walk(root)
    return out


# ---------------------------------------------------------------------------
# plan shapes
# ---------------------------------------------------------------------------

def test_complete_agg_collapse_plan():
    s, _ = _sessions()
    f, _d = _tables(s)
    q = f.group_by("g").agg(sum_("v", "sv"))
    names = _plan_names(q)
    assert "TpuShuffleExchangeExec" not in names
    root, _ = q._planned()
    assert root.mode.value == "Complete"


def test_collapse_kill_switch():
    s, _ = _sessions({"spark.rapids.tpu.completeAggCollapse.enabled": False})
    f, _d = _tables(s)
    names = _plan_names(f.group_by("g").agg(sum_("v", "sv")))
    assert "TpuShuffleExchangeExec" in names


def test_join_agg_fused_plan_and_kill_switch():
    s, _ = _sessions()
    f, d = _tables(s)
    q = f.join(d, on="k").group_by("g").agg(sum_("w", "sw"))
    assert "TpuJoinAggFusedExec" in _plan_names(q)
    s2, _ = _sessions({"spark.rapids.tpu.joinAggFusion.enabled": False})
    f2, d2 = _tables(s2)
    q2 = f2.join(d2, on="k").group_by("g").agg(sum_("w", "sw"))
    assert "TpuJoinAggFusedExec" not in _plan_names(q2)


def test_window_chain_fused_plan_and_kill_switch():
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction

    def build(s):
        f, _d = _tables(s)
        daily = f.group_by("g", "k").agg(sum_("v", "sv"))
        w = daily.window([WindowFunction("rank", None, "rk")],
                         partition_by=["g"],
                         order_by=[(col("sv"), SortSpec(ascending=False))])
        return w.filter(col("rk") <= lit(3))

    s, _ = _sessions()
    assert "TpuWindowChainFusedExec" in _plan_names(build(s))
    s2, _ = _sessions({"spark.rapids.tpu.windowChainFusion.enabled": False})
    assert "TpuWindowChainFusedExec" not in _plan_names(build(s2))


# ---------------------------------------------------------------------------
# correctness: fused == kill-switched == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left"])
def test_join_agg_fused_matches_oracle(how):
    results = []
    for extra in (None,
                  {"spark.rapids.tpu.joinAggFusion.enabled": False},
                  {"spark.rapids.sql.enabled": False}):
        conf = {"spark.rapids.sql.enabled": True}
        conf.update(extra or {})
        s = TpuSession(conf)
        f, d = _tables(s)
        q = (f.join(d, on="k", how=how)
             .group_by("g").agg(sum_("w", "sw")))
        results.append(sorted(q.collect(), key=str))
    assert results[0] == results[1] == results[2]


def test_join_agg_fused_dup_build_keys():
    """Duplicate build keys force the general materialize+agg path."""
    results = []
    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled})
        fsch = T.StructType([T.StructField("k", T.INT, True),
                             T.StructField("v", T.INT)])
        dsch = T.StructType([T.StructField("k", T.INT),
                             T.StructField("w", T.INT)])
        f = s.create_dataframe(
            {"k": [1, 2, 2, 3, None], "v": [10, 20, 30, 40, 50]}, fsch)
        d = s.create_dataframe({"k": [2, 2, 3], "w": [7, 8, 9]}, dsch)
        q = f.join(d, on="k").group_by("v").agg(sum_("w", "sw"))
        results.append(sorted(q.collect(), key=str))
    assert results[0] == results[1]


def test_window_chain_fused_matches_oracle():
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction

    results = []
    for extra in (None,
                  {"spark.rapids.tpu.windowChainFusion.enabled": False},
                  {"spark.rapids.sql.enabled": False}):
        conf = {"spark.rapids.sql.enabled": True}
        conf.update(extra or {})
        s = TpuSession(conf)
        f, _d = _tables(s)
        daily = f.group_by("g", "k").agg(sum_("v", "sv"))
        w = daily.window([WindowFunction("rank", None, "rk")],
                         partition_by=["g"],
                         order_by=[(col("sv"), SortSpec(ascending=False))])
        q = w.filter(col("rk") <= lit(3))
        results.append(sorted(q.collect(), key=str))
    assert results[0] == results[1] == results[2]


def test_fused_agg_avg_multibatch():
    """avg across multiple batches must merge (sum,count) buffers, not
    average averages — the COMPLETE twins contract."""
    n = 3000
    for conf in ({"spark.rapids.sql.enabled": True,
                  "spark.rapids.sql.reader.batchSizeRows": 512},
                 {"spark.rapids.sql.enabled": False}):
        s = TpuSession(conf)
        sch = T.StructType([T.StructField("g", T.INT),
                            T.StructField("v", T.INT)])
        df = s.create_dataframe(
            {"g": [i % 3 for i in range(n)],
             "v": [(i * 13) % 97 for i in range(n)]}, sch)
        got = sorted(df.group_by("g").agg(("avg", "v", "av")).collect(),
                     key=str)
        if conf["spark.rapids.sql.enabled"]:
            tpu = got
        else:
            assert [(g, round(a, 9)) for g, a in tpu] == \
                [(g, round(a, 9)) for g, a in got]


# ---------------------------------------------------------------------------
# perf counters: the <=3 programs / <=2 syncs steady-state budget
# ---------------------------------------------------------------------------

def _steady_counts(q):
    q.collect()   # compile + learn strategies
    q.collect()   # strategy-switch compiles
    PC.reset()
    q.collect()
    c = PC.snapshot()
    return c["programs_launched"], c["host_syncs"]


def test_counter_budget_scan_filter_agg():
    s, _ = _sessions()
    f, _d = _tables(s)
    q = f.filter(col("v") > lit(0)).agg(sum_("v", "sv"))
    launches, syncs = _steady_counts(q)
    assert launches <= 1 and syncs <= 1, (launches, syncs)


def test_counter_budget_join_agg():
    s, _ = _sessions()
    f, d = _tables(s)
    q = f.join(d, on="k").group_by("g").agg(sum_("w", "sw"))
    launches, syncs = _steady_counts(q)
    # ISSUE 17 tightened from <=3: the collect-boundary shrink program is
    # elided when the padded-transfer waste is under the conf budget
    assert launches <= 2 and syncs <= 2, (launches, syncs)


def test_counter_budget_window_chain():
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction

    s, _ = _sessions()
    f, _d = _tables(s)
    daily = f.group_by("g", "k").agg(sum_("v", "sv"))
    w = daily.window([WindowFunction("rank", None, "rk")],
                     partition_by=["g"],
                     order_by=[(col("sv"), SortSpec(ascending=False))])
    q = w.filter(col("rk") <= lit(3))
    launches, syncs = _steady_counts(q)
    # ISSUE 17 tightened from <=2 launches: collect-side shrink elided
    assert launches <= 1 and syncs <= 2, (launches, syncs)
