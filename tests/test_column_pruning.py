"""Column pruning at plan time (plan/pruning.py) and its consumers.

The pass runs on the TPU path only, so every differential test in this
suite already holds a pruned TPU plan against the unpruned CPU oracle;
the cases here aim at the pass itself: every join type, conditions that
read what the parent does not, duplicate key names, operators above and
below the join, reuse of a DataFrame, and the queries the pass must leave
alone.  The consumer tests pin what the narrowing buys on the device: the
gathers of ``materialize_pairs``, no program for a projection of bare
references, one upload over two plannings, a parquet read of the named
columns only.
"""
import numpy as np
import pytest

from asserts import assert_tpu_and_cpu_are_equal_collect
from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.base import BoundReference
from spark_rapids_tpu.expr.predicates import GreaterThan, LessThan
from spark_rapids_tpu.plan import nodes as PN
from spark_rapids_tpu.plan.pruning import prune_columns
from spark_rapids_tpu.session import (
    DataFrame,
    TpuSession,
    col,
    count_,
    lit,
    sum_,
)

SHUFFLED = {"spark.sql.autoBroadcastJoinThreshold": "-1"}
BROADCAST = {}

_RNG = np.random.default_rng(7)
_N, _M = 96, 40
_SALES = {
    "date_sk": _RNG.integers(0, 9, _N).tolist(),
    "store_sk": _RNG.integers(1, 6, _N).tolist(),
    "item_sk": [None if i % 17 == 0 else int(v)
                for i, v in enumerate(_RNG.integers(0, 12, _N))],
    "ticket": _RNG.integers(0, 12, _N).tolist(),
    "quantity": _RNG.integers(1, 100, _N).tolist(),
    "ext_sales": _RNG.integers(100, 10_000, _N).tolist(),
    "net_profit": _RNG.integers(-100, 400, _N).tolist(),
}
_RETURNS = {
    "ticket": _RNG.integers(0, 14, _M).tolist(),
    "item_sk": _RNG.integers(0, 14, _M).tolist(),
    "return_amt": [None if i % 11 == 0 else int(v)
                   for i, v in enumerate(_RNG.integers(50, 5_000, _M))],
}
_SALES_T = T.StructType([
    T.StructField(n, T.LONG if n in ("ticket", "quantity", "ext_sales",
                                     "net_profit") else T.INT)
    for n in _SALES])
_RETURNS_T = T.StructType([
    T.StructField("ticket", T.LONG), T.StructField("item_sk", T.INT),
    T.StructField("return_amt", T.LONG)])
ON = ["ticket", "item_sk"]


def sales(s):
    return s.create_dataframe(_SALES, _SALES_T)


def returns(s):
    return s.create_dataframe(_RETURNS, _RETURNS_T)


def _ref(df, i):
    f = df.schema.fields[i]
    return BoundReference(i, f.dataType, f.nullable, name=f.name)


def _keyed(s, how, condition=None, broadcast=False):
    """An equi-join on (ticket, item_sk) built node by node, so it can
    carry a residual condition (the DataFrame API has no spelling for
    one); ``condition`` gets the joined frame."""
    a, b = sales(s), returns(s)
    lk = [col(k).resolve(a.schema) for k in ON]
    rk = [col(k).resolve(b.schema) for k in ON]
    jt = {"inner": PN.JoinType.INNER, "left": PN.JoinType.LEFT_OUTER}[how]
    if broadcast:
        node = PN.BroadcastHashJoin(a.plan, PN.BroadcastExchange(b.plan),
                                    lk, rk, jt)
    else:
        node = PN.SortMergeJoin(
            PN.Exchange(PN.HashPartitioning(lk, 4), a.plan),
            PN.Exchange(PN.HashPartitioning(rk, 4), b.plan), lk, rk, jt)
    df = DataFrame(node, s)
    if condition is not None:
        node.condition = condition(df)
    return df


def _walk(plan):
    yield plan
    for c in plan.children:
        yield from _walk(c)


# -- the pass against the oracle -----------------------------------------

_OUTER = ["inner", "left", "right", "full"]
_READS = {
    # what the parent reads of the joined frame
    "subset": lambda j: j.select(col("store_sk"), col("ext_sales"),
                                 col("return_amt")),
    "left_only": lambda j: j.select(col("store_sk"), col("quantity")),
    "right_only": lambda j: j.select(col("return_amt")),
    # a key column, by ordinal: both sides carry the key names
    "left_key": lambda j: j.select(_ref(j, 3), col("ext_sales")),
    "right_key": lambda j: j.select(_ref(j, 7), _ref(j, 8),
                                    col("store_sk")),
    "count_star": lambda j: j.agg(count_(None, "n")),
}
# every read through the shuffled join; the broadcast join shares the
# exec's code, so two reads of it
_JOIN_CASES = [(how, reads, conf_name)
               for how in _OUTER for reads in _READS
               for conf_name in ("shuffled", "broadcast")
               if conf_name == "shuffled" or reads in ("subset", "right_key")]


@pytest.mark.parametrize("how,reads,conf_name", _JOIN_CASES)
def test_outer_family_joins_prune_to_the_oracle(how, reads, conf_name):
    conf = SHUFFLED if conf_name == "shuffled" else BROADCAST

    def q(s):
        return _READS[reads](sales(s).join(returns(s), on=ON, how=how))

    assert_tpu_and_cpu_are_equal_collect(q, conf)


@pytest.mark.parametrize("how,reads,conf_name", [
    (how, reads, conf_name) for how in ("semi", "anti")
    for reads in ("left_only", "left_key", "count_star")
    for conf_name in ("shuffled", "broadcast")
    if conf_name == "shuffled" or reads == "left_only"])
def test_semi_anti_joins_prune_to_the_oracle(how, reads, conf_name):
    conf = SHUFFLED if conf_name == "shuffled" else BROADCAST

    def q(s):
        return _READS[reads](sales(s).join(returns(s), on=ON, how=how))

    assert_tpu_and_cpu_are_equal_collect(q, conf)


@pytest.mark.parametrize("reads", ["subset", "left_only", "right_only",
                                   "count_star"])
def test_cross_join_prunes_to_the_oracle(reads):
    def q(s):
        a = sales(s).filter(col("date_sk") < lit(2))
        return _READS[reads](a.cross_join(returns(s)))

    assert_tpu_and_cpu_are_equal_collect(q)


_CONDITIONS = {
    # ext_sales > return_amt, date_sk < 5: `left_only` reads none of them
    "both_sides": lambda j: GreaterThan(_ref(j, 5), _ref(j, 9)),
    "one_side": lambda j: LessThan(_ref(j, 0), lit(5)),
}
# the kill switch leaves the pruned plan's join, emit list and all, to the
# CPU oracle: what the breaker does to a plan it re-tags
_CPU_JOIN = {"spark.rapids.sql.exec.SortMergeJoin": False}


@pytest.mark.parametrize("kind", ["shuffled", "broadcast", "cpu_join"])
@pytest.mark.parametrize("cond", list(_CONDITIONS))
@pytest.mark.parametrize("reads", ["left_only", "subset"])
def test_inner_join_condition_reads_what_the_parent_does_not(
        kind, cond, reads):
    def q(s):
        j = _keyed(s, "inner", _CONDITIONS[cond],
                   broadcast=kind == "broadcast")
        return _READS[reads](j)

    assert len(q(TpuSession()).collect()) > 0
    text = q(TpuSession(_CPU_JOIN if kind == "cpu_join" else {})).explain()
    assert "emit=[" in text and ("!SortMergeJoin" in text) == (
        kind == "cpu_join")
    assert_tpu_and_cpu_are_equal_collect(
        q, _CPU_JOIN if kind == "cpu_join" else None)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
@pytest.mark.parametrize("reads", ["left_only", "left_key"])
def test_nested_loop_join_narrows_its_children_only(how, reads):
    """``on=<expression>`` plans a BroadcastNestedLoopJoin, which takes no
    emit list: its condition reads ticket and return_amt, the parent
    neither of the two."""
    def q(s):
        a = sales(s).filter(col("date_sk") < lit(3))
        b = returns(s).select(col("ticket").alias("r_ticket"),
                              col("return_amt"))
        on = (col("ticket").eq(col("r_ticket"))
              & (col("ext_sales") > col("return_amt")))
        return _READS[reads](a.join(b, on=on, how=how))

    assert_tpu_and_cpu_are_equal_collect(q)


def _window(j):
    fn = PN.WindowFunction("sum", col("ext_sales"), "running")
    # ties in (ticket, ext_sales) add the same amount in either order
    return j.window([fn], ["store_sk"], [col("ticket"), col("ext_sales")])


_SHAPES = {
    # operators above the join
    "filter_above": lambda a, b: a.join(b, on=ON, how="left")
    .filter(col("quantity") > lit(20)).select(col("store_sk"),
                                              col("return_amt")),
    "project_above": lambda a, b: a.join(b, on=ON, how="left")
    .select((col("ext_sales") * lit(2)).alias("x"), col("store_sk"))
    .select(col("x")),
    "aggregate_above": lambda a, b: a.join(b, on=ON, how="left")
    .group_by("store_sk").agg(sum_("ext_sales", "s"),
                              count_("return_amt", "n")),
    "sort_above": lambda a, b: a.join(b, on=ON, how="inner")
    .select(col("store_sk"), col("ext_sales"), col("return_amt"))
    .order_by("ext_sales", "store_sk", "return_amt")
    .select(col("ext_sales"), col("return_amt")),
    "sort_reads_more_than_parent": lambda a, b: a.join(b, on=ON, how="left")
    .order_by("ext_sales", "quantity", "date_sk", "store_sk")
    .select(col("store_sk")),
    "window_above": lambda a, b: _window(a.join(b, on=ON, how="left")
                                         .select(col("store_sk"),
                                                 _ref(a, 3).alias("ticket"),
                                                 col("ext_sales"),
                                                 col("quantity")))
    .select(col("store_sk"), col("running")),
    "limit_above": lambda a, b: a.join(b, on=ON, how="inner")
    .order_by("ext_sales", "quantity", "return_amt").limit(7)
    .select(col("ext_sales"), col("return_amt")),
    "union_above": lambda a, b: a.join(b, on=ON, how="inner")
    .select(col("store_sk"), col("return_amt"))
    .union(a.join(b, on=ON, how="left_anti")
           .select(col("store_sk"), col("ext_sales")))
    .group_by("store_sk").agg(sum_("return_amt", "s")),
    # operators below the join
    "filter_below": lambda a, b: a.filter(col("quantity") > lit(30))
    .join(b.filter(col("return_amt") > lit(100)), on=ON, how="left")
    .select(col("store_sk"), col("return_amt")),
    "project_below": lambda a, b: a.select(
        col("ticket"), col("item_sk"), col("store_sk"),
        (col("ext_sales") + col("net_profit")).alias("gross"),
        (col("quantity") * lit(3)).alias("unread"))
    .join(b, on=ON, how="left").select(col("store_sk"), col("gross")),
    "aggregate_below": lambda a, b: a.group_by("ticket", "item_sk")
    .agg(sum_("ext_sales", "s"), sum_("quantity", "q"))
    .join(b, on=ON, how="left").select(col("s"), col("return_amt")),
    "sort_below": lambda a, b: a.order_by("ext_sales", "date_sk")
    .join(b, on=ON, how="inner").select(col("store_sk")),
    "window_below": lambda a, b: _window(a).join(b, on=ON, how="left")
    .select(col("running"), col("return_amt")),
    "limit_below": lambda a, b: a.order_by(
        "ext_sales", "quantity", "date_sk", "ticket").limit(50)
    .join(b, on=ON, how="left").select(col("store_sk"), col("return_amt")),
    "union_below": lambda a, b: a.union(a).join(b, on=ON, how="left")
    .select(col("store_sk"), col("return_amt")),
    "sample_below": lambda a, b: a.sample(0.5, seed=3)
    .join(b, on=ON, how="left").select(col("store_sk"), col("return_amt")),
    "repartition_below": lambda a, b: a.repartition(3, "store_sk")
    .join(b, on=ON, how="left").select(col("quantity")),
    "cached_below": lambda a, b: a.cache().join(b, on=ON, how="left")
    .select(col("store_sk"), col("return_amt")),
    "join_below_join": lambda a, b: a.join(b, on=ON, how="left")
    .select(_ref(a, 3), col("store_sk"), col("return_amt"))
    .join(b.select(col("ticket"), col("return_amt").alias("again")),
          on="ticket", how="inner").select(col("store_sk"), col("again")),
}


@pytest.mark.parametrize("shape,conf_name", [
    (shape, conf_name) for shape in _SHAPES
    for conf_name in ("shuffled", "broadcast")
    if conf_name == "shuffled" or shape in (
        "filter_above", "aggregate_above", "project_below", "window_below",
        "join_below_join")])
def test_operators_above_and_below_the_join(shape, conf_name):
    conf = SHUFFLED if conf_name == "shuffled" else BROADCAST
    assert_tpu_and_cpu_are_equal_collect(
        lambda s: _SHAPES[shape](sales(s), returns(s)), conf)


def test_count_star_keeps_the_narrowest_column():
    s = TpuSession(SHUFFLED)
    df = sales(s).agg(count_(None, "n"))
    scan = [n for n in _walk(prune_columns(df.plan))
            if isinstance(n, PN.LocalTableScan)]
    assert [f.name for f in scan[0].output.fields] == ["date_sk"]
    assert df.collect() == [(_N,)]


def test_unread_projected_expression_is_dropped_unless_nondeterministic():
    from spark_rapids_tpu.expr.misc import Rand

    s = TpuSession()
    df = sales(s).select(col("store_sk"),
                         (col("quantity") * lit(2)).alias("unread"),
                         Rand(1).alias("r")).select(col("store_sk"))
    inner = prune_columns(df.plan).children[0]
    assert [e.name for e in inner.exprs] == ["store_sk", "r"]
    assert sorted(df.collect()) == sorted((v,) for v in _SALES["store_sk"])


def test_opaque_expressions_read_every_column():
    """A lambda body is bound against an extended schema: the pass cannot
    list its references, so its node keeps all of its child's columns."""
    from spark_rapids_tpu.expr.hof import ArrayTransform

    def q(s):
        df = s.create_dataframe(
            {"a": [1, 2, 3], "arr": [[1, 2], [3], []], "b": [5, 6, 7]},
            T.StructType([T.StructField("a", T.INT),
                          T.StructField("arr", T.ArrayType(T.INT)),
                          T.StructField("b", T.INT)]))
        return df.select(ArrayTransform(col("arr"), "x",
                                        col("x") + col("b")).alias("t"))

    assert_tpu_and_cpu_are_equal_collect(q)
    plan = q(TpuSession()).plan
    assert prune_columns(plan) is plan


# -- the user's plan and the plans the pass must leave alone ---------------

def _identity_of(plan):
    ids = []
    for n in _walk(plan):
        ids.append(id(n))
        for v in vars(n).values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for e in vs:
                e = e[0] if isinstance(e, tuple) else e
                if hasattr(e, "collect"):
                    ids += [(id(x), getattr(x, "ordinal", None))
                            for x in e.collect(lambda _: True)]
    return ids


def test_reused_dataframe_still_sees_the_full_schema():
    s = TpuSession(SHUFFLED)
    o = TpuSession({"spark.rapids.sql.enabled": False})

    def both(sess):
        j = sales(sess).join(returns(sess), on=ON, how="left")
        first = j.select(col("store_sk"), col("return_amt"))
        return j, first

    j, first = both(s)
    oj, ofirst = both(o)
    before = _identity_of(j.plan)
    assert sorted(first.collect(), key=str) == sorted(ofirst.collect(),
                                                      key=str)
    # the user's nodes and expressions are the same objects, unmoved
    assert _identity_of(j.plan) == before
    assert j.columns == oj.columns and len(j.columns) == 10
    second = j.select(col("net_profit"), col("date_sk"), _ref(j, 7))
    osecond = oj.select(col("net_profit"), col("date_sk"), _ref(oj, 7))
    assert sorted(second.collect(), key=str) == sorted(osecond.collect(),
                                                       key=str)
    assert sorted(j.collect(), key=str) == sorted(oj.collect(), key=str)


def test_oracle_path_runs_the_unpruned_plan():
    o = TpuSession({"spark.rapids.sql.enabled": False})
    df = sales(o).join(returns(o), on=ON, how="left").select(col("store_sk"))
    root, meta = df._planned()
    assert root is df.plan and meta is None


_FULL_READS = {
    "scan": lambda a, b: a,
    # Q6's shape: filter, project and sum over every column of the table
    "q6_like": lambda a, b: b.filter(
        (col("ticket") < lit(9)) & (col("item_sk") > lit(1)))
    .agg(sum_("return_amt", "revenue")),
    "join_all_columns": lambda a, b: a.join(b, on=ON, how="left"),
    "sorted_join": lambda a, b: a.join(b, on=ON, how="inner")
    .order_by("ext_sales"),
}


@pytest.mark.parametrize("shape", list(_FULL_READS))
def test_query_reading_every_column_plans_to_the_identical_tree(shape):
    from spark_rapids_tpu.overrides import TpuOverrides

    s = TpuSession(SHUFFLED)
    df = _FULL_READS[shape](sales(s), returns(s))
    snap = PC.snapshot()
    assert prune_columns(df.plan) is df.plan
    with_pass = df._planned()[0].pretty()
    without = TpuOverrides.apply(df.plan, s.conf)[0].pretty()
    assert with_pass == without
    assert "emit=" not in with_pass
    assert PC.since(snap)["plan_columns_pruned"] == 0


def test_counter_and_explain_say_the_pass_engaged():
    s = TpuSession(SHUFFLED)
    df = (sales(s).join(returns(s), on=ON, how="left")
          .select(col("store_sk"), col("ext_sales"), col("return_amt")))
    snap = PC.snapshot()
    text = df.explain()
    assert "emit=[store_sk, ext_sales, return_amt]" in text
    # 3 of store_sales' 7 at the scan, 4 of the join's 7 at the join
    assert PC.since(snap)["plan_columns_pruned"] == 7
    df.collect()
    df.collect()
    assert PC.since(snap)["plan_columns_pruned"] == 7, \
        "bumped once per planning, not per collect"


# -- the consumers -----------------------------------------------------------

def _qb(s, rows=512):
    """The benchmark's shuffled-join query over its own generators."""
    from benchmark.datagen import store_returns, store_sales
    from benchmark.harness.cell import _resident_frame
    from benchmark.queries import qb_left_join

    rng = np.random.default_rng(11)
    ss = store_sales.make(rows, rng)
    sr = store_returns.make(rows // 10, rng, parent=ss)
    frames = {"store_sales": _resident_frame(s, ss, store_sales.TYPES),
              "store_returns": _resident_frame(s, sr, store_returns.TYPES)}
    return qb_left_join.build(frames), frames, (ss, sr)


def _gather_words(jaxpr) -> int:
    """32-bit words moved by the program's gathers: a full-width random
    access pass each (an int64 column is two on the chip)."""
    words = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            words += max(1, eqn.outvars[0].aval.dtype.itemsize // 4)
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                words += _gather_words(sub.jaxpr)
    return words


def test_materialize_pairs_gathers_only_the_emitted_columns(monkeypatch):
    """qb reads store_sk, ext_sales and return_amt of the join's ten
    columns: 5 words + 3 validity = 8 column gathers beside the 4 index
    gathers of the pair expansion, not 26 + 4.  (qb's build keys are
    unique, so the join looks them up instead; the pair path is forced
    here.)"""
    import jax

    from spark_rapids_tpu.exec.join import _BaseTpuJoinExec

    orig = _BaseTpuJoinExec.materialize_pairs
    seen = []

    def spy(*args):
        seen.append(jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") else x, args))
        return orig(*args)

    monkeypatch.setattr(_BaseTpuJoinExec, "materialize_pairs",
                        staticmethod(spy))
    monkeypatch.setattr(_BaseTpuJoinExec, "_unique_build",
                        lambda self, build: False)
    s = TpuSession({**SHUFFLED,
                    "spark.rapids.tpu.scan.cacheDeviceBatches": True})
    df, _, (ss, sr) = _qb(s)
    from benchmark.queries import qb_left_join

    assert qb_left_join.answer(df.collect()) == qb_left_join.reference(
        {"store_sales": ss, "store_returns": sr})
    assert len(seen) == 1
    args = seen[0]
    b_cols, p_cols = args[1], args[2]
    assert (len(p_cols), len(b_cols)) == (2, 1)

    def words(b, p):
        fn = lambda ri, bc, pc, lo, cn, um, tot, nr: orig(  # noqa: E731
            ri, bc, pc, lo, cn, um, tot, nr, args[8], args[9])
        return _gather_words(jax.make_jaxpr(fn)(
            args[0], b, p, *args[3:8]).jaxpr)

    index_gathers = words((), ())
    assert index_gathers == 4
    assert words(b_cols, p_cols) - index_gathers == 8
    # the same plan without the pass: 7 + 3 columns, 11 + 5 words and 10
    # validity vectors
    from spark_rapids_tpu.config import ambient_conf
    from spark_rapids_tpu.overrides import TpuOverrides

    seen.clear()
    root, _ = TpuOverrides.apply(df.plan, s.conf)
    with ambient_conf(s.conf):
        list(root.execute_columnar())
    assert (len(seen[0][2]), len(seen[0][1])) == (7, 3)
    assert words(seen[0][1], seen[0][2]) - index_gathers == 26


def test_bare_reference_projection_launches_no_program():
    from spark_rapids_tpu.exec.basic import TpuProjectExec, _selection

    s = TpuSession()
    a = sales(s)
    # below a sort whose child (a union) cannot narrow itself the pass
    # inserts a projection of bare references
    df = a.union(a).order_by("ext_sales", "date_sk", "quantity") \
        .select(col("ext_sales"))
    inserted = [n for n in _walk(prune_columns(df.plan))
                if isinstance(n, PN.Project)
                and isinstance(n.children[0], PN.Union)]
    assert len(inserted) == 1
    assert [e.name for e in inserted[0].exprs] == ["date_sk", "quantity",
                                                   "ext_sales"]
    # executed alone it selects column objects
    scan = s.create_dataframe(_SALES, _SALES_T)._planned()[0]
    proj = TpuProjectExec(
        [_ref(a, 5).alias("x").resolve(a.schema), _ref(a, 1)], scan)
    assert _selection(proj.ops) == [5, 1]
    batches = list(scan.execute_columnar())
    snap = PC.snapshot()
    out = list(proj.execute_columnar())
    delta = PC.since(snap)
    assert delta["programs_launched"] == 0 and delta["compiles"] == 0
    assert out[0].columns[0].data is not None
    assert out[0].schema.field_names() == ["x", "store_sk"]
    assert np.array_equal(np.asarray(out[0].columns[1].data),
                          np.asarray(batches[0].columns[1].data))
    # and a user's bare select over a scan costs no program end to end
    snap = PC.snapshot()
    rows = a.select(col("ext_sales"), col("store_sk")).collect()
    assert PC.since(snap)["programs_launched"] == 0
    assert rows == list(zip(_SALES["ext_sales"], _SALES["store_sk"]))


def test_resident_cache_survives_a_replan_and_is_shared_by_column():
    s = TpuSession({**SHUFFLED,
                    "spark.rapids.tpu.scan.cacheDeviceBatches": True})
    df, frames, _ = _qb(s)
    snap = PC.snapshot()
    first = df.collect()
    uploaded = PC.since(snap)["bytes_h2d"]
    assert uploaded > 0
    # a second planning (a new DataFrame over the same frames): no upload
    from benchmark.queries import qb_left_join

    again = qb_left_join.build(frames)
    assert again._planned()[0] is not df._planned()[0]
    snap = PC.snapshot()
    assert sorted(again.collect()) == sorted(first)
    assert PC.since(snap)["bytes_h2d"] == 0
    # a query reading one more column uploads that column alone
    cap = frames["store_sales"].plan._device_cache["cols"][1][0].capacity
    snap = PC.snapshot()
    frames["store_sales"].select(col("store_sk"), col("quantity")).collect()
    assert PC.since(snap)["bytes_h2d"] == cap * 8 + cap  # int64 + validity
    # four of store_sales' seven columns were resident for qb
    cache = frames["store_sales"].plan._device_cache
    assert sorted(cache["cols"]) == [1, 2, 3, 4, 5]


def test_parquet_scan_reads_only_the_named_columns(tmp_path, monkeypatch):
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = str(tmp_path / "sales.parquet")
    pq.write_table(pa.table({k: pa.array(v, pa.int64())
                             for k, v in _SALES.items()}), path)
    read_columns = []
    orig = pq.ParquetFile.read_row_groups

    # the scan reads a file by runs of row groups (io/scan.py _open_units)
    def spy(self, row_groups, columns=None, **kw):
        tbl = orig(self, row_groups, columns=columns, **kw)
        read_columns.append(tbl.column_names)
        return tbl

    monkeypatch.setattr(pq.ParquetFile, "read_row_groups", spy)
    conf = {"spark.rapids.sql.format.parquet.deviceDecode.enabled": False}

    def q(s, cols):
        return s.read.parquet(path).filter(col("quantity") > lit(50)) \
            .select(*[col(c) for c in cols])

    s = TpuSession(conf)
    snap = PC.snapshot()
    rows = q(s, ["store_sk"]).collect()
    narrow = PC.since(snap)["bytes_h2d"]
    assert read_columns == [["store_sk", "quantity"]]
    snap = PC.snapshot()
    q(s, list(_SALES)).collect()
    wide = PC.since(snap)["bytes_h2d"]
    assert read_columns[1] == list(_SALES)
    assert narrow * 7 == wide * 2
    assert sorted(rows) == sorted(
        (st,) for st, qy in zip(_SALES["store_sk"], _SALES["quantity"])
        if qy > 50)


def test_text_scans_are_not_narrowed(tmp_path):
    """csv parses by position: the scan keeps its schema and the columns
    are dropped above it."""
    path = str(tmp_path / "t.csv")
    with open(path, "w") as f:
        f.write("a,b,c\n1,2,3\n4,5,6\n")
    s = TpuSession()
    df = s.read.csv(path).select(col("c"))
    scan = [n for n in _walk(prune_columns(df.plan))
            if isinstance(n, PN.FileSourceScan)]
    assert len(scan[0].output.fields) == 3
    assert_tpu_and_cpu_are_equal_collect(
        lambda sess: sess.read.csv(path).select(col("c")))
