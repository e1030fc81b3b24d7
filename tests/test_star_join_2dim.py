"""Query 43's star (benchmark cell ``ds_star_join_2dim``) through the
engine: store_sales looked up in date_dim AND store inside ONE
``TpuJoinAggFusedExec`` program, against the query's plain numpy
reference, and a third dimension (``item``, this file's alone) against the
CPU row oracle.

What the chain must keep: each dimension's lookup takes its own branch by
its own build capacity (the calendar's 262,144 rows the VPU merge, the
store's 1,024 the MXU, by binary search under a 16,384-row probe and by
the merge sort past it); a null key matches nothing in either dimension;
the store's two history rows of one (name, id) are ONE group, whose
strings come back from a code carried through the lookup; a dimension
whose keys repeat runs the joins unfused, with the same answer; and the
programs of ``ds_broadcast_join_agg`` and of this star keep the registry
keys and the code they had."""
import numpy as np
import pytest

from benchmark.datagen import (
    date_dim_days,
    store_sales_q43,
    store_sales_star,
    store_spec,
)
from benchmark.harness import cell as C
from benchmark.queries import q43_star_join_2dim as Q

from chip_smoke import _find_exec

CONF = {"spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.resilience.runtimeFallbackEnabled": False,
        "spark.rapids.tpu.scan.cacheDeviceBatches": True}
COUNTERS = ("joinagg_fused_lookups", "join_rows_materialized",
            "join_lookups_mxu", "join_lookups_vpu", "join_matches_merge",
            "join_matches_gather", "joinagg_unique_probes",
            "joinagg_general_probes")
GENERATORS = {"store_sales": store_sales_q43, "date_dim": date_dim_days,
              "store": store_spec}


@pytest.fixture(scope="module")
def session():
    from spark_rapids_tpu.session import TpuSession

    return TpuSession(dict(CONF))


def _tables(fact_rows, seed=43):
    return {t: GENERATORS[t].make(
        fact_rows if t == "store_sales" else
        {"date_dim": date_dim_days.N_DATES, "store": 12}[t],
        np.random.default_rng([seed, i]))
        for i, t in enumerate(GENERATORS)}


def _frames(session, tables, types=None):
    types = types or {t: g.TYPES for t, g in GENERATORS.items()}
    return {t: C._resident_frame(session, cols, types[t], t)
            for t, cols in tables.items()}


def _moved(df):
    from spark_rapids_tpu import perfcounters as PC

    snap = PC.snapshot()
    rows = df.collect()
    delta = PC.since(snap)
    return rows, {k: delta[k] for k in COUNTERS if delta[k]}


@pytest.mark.parametrize("fact_rows,store_match", [
    (3000, "gather"), (20000, "merge")])
def test_both_lookups_in_one_program_match_the_reference(
        session, fact_rows, store_match):
    from spark_rapids_tpu.exec.exchange import TpuBroadcastExchangeExec
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec
    from spark_rapids_tpu.exec.join import TpuBroadcastHashJoinExec
    from spark_rapids_tpu.exec.sort import TpuTopNExec

    tables = _tables(fact_rows)
    df = Q.build(_frames(session, tables))
    want = Q.reference(tables)
    assert len({k[:2] for k in want}) == 6
    for _ in range(2):
        rows, moved = _moved(df)
        assert Q.answer(rows) == want
        # one call of the one program, two lookups in it: the calendar's
        # on the VPU behind the merge sort, the store's on the MXU
        assert moved == {"joinagg_fused_lookups": 2, "join_lookups_mxu": 1,
                         "join_lookups_vpu": 1, "joinagg_unique_probes": 1,
                         "join_matches_merge": 1 + (store_match == "merge"),
                         **({"join_matches_gather": 1}
                            if store_match == "gather" else {})}
    root = df._planned()[0]
    assert isinstance(root, TpuTopNExec)
    fused = _find_exec(root, TpuJoinAggFusedExec)
    assert fused is not None and fused._build_unique is True
    assert _find_exec(root, TpuBroadcastHashJoinExec) is None
    assert sum(isinstance(n, TpuBroadcastExchangeExec)
               for n in _walk(root)) == 2
    assert fused.describe().endswith(
        " path=unique lookup=vpu,mxu match=merge," + store_match
        + " build_cap=262144,1024")
    # the answer's rows: six stores, ordered by name and id
    assert [r[:2] for r in rows] == sorted(r[:2] for r in rows)


def _walk(node):
    yield node
    for c in getattr(node, "children", []):
        yield from _walk(c)


def test_what_the_aggregate_reads_of_one_dimension_is_computed_per_build_row(
        session):
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec

    df = Q.build(_frames(session, _tables(3000)))
    df.collect()
    fused = _find_exec(df._planned()[0], TpuJoinAggFusedExec)
    (agg, hoist), = fused._hoists.values()
    assert agg is fused.agg
    # the seven day predicates of date_dim, the group key tuple of store
    assert [[e.sql_string() for e in d] for d in hoist.derived] == [
        [f"(d_day_name = '{d}')" for d in date_dim_days.DAY_NAMES], []]
    assert [[e.sql_string() for e in d] for d in hoist.coded] == [
        [], ["s_store_name", "s_store_id"]]
    assert hoist.keys == [("code", 0, 1, 0), ("code", 0, 1, 1)]
    assert [g.sql_string() for g in hoist.agg.grouping] == ["__code1"]
    assert "d_day_name" not in hoist.agg.describe()


def test_a_null_key_in_either_dimension_matches_nothing(session):
    """The fact rows' nulls are the generator's; the test sees them:
    matched by the value under the mask, either key changes the answer."""
    tables = _tables(3000, seed=7)
    want = Q.reference(tables)
    assert Q.answer(Q.build(_frames(session, tables)).collect()) == want
    for column in ("date_sk", "store_sk"):
        ss = dict(tables["store_sales"])
        hidden = np.ma.getdata(ss[column])[np.ma.getmaskarray(ss[column])]
        assert len(hidden) > 50
        ss[column] = np.ma.MaskedArray(np.ma.getdata(ss[column]),
                                       mask=False)
        assert Q.reference({**tables, "store_sales": ss}) != want, column


def test_two_store_rows_of_one_name_and_id_are_one_group(session):
    tables = _tables(3000, seed=11)
    rows = Q.build(_frames(session, tables)).collect()
    st = tables["store"]
    kept_rows = int(np.sum(st["s_gmt_offset"] == -500))
    assert kept_rows == 10 and len(rows) == 6
    assert len({r[:2] for r in rows}) == 6
    # each store of two kept rows sums the sales of both
    sk_of = {}
    for sk, n, i, o in zip(st["store_sk"], st["s_store_name"],
                           st["s_store_id"], st["s_gmt_offset"]):
        if o == -500:
            sk_of.setdefault((n, i), []).append(int(sk))
    assert sorted(len(v) for v in sk_of.values()) == [1, 1, 2, 2, 2, 2]
    assert Q.answer(rows) == Q.reference(tables)


def test_a_repeated_key_in_a_dimension_runs_the_joins_unfused(session):
    """store_sk 1 on two rows of different stores: a sale of store 1
    counts in both groups.  The chain asks each build side whether its
    keys repeat, and runs every join materialized for this plan; the CPU
    row oracle answers the same."""
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec
    from spark_rapids_tpu.session import TpuSession

    tables = _tables(3000, seed=5)
    st = {c: v.copy() for c, v in tables["store"].items()}
    st["store_sk"][2] = 1                  # store 2's row now says 1
    tables["store"] = st
    df = Q.build(_frames(session, tables))
    oracle = Q.build(_frames(
        TpuSession({"spark.rapids.sql.enabled": False}), tables))
    want = Q.answer(oracle.collect())
    for _ in range(2):
        rows, moved = _moved(df)
        assert Q.answer(rows) == want
        assert "joinagg_fused_lookups" not in moved
        assert moved["join_rows_materialized"] > 3000 // 10
    fused = _find_exec(df._planned()[0], TpuJoinAggFusedExec)
    assert fused._build_unique is False


def test_a_third_dimension_joins_the_same_program(session):
    """store_sales against date_dim, store AND item (18,000 rows: the
    65,536 capacity, VPU): three lookups in one program, group keys from
    two dimensions, against the CPU row oracle."""
    from decimal import Decimal

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.base import Literal
    from spark_rapids_tpu.expr.conditional import CaseWhen
    from spark_rapids_tpu.expr.predicates import EqualTo
    from spark_rapids_tpu.session import TpuSession, col, count_, lit, sum_

    rng = np.random.default_rng(3)
    tables = _tables(5000, seed=3)
    star = store_sales_star.make(5000, np.random.default_rng(3))
    tables["store_sales"]["item_sk"] = star["item_sk"]
    n_items = store_sales_star.N_ITEMS
    categories = np.array(["Books", "Music", "Home", "Sports", None],
                          dtype=object)
    tables["item"] = {
        "item_sk": np.arange(1, n_items + 1, dtype=np.int32),
        "i_category": categories[rng.integers(0, 5, n_items)]}
    types = {t: g.TYPES for t, g in GENERATORS.items()}
    types["store_sales"] = types["store_sales"] + ["int"]
    types["item"] = ["int", "string"]

    def build(frames):
        offset = Literal(Decimal("-5.00"), T.DecimalType(5, 2))
        dates = frames["date_dim"].filter(EqualTo(col("d_year"), lit(2000)))
        stores = frames["store"].filter(
            EqualTo(col("s_gmt_offset"), offset))
        return (frames["store_sales"].join(dates, on="date_sk")
                .join(stores, on="store_sk").join(frames["item"],
                                                  on="item_sk")
                .group_by("s_store_name", "i_category")
                .agg(sum_(CaseWhen([(EqualTo(col("d_day_name"),
                                             lit("Sunday")),
                                     col("sales_price"))]), "sun"),
                     count_(None, "n")))

    def sorted_rows(df):
        return sorted(df.collect(), key=repr)

    frames = _frames(session, tables, types)
    for t in ("item",):
        frames[t] = C._resident_frame(session, tables[t], types[t], t)
    df = build(frames)
    oracle = build(_frames(TpuSession({"spark.rapids.sql.enabled": False}),
                           tables, types))
    want = sorted_rows(oracle)
    assert any(r[1] is None for r in want)        # a null category group
    for _ in range(2):
        rows, moved = _moved(df)
        assert sorted(rows, key=repr) == want
        assert moved["joinagg_fused_lookups"] == 3
        assert moved["join_lookups_vpu"] == 2
        assert moved["join_lookups_mxu"] == 1
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec

    fused = _find_exec(df._planned()[0], TpuJoinAggFusedExec)
    assert len(fused.joins) == 3 and len(fused.children) == 4


# sha256 of each program's StableHLO (the IR before XLA optimizes it;
# values numbered by position, no source locations) at the tiny sizes of
# test_the_fused_programs_keep_their_keys_and_their_code, by program kind
# and, for a join's build sort, the join's place in the star
FUSED_PROGRAMS = {
    "one_dim": {
        "build_has_dup":
            "8dffdfb03ed231a3a31dfa9cae4bde75c3eea7bca4b41c10c52b54f91cd5a117",
        "uniq_agg":
            "88ccd6df6373c73939d1694dcb60b43b92d4fb3edf953a370258cd2c12ca35cf",
        "join0:build_preops":
            "b2603c0d3fa06c4d39fe17e2e4c69804b33b89883db7d0fd9c2741bb1aa54b6f",
    },
    "two_dim": {
        "build_has_dup":
            "9e4fdd914f4274ac8fa840f54ad03b96bdbc6101158da403900cb10f607b3110",
        "uniq_agg":
            "cb7723736fb50fc48da04863415ea0b7efde33665538b642ab1e5f40f80f8c19",
        "join0:build_preops":
            "177d0bbfdc09097dc862839fa71b5faf4c14fca517a4e4a88c23225d1c389dab",
        "join1:build_preops":
            "9f9366c7f928e26a1fb29954d5b71b6260ec6570eb627134a7d660936f6385fe",
    },
}


def _program_digests(programs, calls):
    """{kind: sha256 of the StableHLO} of the jitted ``programs`` ({registry
    key: program}) as they were last called (``calls``)."""
    import hashlib

    out = {}
    for key, jitted in programs.items():
        args, kwargs = calls[id(jitted)]
        text = jitted.lower(*args, **kwargs).as_text()
        kind = key if isinstance(key, str) else key[0]
        out[kind] = hashlib.sha256(text.encode()).hexdigest()
    return out


@pytest.mark.parametrize("star", ["one_dim", "two_dim"])
def test_the_fused_programs_keep_their_keys_and_their_code(
        session, monkeypatch, star):
    """``ds_broadcast_join_agg``'s and ``ds_star_join_2dim``'s programs:
    scope, keys and code (each build sort, the has-dup program, the one
    program) as pinned, however the unfused join beside them changes."""
    from benchmark.datagen import date_dim_spec
    from benchmark.queries import qa_broadcast_join_agg as QA
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec

    calls = {}
    counted = PC._CountingJit.__call__

    def spy(self, *args, **kwargs):
        calls[id(self)] = (args, kwargs)
        return counted(self, *args, **kwargs)

    monkeypatch.setattr(PC._CountingJit, "__call__", spy)
    if star == "one_dim":
        rng = np.random.default_rng(1)
        tables = {"store_sales": store_sales_star.make(3000, rng),
                  "date_dim": date_dim_spec.make(date_dim_spec.N_DATES,
                                                 rng)}
        frames = {"store_sales": C._resident_frame(
            session, tables["store_sales"], store_sales_star.TYPES),
            "date_dim": C._resident_frame(session, tables["date_dim"],
                                          date_dim_spec.TYPES)}
        df = QA.build(frames)
        assert QA.answer(df.collect()) == QA.reference(tables)
    else:
        tables = _tables(3000)
        df = Q.build(_frames(session, tables))
        assert Q.answer(df.collect()) == Q.reference(tables)
    fused = _find_exec(df._planned()[0], TpuJoinAggFusedExec)
    agg = fused.agg
    assert fused._registry_scope() == (
        ("joinagg",) + sum((j._registry_scope()
                            for j in reversed(fused.joins)), ())
        + (agg._program_fp(),))
    if star == "one_dim":
        join, = fused.joins
        assert fused._hoist(agg) is None
        B = agg._bounded_groups_cap(4096)
        assert set(fused._jit_cache) == {
            "build_has_dup", ("uniq_agg", agg._program_fp(), B)}
        assert fused.describe() == (
            f"TpuJoinAggFused[{agg.describe()} <- {join.describe()}] "
            "path=unique lookup=vpu match=merge build_cap=262144")
    digests = _program_digests(fused._jit_cache, calls)
    for i, join in enumerate(fused.joins):
        # the unfused join never ran: its build sort, nothing else
        assert [k[0] for k in join._jit_cache] == ["build_preops"]
        digests.update({f"join{i}:{kind}": d for kind, d in
                        _program_digests(join._jit_cache, calls).items()})
    assert digests == FUSED_PROGRAMS[star]


def test_the_group_code_names_the_first_row_of_each_tuple():
    import jax.numpy as jnp

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import DeviceColumn, HostColumn
    from spark_rapids_tpu.exec.fused import _group_code

    names = ["b", "a", None, "b", "a", None, "b", "c"]
    ids = [1, 1, 2, 1, 2, 2, 1, None]
    cols = [DeviceColumn.from_host(h, capacity=8) for h in (
        HostColumn.from_strings(names),
        HostColumn.from_numpy(
            np.array([0 if i is None else i for i in ids], np.int32),
            T.INT, validity=np.array([i is not None for i in ids])))]
    code = np.asarray(_group_code(cols).data)
    assert code.tolist() == [0, 1, 2, 0, 4, 2, 0, 7]
    assert jnp.asarray(code).dtype == jnp.int32


def test_packed_flags_come_back_with_their_nulls():
    import jax.numpy as jnp

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.exec.fused import _FLAGS_A_WORD, _Flag, _pack
    from spark_rapids_tpu.expr.base import BoundReference, EvalContext

    rng = np.random.default_rng(2)
    flags = [DeviceColumn(T.BOOLEAN, jnp.asarray(rng.random(64) < 0.8),
                          data=jnp.asarray(rng.random(64) < 0.5))
             for _ in range(19)]
    other = DeviceColumn(T.INT, jnp.ones(64, jnp.bool_),
                         data=jnp.arange(64, dtype=jnp.int32))
    cols = flags[:10] + [other] + flags[10:]
    carried = _pack(cols)
    assert len(carried) == 3                # two words and the int
    assert carried[2] is other
    keep = jnp.asarray(rng.random(64) < 0.7)
    carried = [DeviceColumn(c.dtype, c.validity & keep, data=c.data)
               for c in carried]
    batch = ColumnarBatch(carried, 64, T.StructType(
        [T.StructField(f"c{i}", c.dtype) for i, c in enumerate(carried)]))
    for k, c in enumerate(flags):
        word, bit = divmod(k, _FLAGS_A_WORD)
        got = _Flag(BoundReference(word, T.INT), bit).eval_tpu(
            EvalContext(batch))
        valid = np.asarray(c.validity & keep)
        assert (np.asarray(got.validity) == valid).all()
        assert (np.asarray(got.data)[valid] == np.asarray(c.data)[valid]).all()


def test_seven_day_sums_sort_the_flag_word_and_the_price_alone(
        session, monkeypatch):
    """Query 43's aggregate reads seven ``CASE WHEN flag THEN price END``:
    its sort carries the columns they read (the flag word and the price,
    4 operands) where the seven inputs would be 14, and the answer is the
    reference's.  A fact table of its own size, so that the program is
    traced here."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec

    seen = []
    carried_refs = TpuHashAggregateExec._carried_refs

    def spy(self, ctx, payload):
        out = carried_refs(self, ctx, payload)
        seen.append((sum(len(arrs) for _, _, arrs in payload),
                     out and sum(len(arrs) for _, _, arrs in out)))
        return out

    monkeypatch.setattr(TpuHashAggregateExec, "_carried_refs", spy)
    tables = _tables(700, seed=17)
    rows = Q.build(_frames(session, tables)).collect()
    assert Q.answer(rows) == Q.reference(tables)
    assert (14, 4) in seen, seen


@pytest.mark.parametrize("how", ["inner", "left"])
def test_one_dimension_with_string_keys(session, how):
    """A star of one dimension takes the per-build-row code too, on an
    INNER join alone: a LEFT join's unmatched fact row reads nulls, and
    ``coalesce(name, 'none')`` of a null is not null, so there the
    strings are gathered as they are.  Both against the CPU row
    oracle."""
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec
    from spark_rapids_tpu.expr.conditional import CaseWhen, Coalesce
    from spark_rapids_tpu.expr.predicates import EqualTo
    from spark_rapids_tpu.session import TpuSession, col, count_, lit, sum_

    tables = _tables(3000, seed=13)
    del tables["date_dim"]

    def build(frames):
        return (frames["store_sales"].join(frames["store"], on="store_sk",
                                           how=how)
                .group_by("s_store_id")
                .agg(sum_(CaseWhen([(EqualTo(
                    Coalesce([col("s_store_name"), lit("none")]),
                    lit("none")), col("sales_price"))]), "unnamed"),
                    count_(None, "n")))

    df = build(_frames(session, tables))
    want = sorted(build(_frames(TpuSession(
        {"spark.rapids.sql.enabled": False}), tables)).collect(), key=repr)
    for _ in range(2):
        assert sorted(df.collect(), key=repr) == want
    fused = _find_exec(df._planned()[0], TpuJoinAggFusedExec)
    hoist = fused._hoist(fused.agg)
    assert (hoist is None) == (how == "left")
    if how == "left":
        # the null-key fact rows are a group of their own, whose
        # coalesced name is 'none'
        assert any(r[0] is None and r[1] is not None for r in want)
