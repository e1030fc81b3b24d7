"""The TPC-DS star join (benchmark cell ``ds_broadcast_join_agg``) through
the engine against its plain numpy reference, on both dimension lookups
of ``TpuJoinAggFusedExec``'s one-program path: a 2,555-row calendar pads
to 8,192 rows and rides the MXU one-hot contraction, the specification's
73,049 rows pad to 262,144 and take the VPU gathers behind a merge-rank
search; each with keys and measure nullable and not.

Collected twice: the first collect asks the sorted build side whether
its keys are unique and takes the one-program path at once (the general
path, with its size sync and pair expansion, is for a build side with
duplicate keys: tests/test_fusion_perf.py), the second runs it again."""
import numpy as np
import pytest

from benchmark.datagen import date_dim, date_dim_spec, store_sales_star
from benchmark.harness import cell as C
from benchmark.queries import qa_broadcast_join_agg as QA

from chip_smoke import _find_exec

FACT_ROWS = 3000
COUNTERS = ("joinagg_general_probes", "joinagg_unique_probes",
            "join_lookups_mxu", "join_lookups_vpu", "agg_groups_cap_regrows")


@pytest.fixture(scope="module")
def session():
    from spark_rapids_tpu.session import TpuSession

    return TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.resilience.runtimeFallbackEnabled": False,
        "spark.rapids.tpu.scan.cacheDeviceBatches": True})


def _tables(calendar, nullable, seed=29):
    rng = np.random.default_rng(seed)
    dd = calendar.make(calendar.N_DATES, rng)
    ss = store_sales_star.make(FACT_ROWS, rng)
    types = list(store_sales_star.TYPES)
    if not nullable:
        ss = {c: np.ma.getdata(v) for c, v in ss.items()}
        types = [t.removesuffix("?") for t in types]
    return {"store_sales": ss, "date_dim": dd}, types


def _no_null_sum(answer):
    """No group of the answer has a NULL sum (every key is a pair)."""
    return all(len(k) == 2 for k in answer)


def _without_mask(tables, column):
    """The planted fault: the values under ``column``'s mask as values."""
    ss = dict(tables["store_sales"])
    ss[column] = np.ma.MaskedArray(np.ma.getdata(ss[column]), mask=False)
    return {**tables, "store_sales": ss}


@pytest.mark.parametrize("nullable", [False, True], ids=["plain", "nullable"])
@pytest.mark.parametrize("calendar,lookup,build_cap", [
    (date_dim, "mxu", 8192), (date_dim_spec, "vpu", 262144)],
    ids=["2555_rows_mxu", "73049_rows_vpu"])
def test_engine_matches_the_reference(session, calendar, lookup, build_cap,
                                      nullable):
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.exec.exchange import (
        TpuBroadcastExchangeExec,
        TpuShuffleExchangeExec,
    )
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec

    tables, types = _tables(calendar, nullable)
    frames = {
        "store_sales": C._resident_frame(session, tables["store_sales"],
                                         types, "store_sales"),
        "date_dim": C._resident_frame(session, tables["date_dim"],
                                      calendar.TYPES, "date_dim")}
    df = QA.build(frames)
    want = QA.reference(tables)
    assert len(want) > 40 and _no_null_sum(want)

    moved = []
    for _ in range(2):
        snap = PC.snapshot()
        assert QA.answer(df.collect()) == want
        delta = PC.since(snap)
        moved.append((delta["programs_launched"], delta["host_syncs"])
                     + tuple(delta[k] for k in COUNTERS))
    # one probe batch a collect, one call of the one-program path on the
    # lookup the build capacity chose; the first collect's third program
    # and second sync ask whether the build keys are unique
    unique = tuple(int(k in ("joinagg_unique_probes",
                             "join_lookups_" + lookup)) for k in COUNTERS)
    assert moved == [(3, 3) + unique, (2, 2) + unique]

    root = df._planned()[0]
    fused = _find_exec(root, TpuJoinAggFusedExec)
    assert fused is not None and fused._build_unique is True
    assert _find_exec(root, TpuBroadcastExchangeExec) is not None
    assert _find_exec(root, TpuShuffleExchangeExec) is None
    assert fused.describe().endswith(
        f" path=unique lookup={lookup} build_cap={build_cap}")
    assert f"lookup={lookup}" in root.pretty()

    if nullable:
        # a null store is a group of its own, in more than one year
        assert sum(k[1] is None for k in want) >= 4
        # and the test would see the reference's null handling planted
        # wrong: a null date key matched by the value under its mask, a
        # null measure added, the null-store rows given to a store
        for column in ("date_sk", "ext_sales", "store_sk"):
            assert QA.reference(_without_mask(tables, column)) != want, column


def test_describe_says_nothing_of_a_lookup_before_the_first_collect(session):
    from spark_rapids_tpu.exec.fused import TpuJoinAggFusedExec

    tables, types = _tables(date_dim, False)
    frames = {t: C._resident_frame(session, tables[t], ty, t)
              for t, ty in (("store_sales", types),
                            ("date_dim", date_dim.TYPES))}
    fused = _find_exec(QA.build(frames)._planned()[0], TpuJoinAggFusedExec)
    assert fused.describe().endswith("]") and "lookup=" not in fused.describe()
