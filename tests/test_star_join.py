"""The TPC-DS star join (benchmark cell ``ds_broadcast_join_agg``) through
the engine against its plain numpy reference, on both dimension lookups
of ``TpuJoinAggFusedExec``'s one-program path: a 2,555-row calendar pads
to 8,192 rows and rides the MXU one-hot contraction behind a binary
search that gathers the key words, the specification's 73,049 rows pad
to 262,144 and take the VPU gathers, match and position from the merge
sort; each with keys and measure nullable and not.  The merge branch on
both sides of its payload rule: the fact rows' 8,192-row bucket under
the calendar's 262,144 (``row_index[loc]``), and 10,000 fact rows
against 10,000 days, both at 65,536 (the payload permuted into key
order); and under the MXU lookup: 1,000 days, which end before the fact
rows' dates do, in the 1,024-row bucket against 65,536.

Collected twice: the first collect asks the sorted build side whether
its keys are unique and takes the one-program path at once (the general
path, with its size sync and pair expansion, is for a build side with
duplicate keys: tests/test_fusion_perf.py), the second runs it again."""
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.datagen import date_dim, date_dim_spec, store_sales_star
from benchmark.harness import cell as C
from benchmark.queries import qa_broadcast_join_agg as QA

from chip_smoke import _find_exec

FACT_ROWS = 3000
COUNTERS = ("joinagg_general_probes", "joinagg_unique_probes",
            "join_lookups_mxu", "join_lookups_vpu", "join_matches_merge",
            "join_matches_gather", "agg_groups_cap_regrows")


def _spec_slice(n_dates, fact_rows):
    """Stands in for a calendar module: ``n_dates`` days of the
    specification's calendar from 1998-01-01 on (the fact rows' first
    year), joined by ``fact_rows`` fact rows instead of FACT_ROWS."""
    def make(n, rng):
        whole = date_dim_spec.make(date_dim_spec.N_DATES, rng)
        first = int(np.searchsorted(whole["d_year"], 1998))
        return {c: v[first:first + n] for c, v in whole.items()}

    return SimpleNamespace(N_DATES=n_dates, FACT_ROWS=fact_rows,
                           TYPES=date_dim_spec.TYPES, make=make)


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
    ss = store_sales_star.make(
        getattr(calendar, "FACT_ROWS", FACT_ROWS), rng)
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
@pytest.mark.parametrize("calendar,lookup,match,build_cap", [
    (date_dim, "mxu", "gather", 8192),
    (date_dim_spec, "vpu", "merge", 262144),
    (_spec_slice(10000, 10000), "vpu", "merge", 65536),
    (_spec_slice(1000, 10000), "mxu", "merge", 1024)],
    ids=["2555_rows_mxu", "73049_rows_vpu", "10000_rows_equal_caps",
         "1000_rows_mxu_merge"])
def test_engine_matches_the_reference(session, calendar, lookup, match,
                                      build_cap, nullable):
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
    assert len(want) > 30 and _no_null_sum(want)

    moved = []
    for _ in range(2):
        snap = PC.snapshot()
        assert QA.answer(df.collect()) == want
        delta = PC.since(snap)
        moved.append((delta["programs_launched"], delta["host_syncs"])
                     + tuple(delta[k] for k in COUNTERS))
    # one probe batch a collect, one call of the one-program path on the
    # lookup the build capacity chose and the match both capacities chose;
    # the first collect's third program and second sync ask whether the
    # build keys are unique
    unique = tuple(int(k in ("joinagg_unique_probes",
                             "join_lookups_" + lookup,
                             "join_matches_" + match)) for k in COUNTERS)
    assert moved == [(3, 3) + unique, (2, 2) + unique]

    root = df._planned()[0]
    fused = _find_exec(root, TpuJoinAggFusedExec)
    assert fused is not None and fused._build_unique is True
    assert _find_exec(root, TpuBroadcastExchangeExec) is not None
    assert _find_exec(root, TpuShuffleExchangeExec) is None
    assert fused.describe().endswith(
        f" path=unique lookup={lookup} match={match} build_cap={build_cap}")
    assert f"lookup={lookup} match={match}" in root.pretty()

    if nullable:
        # a null store is a group of its own, in more than one year
        assert sum(k[1] is None for k in want) >= 3
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
