"""The cell ``ds_broadcast_join_agg`` brings its own control, planted
faults and reader cases (its "two seeds", CPU rehearsal and span
rehearsal are cases of the manifest's tests, with no edit): the float32
control comes out not correct, a wrong null rule or a wrong calendar in
the answer is caught by the comparison that decides ``correct``, and the
two per-layer readers it adds read a hand-made table.  The engine itself
is held against the same reference in tests/test_star_join.py."""
import numpy as np
import pytest

from benchmark import control
from benchmark.harness import check
from benchmark.harness.cell import make_tables
from benchmark.harness.manifest import Manifest, load_module

from test_benchmark_harness import _verdict, tiny
from test_benchmark_spans import C, TABLE, _run

CELL = "ds_broadcast_join_agg"
FUSED = C + "/srt.execute/srt.op.TpuJoinAggFusedExec"


@pytest.fixture(scope="module")
def cell():
    return tiny(Manifest().cell(CELL), divisor=100)


@pytest.fixture(scope="module")
def tables(cell):
    return make_tables(cell, 2**31 + 29)


def test_the_configuration_states_the_specs_sizes(cell):
    full = Manifest().cell(CELL)
    assert full.table_rows == {"store_sales": 2_880_404, "date_dim": 73_049}
    assert full.fact_rows == 2_880_404 and full.chips == 1
    assert full.config["reduced"] == ["scale_factor"]
    assert full.query.min_bytes(full.table_rows) == \
        2_880_404 * 16 + 73_049 * 12
    # no pin of the join strategy: the broadcast is the engine's decision
    assert not any("Broadcast" in k or "broadcast" in k for k in full.conf)
    dd = make_tables(cell, 1)["date_dim"]
    assert (dd["date_sk"][0], dd["date_sk"][-1]) == (2415022, 2488070)
    leap_day = 2451604 - 2415022            # 2000-02-29
    assert [int(dd[c][leap_day]) for c in ("d_year", "d_moy", "d_qoy")] == \
        [2000, 2, 1]


def test_the_generator_leaves_the_stated_share_null(tables):
    ss = tables["store_sales"]
    for c in ("date_sk", "store_sk", "ext_sales"):
        assert 0.035 < np.ma.getmaskarray(ss[c]).mean() < 0.055, c
    assert not any(isinstance(ss[c], np.ma.MaskedArray)
                   for c in ("item_sk", "ticket"))
    assert ss["date_sk"].min() >= 2450816 and ss["date_sk"].max() <= 2452642
    assert set(ss["store_sk"].compressed()) == set(range(1, 13))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(cell, seed):
    compared = control.control(cell, seed)
    assert not check.is_correct(compared)
    assert compared["max_abs_err"]["value"] > 0
    assert compared["groups_off"]["value"] == 0


def _drop_the_null_groups(q, tables, want):
    return {k: v for k, v in want.items() if k[1] is not None}


def _match_a_null_key(q, tables, want):
    """A null ``date_sk`` joined by the value under its mask."""
    ss = dict(tables["store_sales"])
    ss["date_sk"] = np.ma.MaskedArray(np.ma.getdata(ss["date_sk"]),
                                      mask=False)
    return q.reference({**tables, "store_sales": ss})


def _one_group_off_by_a_cent(q, tables, want):
    first = next(iter(want))
    return {**want, first: want[first] + 1}


def _years_of_365_days(q, tables, want):
    """The old generator's calendar over the specification's keys."""
    dd = dict(tables["date_dim"])
    day = np.arange(len(dd["date_sk"]))
    dd["d_year"] = (1900 + day // 365).astype(np.int32)
    dd["d_qoy"] = ((day % 365) // 92 + 1).clip(1, 4).astype(np.int32)
    return q.reference({**tables, "date_dim": dd})


@pytest.mark.parametrize("fault,fails", [
    (_drop_the_null_groups, "groups_off"),
    (_match_a_null_key, "max_abs_err"),
    (_one_group_off_by_a_cent, "max_abs_err"),
    (_years_of_365_days, "groups_off"),
])
def test_a_planted_fault_comes_out_not_correct(cell, tables, fault, fails):
    want = cell.query.reference(tables)
    assert _verdict([dict(want)], want)[0]
    assert any(k[1] is None for k in want)
    ok, v = _verdict([fault(cell.query, tables, want)], want)
    assert not ok and v[fails] > 0 and v["wrong_answers"] == 1
    if fault is _one_group_off_by_a_cent:
        assert v["max_abs_err"] == 1 and v["groups_off"] == 0


def test_a_null_sum_is_a_group_apart():
    """``answer`` keys a NULL sum apart, so that a 0 in its place is a
    group off, not a silent match."""
    from decimal import Decimal

    q = load_module("queries", "qa_broadcast_join_agg")
    rows = [(1998, 3, Decimal("12.34")), (1998, None, Decimal("0.07")),
            (1999, 3, None)]
    got = q.answer(rows)
    assert got == {(1998, 3): 1234, (1998, None): 7,
                   (1999, 3, q.NULL_SUM): 0}
    zero = q.answer(rows[:2] + [(1999, 3, Decimal("0.00"))])
    assert _verdict([zero], got)[1]["groups_off"] == 2
    ss = {"date_sk": np.ma.MaskedArray([2450816, 2450816], mask=False),
          "store_sk": np.ma.MaskedArray([3, 3], mask=False),
          "ext_sales": np.ma.MaskedArray([5, 6], mask=True)}
    dd = {"date_sk": np.array([2450816]), "d_year": np.array([1998]),
          "d_qoy": np.array([1])}
    assert q.reference({"store_sales": ss, "date_dim": dd}) == \
        {(1998, 3, q.NULL_SUM): 0}


def test_the_two_readers_on_a_hand_made_table():
    joinagg = load_module("layer_metrics", "joinagg_self_ms_per_collect")
    mxu = load_module("layer_metrics", "mxu_lookups_per_collect")
    table = {
        C: (4, 40_000_000, 2_000_000),
        FUSED: (8, 36_000_000, 1_000_000),
        FUSED + "/srt.join.build": (4, 3_000_000, 400_000),
        FUSED + "/srt.join.build/srt.launch": (4, 2_600_000, 2_600_000),
        FUSED + "/srt.joinagg.unique": (4, 30_000_000, 600_000),
        FUSED + "/srt.joinagg.unique/srt.launch": (4, 1_400_000, 1_400_000),
        FUSED + "/srt.joinagg.unique/srt.sync": (4, 28_000_000, 28_000_000),
        # what the fused node pulls is another operator's
        FUSED + "/srt.op.TpuLocalTableScanExec": (8, 1_200_000, 1_200_000),
        FUSED + "/srt.op.TpuBroadcastExchangeExec": (8, 800_000, 500_000),
        FUSED + "/srt.op.TpuBroadcastExchangeExec/srt.sync":
            (4, 300_000, 300_000),
    }
    run = _run(table, join_lookups_mxu=0, join_lookups_vpu=4)
    assert joinagg.read(run) == pytest.approx(
        (1.0 + 0.4 + 2.6 + 0.6 + 1.4 + 28.0) / 4)
    assert mxu.read(run) == 0
    assert mxu.read(_run(table, join_lookups_mxu=6, join_lookups_vpu=2)) \
        == 1.5
    # another plan's table: the fused node never ran
    assert joinagg.read(_run(TABLE, join_lookups_mxu=0)) == 0
    # a program that has neither the spans nor the counters (the parent
    # of the PR that brought them): nothing to read, and no error
    assert joinagg.read(_run({})) is None
    assert mxu.read(_run(table)) is None
