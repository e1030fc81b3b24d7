"""The cell ``q18_mesh_groupby_4chip`` (TPC-H Q18's IN-subquery aggregate
over four chips) brings its own control and planted faults (its "two
seeds", CPU rehearsal and span rehearsal are cases of the manifest's
tests, with no edit): the configuration states SF10 whole on four chips,
the bfloat16 control comes out not correct at the cell's size, and a chip
that drops a boundary order's rows, an order routed to two chips or a
``>=`` in the HAVING's place is caught by the comparison that decides
``correct``.  An engine that counts no all-to-all quota is refused when
the query is built.  The engine itself is held against the same
reference in tests/test_mesh_groupby_q18.py.

At the rehearsal's size (1/4000 of SF10) an order over 300 is rare, so
the faults are planted with the HAVING's value at 100: orders qualify,
and some sum to exactly 100.00."""
from decimal import Decimal

import numpy as np
import pytest

from benchmark import control
from benchmark.harness import check
from benchmark.harness.manifest import Manifest

from test_benchmark_harness import _verdict, tiny
from test_benchmark_rehearsal import _measure

CELL = "q18_mesh_groupby_4chip"
SEED = 2**31 + 18


def test_the_configuration_is_sf10_on_four_chips():
    full = Manifest().cell(CELL)
    assert full.table_rows == {"lineitem": 59_986_052}
    assert full.fact_rows == 59_986_052 and full.chips == 4
    assert full.config["reduced"] == [] and full.config["scale_factor"] == 10
    conf = full.conf
    assert conf["spark.rapids.tpu.mesh.enabled"] is True
    assert conf["spark.rapids.shuffle.mode"] == "ICI"
    assert conf["spark.rapids.tpu.mesh.devices"] == 4
    assert conf["spark.rapids.tpu.scan.cacheDeviceBatches"] is True
    assert full.plan["expect"] == ["TpuIciShuffleAggExec"]
    assert set(full.plan["forbid"]) == {"TpuShuffleExchangeExec",
                                        "TpuHashAggregateExec"}
    assert full.query.THRESHOLD == 300
    # the fullest chip's shard: 14,996,513 rows of two 8-byte columns
    assert full.query.min_bytes(full.table_rows) == 14_996_513 * 16


def test_the_generator_keeps_dbgens_shapes():
    gen = Manifest().cell(CELL).generators["lineitem"]
    t = gen.make(400_000, np.random.default_rng([SEED, 0]))
    keys, qty = t["l_orderkey"], t["l_quantity"]
    assert len(keys) == len(qty) == 400_000
    assert (np.diff(keys) >= 0).all()
    orders, lines = np.unique(keys, return_counts=True)
    assert len(orders) == gen.orders_of(400_000) == 100_023
    assert lines.min() == 1 and lines.max() == 7
    # dbgen's sparse keys: 8 of every 32 values
    assert set(np.unique(orders % 32)) == set(range(8))
    assert orders[:9].tolist() == [1, 2, 3, 4, 5, 6, 7, 32, 33]
    assert qty.min() == 100 and qty.max() == 5000 and (qty % 100 == 0).all()


def test_the_bfloat16_control_comes_out_not_correct():
    """At the cell's own size: sums past 256 lose their last unit."""
    compared = control.control(Manifest().cell(CELL), SEED)
    assert not check.is_correct(compared)
    assert compared["max_abs_err"]["value"] >= 100
    assert compared["groups_off"]["value"] > 0


@pytest.fixture
def cell(monkeypatch):
    c = tiny(Manifest().cell(CELL))
    monkeypatch.setattr(c.query, "THRESHOLD", 100)
    return c


def _drop_a_boundary_orders_rows(monkeypatch, cell):
    """Chip 0's partial aggregate reads one row fewer than the chip holds:
    the last line of its shard, a line of the order that goes on on
    chip 1, is lost."""
    import jax

    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec

    real = TpuHashAggregateExec._agg_fn

    def agg_fn(self, cols, num_rows, row_valid=None, groups_cap=None):
        if row_valid is None:           # the partial, inside program (a)
            num_rows = num_rows - (jax.lax.axis_index("dp") == 0)
        return real(self, cols, num_rows, row_valid, groups_cap)

    monkeypatch.setattr(TpuHashAggregateExec, "_agg_fn", agg_fn)


def _route_an_order_to_two_chips(monkeypatch, cell):
    """Chip 1 sends its first group, the rest of an order that began on
    chip 0, one peer past where chip 0 sent the rest."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import hashing

    real = hashing.spark_partition_ids

    def routed(cols, n, seed=42):
        t = real(cols, n, seed)
        on_one = jax.lax.axis_index("dp") == 1
        return t.at[0].set(jnp.where(on_one, (t[0] + 1) % n, t[0]))

    monkeypatch.setattr(hashing, "spark_partition_ids", routed)


def _greater_or_equal(monkeypatch, cell):
    from spark_rapids_tpu.session import col, lit, sum_

    def build(frames):
        return (frames["lineitem"].group_by("l_orderkey")
                .agg(sum_("l_quantity", "sum_qty"))
                .filter(col("sum_qty") >= lit(Decimal(cell.query.THRESHOLD))))

    monkeypatch.setattr(cell.query, "build", build)


# a boundary order's partial sums, each kept or filtered on its own
# chip, show as a wrong sum or as a group off
WRONG = ("max_abs_err", "groups_off")


@pytest.mark.parametrize("fault,fails", [
    (_drop_a_boundary_orders_rows, WRONG),
    (_route_an_order_to_two_chips, WRONG),
    (_greater_or_equal, ("groups_off",)),
])
def test_a_planted_fault_comes_out_not_correct(monkeypatch, cell, fault,
                                               fails):
    tables = {"lineitem": cell.generators["lineitem"].make(
        cell.fact_rows, np.random.default_rng([SEED, 0]))}
    keys = tables["lineitem"]["l_orderkey"]
    per = -(-len(keys) // 4)
    assert keys[per - 1] == keys[per], "no order spans chips 0 and 1"
    want = cell.query.reference(tables)
    sums = {}
    for k, q in zip(keys, tables["lineitem"]["l_quantity"]):
        sums[int(k)] = sums.get(int(k), 0) + int(q)
    assert 10_000 in sums.values()      # an order of exactly 100.00
    assert (int(keys[per]),) in want
    fault(monkeypatch, cell)
    _, res = _measure(CELL, cell=cell, seed=SEED)
    assert res["correct"] is False
    # every collect answered: the comparison, not a failure, refuses it
    assert res["compared"]["failed_collects"]["value"] == 0
    assert any(res["compared"][f]["value"] > 0 for f in fails), \
        res["compared"]


def test_an_order_answered_twice_is_a_group_off():
    q = Manifest().cell(CELL).query
    rows = [(7, Decimal("301.00")), (9, Decimal("320.00"))]
    want = q.answer(rows)
    assert want == {(7,): 30100, (9,): 32000}
    twice = q.answer(rows + [(7, Decimal("301.00"))])
    ok, values = _verdict([twice], want)
    assert not ok and values["groups_off"] == 1



def test_a_mesh_aggregate_sized_by_the_shard_is_refused(monkeypatch):
    """The parent of the counted exchange counts no ``ici_quota_rows``,
    and the query refuses it before any program compiles."""
    from spark_rapids_tpu import perfcounters

    counters = dict(perfcounters.COUNTERS)
    del counters["ici_quota_rows"]
    monkeypatch.setattr(perfcounters, "COUNTERS", counters)
    with pytest.raises(RuntimeError, match="counts no ici_quota_rows"):
        _measure(CELL)
