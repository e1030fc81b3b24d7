"""TPC-H Q18's IN-subquery aggregate through ``TpuIciShuffleAggExec`` on
4 of the 8 virtual CPU devices: the mesh path, the single-device path and
the numpy reference of ``benchmark/queries/q18_orderkey_having.py`` agree,
and the all-to-all reserves the quota the partial's groups ask for.

The answers are compared with every order kept (``THRESHOLD`` 0): the
qualifying orders of Q18's 300 are a handful at these sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cell as C
from benchmark.harness.manifest import load_module

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 (virtual) devices")
pytestmark = needs_mesh

Q18 = load_module("queries", "q18_orderkey_having")
GEN = load_module("datagen", "lineitem_q18")
N_DEV = 4
BASE = {"spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.resilience.runtimeFallbackEnabled": False}
MESH = {**BASE, "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": True,
        "spark.rapids.tpu.mesh.devices": N_DEV}
RESIDENT = {"spark.rapids.tpu.scan.cacheDeviceBatches": True}
# one partial buffer row: l_orderkey int64 + validity, sum(l_quantity) as
# DECIMAL(22,2) (two int64 words) + validity
ROW_BYTES = 8 + 1 + 16 + 1


@pytest.fixture
def every_order(monkeypatch):
    monkeypatch.setattr(Q18, "THRESHOLD", 0)


def _tables(keys, qty):
    return {"lineitem": {"l_orderkey": np.asarray(keys, np.int64),
                         "l_quantity": np.asarray(qty, np.int64)}}


def _generated(rows, seed):
    return {"lineitem": GEN.make(rows, np.random.default_rng([seed, 0]))}


def _frame(session, tables):
    return C._resident_frame(session, tables["lineitem"], GEN.TYPES,
                             "lineitem")


def _session(conf):
    from spark_rapids_tpu.session import TpuSession

    return TpuSession(dict(conf))


def _ici(df):
    from chip_smoke import _find_exec
    from spark_rapids_tpu.exec.ici import TpuIciShuffleAggExec

    return _find_exec(df._planned()[0], TpuIciShuffleAggExec)


def _run(conf, tables, collects=2):
    """(answers of ``collects`` collects, the counters of the last one, the
    frame)."""
    from spark_rapids_tpu import perfcounters as PC

    df = Q18.build({"lineitem": _frame(_session(conf), tables)})
    assert (_ici(df) is not None) == conf.get(
        "spark.rapids.tpu.mesh.enabled", False)
    answers = []
    for _ in range(collects):
        snap = PC.snapshot()
        answers.append(Q18.answer(df.collect()))
        counters = PC.since(snap)
    return answers, counters, df


def _shards(keys):
    """Each device's rows in the stage's balanced layout."""
    per = -(-len(keys) // N_DEV)
    return [keys[d * per:(d + 1) * per] for d in range(N_DEV)]


def _targets(keys, n_dev=N_DEV):
    """Spark's murmur3 partition id of each LONG key (the engine's own
    hash, run here on the host's device)."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.ops.hashing import spark_partition_ids

    if len(keys) == 0:
        return np.zeros(0, np.int32)
    k = jnp.asarray(np.asarray(keys, np.int64))
    col = DeviceColumn(T.LONG, jnp.ones(len(keys), jnp.bool_), data=k)
    return np.asarray(spark_partition_ids([col], n_dev))


def send_matrix(keys):
    """[c][p]: the distinct keys of device c's shard that hash to peer p:
    what the partial program counts."""
    m = np.zeros((N_DEV, N_DEV), np.int64)
    for c, shard in enumerate(_shards(np.asarray(keys))):
        m[c] = np.bincount(_targets(np.unique(shard)), minlength=N_DEV)
    return m


def _rung(n):
    from spark_rapids_tpu.columnar.column import (DEFAULT_ROW_BUCKETS,
                                                  round_up_bucket)

    return round_up_bucket(max(n, 1), DEFAULT_ROW_BUCKETS)


def _check_all_paths(tables):
    """The reference, after the resident and the resharded mesh paths and
    the single-device path all answered it twice; and the counters of the
    resident mesh path's second collect."""
    want = Q18.reference(tables)
    for conf in (MESH, BASE, {**MESH, **RESIDENT}):
        answers, counters, _ = _run(conf, tables)
        assert answers == [want] * 2, conf
    return want, counters


def test_generated_orders_split_across_a_shard_boundary(every_order):
    tables = _generated(6_000, 2**31 + 35)
    keys = tables["lineitem"]["l_orderkey"]
    shards = _shards(keys)
    split = [d for d in range(1, N_DEV) if shards[d][0] == shards[d - 1][-1]]
    assert split, "no order spans a shard boundary: pick another seed"
    want, _ = _check_all_paths(tables)
    assert len(want) == len(np.unique(keys))


def test_a_shard_whose_partial_holds_most_groups(every_order):
    """Shard 0 holds 6,000 orders of one line, the others a few long
    orders: the quota follows shard 0's groups, the other shards send
    next to nothing."""
    rng = np.random.default_rng(35)
    per = 6_000
    keys = np.concatenate([np.arange(per) * 32 + 1,
                           np.repeat([7, 39, 71], per)])
    qty = rng.integers(1, 51, len(keys)) * 100
    tables = _tables(keys, qty)
    m = send_matrix(keys)
    assert m[0].sum() == per and m[1:].sum() <= 9
    _, counters = _check_all_paths(tables)
    assert counters["ici_quota_rows"] == _rung(m.max()) == 8192


def test_an_empty_shard(every_order):
    """9 rows: the balanced layout gives the last device none, resident
    or resharded every collect."""
    tables = _tables([1, 1, 2, 3, 3, 3, 4, 5, 5], [100] * 9)
    assert [len(s) for s in _shards(tables["lineitem"]["l_orderkey"])] \
        == [3, 3, 3, 0]
    assert _check_all_paths(tables)[0] == {
        (1,): 200, (2,): 100, (3,): 300, (4,): 100, (5,): 200}


@pytest.mark.parametrize("groups", [1024, 1025])
def test_groups_at_a_ladder_rung(every_order, groups):
    """Every key of device 0's shard hashes to peer 1: the matrix's largest
    entry is ``groups``, so the quota is exactly the rung at 1,024, and at
    1,025 the next rung held to a device's share of the batch, 2,048."""
    cand = np.arange(1, 40 * groups, dtype=np.int64)
    keys = cand[_targets(cand) == 1][:groups]
    assert len(keys) == groups
    pad = np.repeat(keys[-1], 3 * groups)       # the other shards: one key
    all_keys = np.concatenate([keys, pad])
    tables = _tables(all_keys, np.full(len(all_keys), 200))
    m = send_matrix(all_keys)
    assert m.max() == m[0, 1] == groups
    _, counters = _check_all_paths(tables)
    assert counters["ici_quota_rows"] == min(
        _rung(groups), _rung(len(all_keys)) // N_DEV) \
        == (1024 if groups == 1024 else 2048)


def test_the_chips_outputs_together_are_the_reference(every_order):
    """The stage's own output: one batch a device, each on its device, no
    key on two devices, and all of them together the reference."""
    tables = _generated(8_000, 2**31 + 77)
    df = Q18.build({"lineitem": _frame(_session({**MESH, **RESIDENT}),
                                       tables)})
    ici = _ici(df)
    batches = list(ici.execute_columnar())
    assert len(batches) == N_DEV
    devices = [b.columns[0].data.devices() for b in batches]
    assert all(len(d) == 1 for d in devices)
    assert len({next(iter(d)) for d in devices}) == N_DEV
    got, seen = {}, set()
    for b in batches:
        for k, v in b.to_rows():
            assert k not in seen, f"order {k} on two devices"
            seen.add(k)
            got[(int(k),)] = int(v.scaleb(2))
    assert got == Q18.reference(tables)


@pytest.mark.parametrize("form", ["scatter", "ends"])
def test_the_counters_count_what_crosses_a_chip(every_order, form):
    """Rows and bytes that leave their chip, from the partition ids of
    each shard's groups; the quota from the same matrix; a resident table
    resharded in its first collect alone, one that is not resident every
    collect.  With the groups-cap ladder's first rung below the shard's
    2,048 rows, programs (a) and (b) take the end-row form: one
    ``agg_segment_compactions`` each a collect."""
    tables = _generated(8_000, 2**31 + 91)
    m = send_matrix(tables["lineitem"]["l_orderkey"])
    moved = int(m.sum() - np.trace(m))
    ladder = {"spark.rapids.tpu.agg.smallGroupsCap":
              64 if form == "ends" else 65536}
    for conf, resident in (({**MESH, **RESIDENT}, True), (MESH, False)):
        answers, d, df = _run({**conf, **ladder}, tables, collects=2)
        assert answers == [Q18.reference(tables)] * 2
        assert d["agg_segment_compactions"] == (2 if form == "ends" else 0)
        ici = _ici(df)
        assert [x._seg_form for x in (ici.partial, ici.final)] == [form] * 2
        assert ici.describe().count(f" seg={form})") == 2, ici.describe()
        assert d["ici_epochs"] == 1
        assert d["ici_rows_exchanged"] == moved
        assert d["ici_bytes_moved"] == moved * ROW_BYTES
        assert d["ici_quota_rows"] == _rung(m.max())
        assert (d["mesh_reshard_bytes"] > 0) != resident, d
        # two programs: (a) and (b), which also finalizes; one sync after
        # each
        spans = {k.split("srt.ici.", 1)[1].split("/")[0] for k in d
                 if k.startswith("span_n|") and "srt.ici." in k and d[k]}
        assert spans == {"partial", "exchange", "emit"}, spans


def test_the_stage_keeps_a_resident_tables_shards(every_order):
    """The scan's resident batch stays a dense prefix on one device; the
    stage lays it out once and keeps the shards under that batch's own
    arrays."""
    from spark_rapids_tpu import perfcounters as PC

    tables = _generated(8_000, 2**31 + 13)
    df = Q18.build({"lineitem": _frame(_session({**MESH, **RESIDENT}),
                                       tables)})
    want = Q18.reference(tables)
    snap = PC.snapshot()
    assert Q18.answer(df.collect()) == want
    first = PC.since(snap)["mesh_reshard_bytes"]
    snap = PC.snapshot()
    assert Q18.answer(df.collect()) == want
    assert first > 0 and PC.since(snap)["mesh_reshard_bytes"] == 0
    ici = _ici(df)
    (batch,) = list(ici.children[0].execute_columnar())
    assert batch.num_rows == 8_000
    assert all(len(c.data.devices()) == 1 for c in batch.columns)
    ((leaves, cols, per),) = ici._shards.values()
    assert all(a is b for a, b in zip(
        leaves, jax.tree_util.tree_leaves(list(batch.columns)), strict=True))
    assert per == 2_000
    assert all(len(c.data.devices()) == N_DEV for c in cols)


def test_the_exchange_is_sized_by_the_counted_quota(every_order):
    """Program (b)'s received capacity is n_dev x Q and its merge runs
    there, not at n_dev x the shard's capacity."""
    tables = _generated(20_000, 2**31 + 5)
    df = Q18.build({"lineitem": _frame(_session({**MESH, **RESIDENT}),
                                       tables)})
    df.collect()
    ici = _ici(df)
    keys = [k for k in ici._programs if isinstance(k, tuple)]
    assert len(keys) == 1
    local_cap, groups_cap, quota, acc_cap, last = keys[0]
    m = send_matrix(tables["lineitem"]["l_orderkey"])
    assert (local_cap, acc_cap, last) == (_rung(20_000) // N_DEV, 0, True)
    assert groups_cap == _rung(m.sum(1).max()) == 8192
    assert quota == _rung(m.max()) == 1024
    assert quota * N_DEV < N_DEV * local_cap


# -- the five stages' ici_rows_exchanged / ici_bytes_moved ----------------

def _stage_df(session, stage, n):
    import bench
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction
    from spark_rapids_tpu.session import col

    rng = np.random.default_rng(35)
    left = bench._df(session, {"k": rng.integers(0, 400, n),
                               "v": rng.integers(0, 1000, n)},
                     [T.LONG, T.LONG])
    if stage == "repartition":
        return left.repartition(N_DEV, "k")
    if stage == "window":
        return left.window([WindowFunction("sum", col("v"), "s")],
                           partition_by=["k"],
                           order_by=[(col("v"), SortSpec())])
    if stage == "sort":
        return left.order_by(col("v"))
    right = bench._df(session, {"k": rng.integers(0, 400, n // 2),
                                "w": rng.integers(0, 1000, n // 2)},
                      [T.LONG, T.LONG])
    return left.join(right, on=["k"])


def _off_chip(keys):
    """Rows whose murmur3 peer is not the device a padded batch's
    contiguous block puts them on."""
    local = _rung(len(keys)) // N_DEV
    return int((_targets(keys) != np.arange(len(keys)) // local).sum())


@pytest.mark.parametrize("stage", ["repartition", "window", "join", "sort"])
def test_every_stage_counts_the_rows_that_leave_their_chip(stage):
    from spark_rapids_tpu import perfcounters as PC

    n = 1000
    conf = {**MESH, "spark.sql.autoBroadcastJoinThreshold": "-1"}
    df = _stage_df(_session(conf), stage, n)
    root = df._planned()[0].pretty()
    assert "TpuIci" in root, root
    snap = PC.snapshot()
    df.collect()
    d = PC.since(snap)
    row = 2 * (8 + 1)                     # two LONG columns and validities
    rng = np.random.default_rng(35)
    lk = rng.integers(0, 400, n)
    rng.integers(0, 1000, n)
    if stage == "sort":
        # range peers come from sampled splitters: what crossed is what
        # was counted, and no more than every row
        assert 0 < d["ici_rows_exchanged"] <= n
    elif stage == "join":
        rk = rng.integers(0, 400, n // 2)
        assert d["ici_rows_exchanged"] == _off_chip(lk) + _off_chip(rk)
    else:
        assert d["ici_rows_exchanged"] == _off_chip(lk)
    assert d["ici_bytes_moved"] == d["ici_rows_exchanged"] * row
