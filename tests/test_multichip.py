"""Multi-chip mesh tests over the virtual 8-device CPU mesh
(reference analog: tests/.../shuffle/* which test the UCX transport with
mocked peers — here the 'mock' is XLA's host-platform device count)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")

# Mesh-COLLECTIVE tests compile multi-device SPMD programs — minutes of
# XLA CPU compile apiece, ~27min for the suite — which the tier-1
# 'not slow' budget cannot absorb.  Plan/install-level tests stay in
# tier-1; the collectives
# run green via `pytest tests/test_multichip.py` (ISSUE 10 run) and the
# driver's MULTICHIP_* artifact (__graft_entry__.dryrun_multichip).
mesh_collective = pytest.mark.slow


@needs_mesh
@mesh_collective
def test_distributed_global_agg_matches_local():
    from spark_rapids_tpu.parallel.mesh import distributed_agg_step, make_mesh

    mesh = make_mesh(8)
    n = 64 * 8
    rng = np.random.default_rng(0)
    price = jnp.asarray(rng.integers(100, 10000, n), jnp.int64)
    discount = jnp.asarray(rng.integers(0, 11, n), jnp.int64)
    quantity = jnp.asarray(rng.integers(100, 5000, n), jnp.int64)
    shipdate = jnp.asarray(rng.integers(8700, 9200, n), jnp.int32)
    valid = jnp.ones(n, jnp.bool_)
    total, count = jax.jit(distributed_agg_step(mesh))(
        price, discount, quantity, shipdate, valid)
    keep = ((np.asarray(shipdate) >= 8766) & (np.asarray(shipdate) < 9131)
            & (np.asarray(discount) >= 5) & (np.asarray(discount) <= 7)
            & (np.asarray(quantity) < 2400))
    want = int((np.asarray(price)[keep] * np.asarray(discount)[keep]).sum())
    assert int(total) == want
    assert int(count) == int(keep.sum())


@needs_mesh
@mesh_collective
def test_ici_shuffle_agg_matches_local():
    from spark_rapids_tpu.parallel.mesh import (
        distributed_shuffle_agg_step,
        make_mesh,
    )

    mesh = make_mesh(8)
    n = 32 * 8
    rng = np.random.default_rng(42)
    keys = jnp.asarray(rng.integers(0, 23, n), jnp.int64)
    vals = jnp.asarray(rng.integers(-100, 100, n), jnp.int64)
    valid = jnp.asarray(rng.random(n) > 0.2)
    fkeys, fsums, fvalid = jax.jit(distributed_shuffle_agg_step(mesh))(
        keys, vals, valid)
    got = {}
    for k, v, ok in zip(np.asarray(fkeys), np.asarray(fsums),
                        np.asarray(fvalid)):
        if ok:
            assert int(k) not in got, "key appears on two devices"
            got[int(k)] = int(v)
    want = {}
    for k, v, ok in zip(np.asarray(keys), np.asarray(vals), np.asarray(valid)):
        if ok:
            want[int(k)] = want.get(int(k), 0) + int(v)
    assert got == want


@needs_mesh
@mesh_collective
def test_broadcast_build_side():
    from spark_rapids_tpu.parallel.mesh import broadcast_build_side, make_mesh

    mesh = make_mesh(8)
    n = 16 * 8
    keys = jnp.arange(n, dtype=jnp.int64)
    vals = keys * 2
    bk, bv = jax.jit(broadcast_build_side(mesh))(keys, vals)
    assert bk.shape == (n,)
    assert bool((np.asarray(bk) == np.arange(n)).all())


@needs_mesh
@mesh_collective
def test_dryrun_entrypoints():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert len(out) == 2
    g.dryrun_multichip(8)


@mesh_collective
def test_dryrun_standalone_like_driver():
    """Run `python __graft_entry__.py` in a fresh interpreter with NONE of
    conftest's platform forcing — exactly how the driver invokes it.  Round 1
    failed precisely because this parity check did not exist (the driver env
    grabbed the real TPU instead of building the virtual mesh)."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "__graft_entry__.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip OK" in proc.stdout


_ICI_CONF = {
    "spark.rapids.sql.enabled": True,
    "spark.rapids.shuffle.mode": "ICI",
    "spark.rapids.tpu.mesh.enabled": True,
}


@needs_mesh
@mesh_collective
def test_ici_plan_grouped_agg_matches_oracle():
    """A real DataFrame query executes through TpuOverrides + the exec layer
    as ONE shard_map collective program on the mesh, and matches the oracle."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import DecimalGen, IntegerGen, StringGen, gen_df
    from spark_rapids_tpu.session import col, count_, lit, max_, min_, sum_

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=20),
                        IntegerGen(min_val=-1000, max_val=1000),
                        DecimalGen(12, 2), StringGen(min_len=1, max_len=8)],
                    ["k", "v", "d", "t"], length=700)
        return (df.filter(col("v") > lit(-900))
                  .group_by("k")
                  .agg(sum_("v", "s"), count_(col("v"), "c"),
                       min_("t", "lo"), max_("t", "hi"), sum_("d", "ds")))

    assert_tpu_and_cpu_are_equal_collect(build, conf=_ICI_CONF)


@needs_mesh
@mesh_collective
def test_ici_plan_global_agg_matches_oracle():
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import DecimalGen, LongGen, gen_df
    from spark_rapids_tpu.session import col, count_, lit, sum_

    def build(s):
        df = gen_df(s, [LongGen(min_val=-10**6, max_val=10**6),
                        DecimalGen(12, 2)], ["v", "d"], length=500)
        return (df.filter(col("v") > lit(0))
                  .agg(sum_("v", "s"), count_(None, "c"), sum_("d", "ds")))

    assert_tpu_and_cpu_are_equal_collect(build, conf=_ICI_CONF)


@needs_mesh
def test_ici_plan_is_installed():
    """The rewrite actually produces the SPMD exec (not the host shuffle)."""
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.exec.ici import TpuIciShuffleAggExec
    from spark_rapids_tpu.session import TpuSession, sum_

    s = TpuSession(dict(_ICI_CONF))
    df = gen_df(s, [IntegerGen(min_val=0, max_val=5), IntegerGen()],
                ["k", "v"], length=100).group_by("k").agg(sum_("v", "s"))
    root, _ = df._planned()

    def find(e):
        if isinstance(e, TpuIciShuffleAggExec):
            return True
        return any(find(c) for c in e.children)
    assert find(root), root.pretty()


@needs_mesh
@mesh_collective
def test_ici_plan_empty_input():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.session import TpuSession, sum_

    s = TpuSession(dict(_ICI_CONF))
    schema = T.StructType([T.StructField("k", T.INT),
                           T.StructField("v", T.LONG)])
    df = s.create_dataframe({"k": [], "v": []}, schema)
    assert df.group_by("k").agg(sum_("v", "s")).collect() == []
    assert df.agg(sum_("v", "s")).collect() == [(None,)]


@needs_mesh
@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
@mesh_collective
def test_ici_plan_shuffled_join_matches_oracle(how):
    """A shuffled equi-join DataFrame query executes as the two-step SPMD
    collective program (all-to-all both sides over ICI, local sorted-probe
    join per device) and matches the oracle."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, LongGen, StringGen, gen_df

    conf = dict(_ICI_CONF)
    conf["spark.sql.autoBroadcastJoinThreshold"] = "-1"

    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=30),
                          LongGen(), StringGen(max_len=6)],
                      ["k", "v", "t"], length=600)
        right = gen_df(s, [IntegerGen(min_val=5, max_val=40,
                                      nullable=False),
                           LongGen()], ["k", "w"], length=300, seed=9)
        return left.join(right, on=["k"], how=how)

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
def test_ici_join_plan_is_installed():
    import sys
    sys.path.insert(0, "tests")
    from data_gen import IntegerGen, LongGen, gen_df
    from spark_rapids_tpu.session import TpuSession

    conf = dict(_ICI_CONF)
    conf["spark.sql.autoBroadcastJoinThreshold"] = "-1"
    s = TpuSession(conf)
    left = gen_df(s, [IntegerGen(nullable=False), LongGen()], ["k", "v"],
                  length=100)
    right = gen_df(s, [IntegerGen(nullable=False), LongGen()],
                   ["k", "w"], length=100, seed=3)
    q = left.join(right, on=["k"])
    root, meta = q._planned()
    assert "TpuIciShuffleJoin" in root.pretty(), root.pretty()


# -- round 3: epoch streaming, distributed sort, device-count sweep ---------


@needs_mesh
@mesh_collective
def test_ici_epoch_streamed_agg():
    """Input far above one epoch's bytes streams through the accumulator
    (multi-epoch path: partial -> a2a -> merge-into-acc per epoch)."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.session import col, count_, sum_

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.epochTargetBytes"] = 4096
    conf["spark.rapids.sql.batchSizeBytes"] = 4096

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=40),
                        IntegerGen(min_val=-100, max_val=100)],
                    ["k", "v"], length=3000)
        return df.group_by("k").agg(sum_("v", "s"), count_(col("v"), "c"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@mesh_collective
def test_ici_epoch_streamed_global_agg():
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import LongGen, gen_df
    from spark_rapids_tpu.session import count_, sum_

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.epochTargetBytes"] = 4096
    conf["spark.rapids.sql.batchSizeBytes"] = 4096

    def build(s):
        df = gen_df(s, [LongGen(min_val=-10**6, max_val=10**6)], ["v"],
                    length=2500)
        return df.agg(sum_("v", "s"), count_(None, "c"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@mesh_collective
def test_ici_distributed_sort():
    """Global order_by runs as the range-exchange mesh sort and emits the
    exact oracle order."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, StringGen, gen_df
    from spark_rapids_tpu.session import col

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=-1000, max_val=1000),
                        StringGen(min_len=0, max_len=6)],
                    ["v", "t"], length=900)
        return df.order_by(col("v"), col("t"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=_ICI_CONF,
                                         ignore_order=False)


@needs_mesh
@mesh_collective
def test_ici_distributed_sort_desc_nulls():
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.session import col

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=-50, max_val=50),
                        IntegerGen()], ["v", "x"], length=600)
        return df.order_by(col("v"), ascending=False)

    assert_tpu_and_cpu_are_equal_collect(build, conf=_ICI_CONF,
                                         ignore_order=False)


@needs_mesh
@mesh_collective
def test_ici_distributed_sort_multi_epoch():
    """Sort input spanning several epochs still emits globally ordered."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.session import col

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.epochTargetBytes"] = 4096
    conf["spark.rapids.sql.batchSizeBytes"] = 4096

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=-10**6, max_val=10**6)],
                    ["v"], length=2500)
        return df.order_by(col("v"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf,
                                         ignore_order=False)


@needs_mesh
def test_ici_sort_installed():
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.exec.ici import TpuIciSortExec
    from spark_rapids_tpu.session import TpuSession, col

    s = TpuSession(dict(_ICI_CONF))
    df = gen_df(s, [IntegerGen()], ["v"], length=64)
    root, _ = df.order_by(col("v"))._planned()

    def find(n):
        if isinstance(n, TpuIciSortExec):
            return True
        return any(find(c) for c in n.children
                   if hasattr(c, "children"))

    assert find(root), f"no TpuIciSortExec in plan: {root.describe()}"


@needs_mesh
@pytest.mark.parametrize("n_dev", [2, 3, 5, 8])
@mesh_collective
def test_ici_device_count_sweep(n_dev):
    """Non-power-of-2 meshes: quota/padding math must hold for every
    device count."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.session import col, count_, sum_

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.devices"] = n_dev

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=15),
                        IntegerGen(min_val=-100, max_val=100)],
                    ["k", "v"], length=500)
        return df.group_by("k").agg(sum_("v", "s"), count_(col("v"), "c"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@pytest.mark.parametrize("n_dev", [3, 5])
@mesh_collective
def test_ici_sort_device_count_sweep(n_dev):
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.session import col

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.devices"] = n_dev

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=-500, max_val=500)], ["v"],
                    length=400)
        return df.order_by(col("v"))

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf,
                                         ignore_order=False)


@needs_mesh
@pytest.mark.parametrize("how", ["right", "full"])
@mesh_collective
def test_ici_right_full_joins_on_mesh(how):
    """RIGHT (mirror-swapped) and FULL (matched-build tail) mesh joins run
    through the ICI exec and match the oracle."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, LongGen, StringGen, gen_df
    from spark_rapids_tpu.exec.ici import TpuIciShuffleJoinExec
    from spark_rapids_tpu.session import TpuSession

    conf = dict(_ICI_CONF)
    conf["spark.sql.autoBroadcastJoinThreshold"] = "-1"

    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=30),
                          LongGen(), StringGen(max_len=6)],
                      ["k", "v", "t"], length=600)
        right = gen_df(s, [IntegerGen(min_val=5, max_val=40),
                           LongGen()], ["k", "w"], length=300, seed=9)
        return left.join(right, on=["k"], how=how)

    s = TpuSession(dict(conf))
    root, _ = build(s)._planned()

    def find(n):
        if isinstance(n, TpuIciShuffleJoinExec):
            return True
        return any(find(c) for c in n.children if hasattr(c, "children"))

    assert find(root), f"{how} join must use the ICI exec: {root.pretty()}"
    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@mesh_collective
def test_ici_full_join_multi_epoch_tail():
    """FULL OUTER across several probe epochs: the matched-build mask ORs
    across epochs so the tail emits exactly the never-matched build rows."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.epochTargetBytes"] = 4096
    conf["spark.rapids.sql.reader.batchSizeRows"] = 256
    conf["spark.sql.autoBroadcastJoinThreshold"] = "-1"

    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=30),
                          IntegerGen()], ["k", "v"], length=2000)
        right = gen_df(s, [IntegerGen(min_val=10, max_val=60),
                           IntegerGen()], ["k", "w"], length=400, seed=3)
        return left.join(right, on="k", how="full")

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@mesh_collective
def test_ici_conditional_inner_join_on_mesh():
    """INNER equi-join with a RESIDUAL condition: the condition filters
    the gathered pairs inside the mesh materialization program (a
    SortMergeJoin plan node carrying condition, as Spark's planner emits
    for mixed equi+residual join predicates)."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, LongGen, gen_df
    from spark_rapids_tpu.session import DataFrame, col

    conf = dict(_ICI_CONF)
    conf["spark.sql.autoBroadcastJoinThreshold"] = "-1"

    def build(s):
        import spark_rapids_tpu.plan.nodes as PN
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.session import _col

        left = gen_df(s, [IntegerGen(min_val=0, max_val=20),
                          LongGen(min_val=-100, max_val=100)],
                      ["k", "v"], length=500)
        right = gen_df(s, [IntegerGen(min_val=0, max_val=25),
                           LongGen(min_val=-100, max_val=100)],
                       ["k2", "w"], length=300, seed=11)
        np_ = s.shuffle_partitions
        lkeys = [_col("k").resolve(left.schema)]
        rkeys = [_col("k2").resolve(right.schema)]
        combined = T.StructType(list(left.schema.fields)
                                + list(right.schema.fields))
        cond = (col("v") < col("w")).resolve(combined)
        lex = PN.Exchange(PN.HashPartitioning(lkeys, np_), left.plan)
        rex = PN.Exchange(PN.HashPartitioning(rkeys, np_), right.plan)
        node = PN.SortMergeJoin(lex, rex, lkeys, rkeys,
                                PN.JoinType.INNER, cond)
        return DataFrame(node, s)

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@mesh_collective
def test_ici_join_probe_epochs():
    """Probe side spanning several epochs: per-device memory = build side
    + one epoch; every epoch's matches stream out."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.epochTargetBytes"] = 4096
    conf["spark.rapids.sql.reader.batchSizeRows"] = 256
    conf["spark.sql.autoBroadcastJoinThreshold"] = "-1"

    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=30, nullable=False),
                          IntegerGen()], ["k", "v"], length=2000)
        right = gen_df(s, [IntegerGen(min_val=10, max_val=40,
                                      nullable=False),
                           IntegerGen()], ["k", "w"], length=300)
        return left.join(right, on="k", how="left")

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
def test_mesh_stage_kill_switches():
    """Per-stage ICI kill switches keep the host path (fallback-visible)."""
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.exec.ici import (TpuIciShuffleAggExec,
                                           TpuIciSortExec)
    from spark_rapids_tpu.session import TpuSession, col, sum_

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.agg.enabled"] = False
    conf["spark.rapids.tpu.mesh.sort.enabled"] = False
    s = TpuSession(conf)
    df = gen_df(s, [IntegerGen(min_val=0, max_val=5), IntegerGen()],
                ["k", "v"], length=64)

    def find(n, cls):
        if isinstance(n, cls):
            return True
        return any(find(c, cls) for c in n.children
                   if hasattr(c, "children"))

    root, _ = df.group_by("k").agg(sum_("v", "s"))._planned()
    assert not find(root, TpuIciShuffleAggExec)
    root2, _ = df.order_by(col("v"))._planned()
    assert not find(root2, TpuIciSortExec)


# -- round 4: distributed window + generic mesh repartition -----------------


@needs_mesh
def test_ici_window_installed():
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.exec.ici import TpuIciWindowExec
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction
    from spark_rapids_tpu.session import TpuSession, col

    s = TpuSession(dict(_ICI_CONF))
    df = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                ["k", "v"], length=64)
    q = df.window([WindowFunction("row_number", None, "rn")],
                  partition_by=["k"],
                  order_by=[(col("v"), SortSpec())])
    root, _ = q._planned()

    def find(n):
        if isinstance(n, TpuIciWindowExec):
            return True
        return any(find(c) for c in n.children if hasattr(c, "children"))

    assert find(root), f"no TpuIciWindowExec in plan: {root.describe()}"


@needs_mesh
@pytest.mark.parametrize("n_dev", [2, 3, 5, 8])
@mesh_collective
def test_ici_window_matches_oracle(n_dev):
    """Partitioned window distributes over the mesh (hash all-to-all on
    PARTITION BY + per-device single-chip window) and matches the oracle
    for every device count."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, LongGen, StringGen, gen_df
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction
    from spark_rapids_tpu.session import col

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.devices"] = n_dev

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=12),
                        LongGen(min_val=-1000, max_val=1000),
                        StringGen(min_len=1, max_len=6)],
                    ["k", "v", "t"], length=600)
        return df.window(
            [WindowFunction("row_number", None, "rn"),
             WindowFunction("rank", None, "rk"),
             WindowFunction("sum", col("v"), "s"),
             WindowFunction("max", col("t"), "mt")],
            partition_by=["k"],
            order_by=[(col("v"), SortSpec())])

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@mesh_collective
def test_ici_window_multi_epoch():
    """Window input spanning several epochs folds into the device-resident
    accumulator before the one window program."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction
    from spark_rapids_tpu.session import col

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.epochTargetBytes"] = 4096
    conf["spark.rapids.sql.reader.batchSizeRows"] = 256

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=20),
                        IntegerGen(min_val=-500, max_val=500)],
                    ["k", "v"], length=2000)
        return df.window(
            [WindowFunction("sum", col("v"), "s"),
             WindowFunction("dense_rank", None, "dr")],
            partition_by=["k"],
            order_by=[(col("v"), SortSpec(ascending=False))])

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)


@needs_mesh
@mesh_collective
def test_ici_window_null_partition_keys():
    """Null PARTITION BY keys form one partition and hash to one device."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction
    from spark_rapids_tpu.session import col

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=3, nullable=True),
                        IntegerGen()], ["k", "v"], length=400, seed=5)
        return df.window(
            [WindowFunction("count", col("v"), "c"),
             WindowFunction("row_number", None, "rn")],
            partition_by=["k"],
            order_by=[(col("v"), SortSpec())])

    assert_tpu_and_cpu_are_equal_collect(build, conf=_ICI_CONF)


@needs_mesh
def test_ici_window_kill_switch():
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.exec.ici import TpuIciWindowExec
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction
    from spark_rapids_tpu.session import TpuSession, col

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.window.enabled"] = False
    s = TpuSession(conf)
    df = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                ["k", "v"], length=64)
    q = df.window([WindowFunction("row_number", None, "rn")],
                  partition_by=["k"], order_by=[(col("v"), SortSpec())])
    root, _ = q._planned()

    def find(n):
        if isinstance(n, TpuIciWindowExec):
            return True
        return any(find(c) for c in n.children if hasattr(c, "children"))

    assert not find(root)


@needs_mesh
@mesh_collective
def test_ici_repartition_installed_and_matches():
    """df.repartition(k) lowers to the generic mesh all-to-all and the
    downstream aggregate still matches the oracle."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.exec.ici import TpuIciRepartitionExec
    from spark_rapids_tpu.session import TpuSession, col, sum_

    s = TpuSession(dict(_ICI_CONF))
    df = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                ["k", "v"], length=200)
    q = df.repartition(4, "k")
    root, _ = q._planned()

    def find(n):
        if isinstance(n, TpuIciRepartitionExec):
            return True
        return any(find(c) for c in n.children if hasattr(c, "children"))

    assert find(root), f"no TpuIciRepartitionExec: {root.describe()}"

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=9),
                        IntegerGen(min_val=-100, max_val=100)],
                    ["k", "v"], length=300)
        return (df.repartition(4, "k").group_by("k")
                .agg(sum_("v", "s")))

    assert_tpu_and_cpu_are_equal_collect(build, conf=_ICI_CONF)


@needs_mesh
def test_ici_repartition_nested_schema_keeps_host_path():
    """Array/struct columns keep the host shuffle (schema guard) and the
    query still returns correct rows."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exec.ici import TpuIciRepartitionExec
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession(dict(_ICI_CONF))
    schema = T.StructType([
        T.StructField("k", T.INT, False),
        T.StructField("a", T.ArrayType(T.INT), True)])
    df = s.create_dataframe({"k": [1, 2, 1], "a": [[1, 2], None, [3]]},
                            schema)
    q = df.repartition(2, "k")
    root, _ = q._planned()

    def find(n):
        if isinstance(n, TpuIciRepartitionExec):
            return True
        return any(find(c) for c in n.children if hasattr(c, "children"))

    assert not find(root), "nested schema must keep the host exchange"
    assert sorted(q.collect()) == [(1, [1, 2]), (1, [3]), (2, None)]


# -- ISSUE 10: real ICI shuffle — null round-trip, counters/event, -----------
# -- zero-host-bytes pin, cross-slice wiring ---------------------------------


@needs_mesh
@mesh_collective
def test_ici_all_to_all_columns_null_validity_round_trip():
    """Satellite: whole-batch ICI all-to-all on the CPU-simulated mesh —
    values, string payloads, AND per-column null validity survive the
    routing; invalid rows drop; every valid row lands on exactly the
    device its hash names."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from jax import shard_map
    from spark_rapids_tpu.parallel.mesh import (
        _local_hash_partition_ids,
        ici_all_to_all_columns,
        make_mesh,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = 8
    mesh = make_mesh(n_dev)
    n = 64 * n_dev
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, 1 << 40, n), jnp.int64)
    vals = jnp.asarray(rng.integers(-1000, 1000, n), jnp.int64)
    v_ok = jnp.asarray(rng.random(n) < 0.7)        # nullable payload
    rows_ok = jnp.asarray(rng.random(n) < 0.9)     # live rows
    chars = jnp.asarray(rng.integers(97, 123, (n, 8)), jnp.uint8)
    lens = jnp.asarray(rng.integers(1, 9, n), jnp.int32)

    def step(kd, vd, vo, ch, ln, ro):
        cols = [DeviceColumn(T.LONG, ro, data=kd),
                DeviceColumn(T.LONG, vo & ro, data=vd),
                DeviceColumn(T.STRING, ro, chars=ch, lengths=ln)]
        tgt = _local_hash_partition_ids(kd, ro, n_dev)
        rcols, rok = ici_all_to_all_columns(cols, ro, tgt, n_dev, "dp")
        return (rcols[0].data, rcols[1].data, rcols[1].validity,
                rcols[2].chars, rcols[2].lengths, rok)

    fn = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("dp"),) * 6,
        out_specs=(P("dp"),) * 6, check_vma=False))
    spec = NamedSharding(mesh, P("dp"))
    args = [jax.device_put(x, spec)
            for x in (keys, vals, v_ok, chars, lens, rows_ok)]
    rk, rv, rvok, rch, rln, rok = [np.asarray(x) for x in fn(*args)]

    pid = np.asarray(jnp.where(
        rows_ok, _local_hash_partition_ids(keys, rows_ok, n_dev), -1))
    per_dev_cap = rk.shape[0] // n_dev
    seen = 0
    for d in range(n_dev):
        sl = slice(d * per_dev_cap, (d + 1) * per_dev_cap)
        m = rok[sl]
        got = sorted(
            (int(k), int(v) if ok else None,
             bytes(c[:int(w)]).decode())
            for k, v, ok, c, w in zip(rk[sl][m], rv[sl][m], rvok[sl][m],
                                      rch[sl][m], rln[sl][m]))
        want_mask = pid == d
        want = sorted(
            (int(k), int(v) if ok else None,
             bytes(np.asarray(c)[:int(w)]).decode())
            for k, v, ok, c, w in zip(
                np.asarray(keys)[want_mask], np.asarray(vals)[want_mask],
                np.asarray(v_ok)[want_mask],
                np.asarray(chars)[want_mask],
                np.asarray(lens)[want_mask]))
        assert got == want, f"device {d}: {len(got)} vs {len(want)} rows"
        seen += len(got)
    assert seen == int(np.asarray(rows_ok).sum())


@needs_mesh
@mesh_collective
def test_ici_all_to_all_zero_host_bytes():
    """Acceptance pin: the all-device ICI shuffle path moves ZERO bytes
    through the host — no D2H materializations, no H2D upload sites —
    once inputs are device-resident (bytes_d2h / bytes_h2d deltas)."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from jax import shard_map
    from spark_rapids_tpu.parallel.mesh import (
        _local_hash_partition_ids,
        ici_all_to_all_columns,
        make_mesh,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = 8
    mesh = make_mesh(n_dev)
    n = 32 * n_dev
    rng = np.random.default_rng(9)
    keys = jnp.asarray(rng.integers(0, 1 << 30, n), jnp.int64)
    vals = jnp.asarray(rng.integers(-50, 50, n), jnp.int64)
    ok = jnp.ones(n, jnp.bool_)

    def step(kd, vd, ro):
        cols = [DeviceColumn(T.LONG, ro, data=kd),
                DeviceColumn(T.LONG, ro, data=vd)]
        tgt = _local_hash_partition_ids(kd, ro, n_dev)
        rcols, rok = ici_all_to_all_columns(cols, ro, tgt, n_dev, "dp")
        return rcols[0].data, rcols[1].data, rok

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P("dp"),) * 3,
                           out_specs=(P("dp"),) * 3, check_vma=False))
    spec = NamedSharding(mesh, P("dp"))
    args = [jax.device_put(x, spec) for x in (keys, vals, ok)]
    jax.block_until_ready(fn(*args))   # compile outside the window
    snap = PC.snapshot()
    out = fn(*args)
    jax.block_until_ready(out)
    d = PC.since(snap)
    assert d["bytes_d2h"] == 0, d
    assert d["bytes_h2d"] == 0, d
    assert d["host_syncs"] == 0, d


@needs_mesh
@mesh_collective
def test_ici_counters_and_diagnostics_event(tmp_path):
    """A mesh-stage query accounts its collective epochs into the
    ici_* counters and emits the ici_shuffle diagnostics event."""
    import json
    import sys
    sys.path.insert(0, "tests")
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.session import TpuSession, sum_

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.diagnostics.enabled"] = True
    conf["spark.rapids.tpu.diagnostics.eventLogDir"] = str(tmp_path)
    s = TpuSession(conf)
    df = gen_df(s, [IntegerGen(min_val=0, max_val=20), IntegerGen()],
                ["k", "v"], length=400)
    snap = PC.snapshot()
    rows = df.group_by("k").agg(sum_("v", "sv")).collect()
    assert rows
    d = PC.since(snap)
    assert d["ici_epochs"] >= 1, d
    assert d["ici_rows_exchanged"] > 0, d
    assert d["ici_shuffle_ns"] > 0, d
    logs = sorted(tmp_path.glob("query-*.jsonl"))
    assert logs
    events = [json.loads(line) for line in
              logs[-1].read_text().splitlines()]
    ici = [e for e in events if e["ev"] == "ici_shuffle"]
    assert ici, [e["ev"] for e in events]
    assert ici[0]["n_dev"] == 8
    assert ici[0]["rows"] > 0


@needs_mesh
@mesh_collective
def test_ici_repartition_cross_slice_hosts():
    """spark.rapids.tpu.ici.crossSliceHosts routes the generic mesh
    repartition through the two-level (host x ici) mesh and still
    matches the oracle."""
    import sys
    sys.path.insert(0, "tests")
    from asserts import assert_tpu_and_cpu_are_equal_collect
    from data_gen import IntegerGen, gen_df
    from spark_rapids_tpu.exec.ici import TpuIciRepartitionExec
    from spark_rapids_tpu.session import TpuSession, sum_

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.ici.crossSliceHosts"] = 2

    s = TpuSession(dict(conf))
    df = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                ["k", "v"], length=200)
    root, _ = df.repartition(4, "k")._planned()

    found = []

    def find(n):
        if isinstance(n, TpuIciRepartitionExec):
            found.append(n)
        for c in n.children:
            if hasattr(c, "children"):
                find(c)

    find(root)
    assert found, root.pretty()
    assert found[0].cross_hosts == 2
    assert "cross_slice=2x4" in found[0].describe()

    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=9),
                        IntegerGen(min_val=-100, max_val=100)],
                    ["k", "v"], length=300)
        return (df.repartition(4, "k").group_by("k")
                .agg(sum_("v", "sv")))

    assert_tpu_and_cpu_are_equal_collect(build, conf=conf)
