"""Ask the chip's compiler before a chip run: AOT-compile the main path's
programs at real sizes for a DESCRIBED v5e (no chip attached, nothing
runs).  Interpret-mode Pallas and the XLA:CPU backend accept programs
Mosaic and the TPU compiler refuse — misaligned blocks, i64 grid
scalars, a program that does not fit HBM — and a refusal here costs no
chip time.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library, and a worker that
merely collects this file must not.  Everything compiles in this process
with the persistent compile cache off (an entry written for a described
chip cannot be read back without one).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from chip_smoke import _find_exec


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    """The abstract operands of ``tree``, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    print(compiled.memory_analysis())
    return compiled


# Every program that holds a multi-operand int64 sort compiles in
# MINUTES past the 8,192-row bucket (PR 23, 8-core sandbox, bounded
# group-by: 10.81 s at 2^13 rows, 187 s at 2^16, 352 s at 2^22, more than
# 400 s at 2^25; join probe 1.3 s / 72.6 s / 121 s at 2^13 / 2^16 / 2^25;
# the one-program ICI epoch of PR 23 11.6 s / 102.9 s at 2^13 / 2^16 and
# longer than 12 min at 2^25).  Since PR 25 the chip compiles the join's
# and the aggregate's programs at the 2^22-row bucket for every PR (the
# benchmark's `first_setup_s`), and the mesh aggregate's two programs at
# 2^24 rows a chip in the four-chip cell (`q18_mesh_groupby_4chip`), so
# what tier-1 keeps is the LOWERING at real sizes (tracing + StableHLO
# for the placed operands: x64, sharding and shape errors, and the
# exchange's counted quota at the Q18 cell's own size) and the proof that
# XLA:TPU accepts each sort-bearing program at all, compiled at 2^13 rows,
# below the cliff.  The compile past the cliff, at 2^16 and (does it fit
# HBM?) at 2^25 rows, is the `slow` cases' and the chip's.
TIER1_ROWS = 1 << 13
PAST_CLIFF_ROWS = 1 << 16
REAL_ROWS = 1 << 25
SORT_PROGRAM = pytest.mark.parametrize(
    "full_compile", [False, pytest.param(True, marks=pytest.mark.slow)],
    ids=["lower", "compile"])


def _lower_or_compile(full_compile, lowered):
    if full_compile:
        compiled = lowered.compile()
        print(compiled.memory_analysis())
        return compiled.as_text()
    return lowered.as_text()


def _long_df(session, n=64, **cols):
    import bench
    from spark_rapids_tpu import types as T

    rng = np.random.default_rng(23)
    data = {name: rng.integers(0, hi, n) for name, hi in cols.items()}
    return bench._df(session, data, [T.LONG] * len(data))


def _lower_group_by(cap, one_chip, bounded):
    """The grouped-aggregate program the engine runs on a ``cap``-row
    batch: the bounded-cardinality one past the groups-cap conf, the
    full-width one at or below it."""
    from spark_rapids_tpu.compilecache.aot import dummy_batch_args
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.session import TpuSession, count_, sum_

    s = TpuSession({"spark.rapids.sql.enabled": True})
    df = _long_df(s, k=50, v=100).group_by("k").agg(
        sum_("v", "s"), count_(None, "c"))
    agg = _find_exec(df._planned()[0], TpuHashAggregateExec)
    bound = agg._bounded_groups_cap(cap)
    assert bool(bound) == bounded, f"groups cap {bound} at {cap} rows"
    jitted, _ = agg._agg_program(bound)[1]()
    return jitted.lower(*_placed(
        dummy_batch_args(agg.input_schema, cap), one_chip))


def _lower_sort_merge_join_probe(cap, one_chip):
    from spark_rapids_tpu.compilecache.aot import (
        abstract_array,
        abstract_scalar,
        dummy_columns,
    )
    from spark_rapids_tpu.exec.join import TpuAdaptiveJoinExec
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.sql.autoBroadcastJoinThreshold": "-1"})
    df = _long_df(s, k=50, v=100).join(_long_df(s, k=50, w=100), on=["k"])
    join = _find_exec(df._planned()[0], TpuAdaptiveJoinExec).shuffled
    pschema = join._probe_child().output
    args = ((abstract_array((cap,), jnp.int64),),
            abstract_scalar(jnp.int32),
            dummy_columns(pschema, cap),
            abstract_scalar(jnp.int32))
    return jax.jit(join._probe_fn(pschema)).lower(*_placed(args, one_chip))


def _lower_left_outer_join_lookup(cap, one_chip):
    """The LEFT OUTER join's lookup program against a unique build side,
    both sides of ``cap`` rows: the binary search and the MXU gather at
    2^13 rows, the merge sort and the VPU gathers past 2^14."""
    from spark_rapids_tpu.compilecache.aot import (
        abstract_array,
        abstract_scalar,
        dummy_columns,
    )
    from spark_rapids_tpu.exec.join import TpuAdaptiveJoinExec
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.sql.autoBroadcastJoinThreshold": "-1"})
    df = _long_df(s, k=50, v=100).join(_long_df(s, k=50, w=100), on=["k"],
                                       how="left")
    join = _find_exec(df._planned()[0], TpuAdaptiveJoinExec).shuffled
    pschema = join._probe_child().output
    bcols = dummy_columns(join._build_child().output, cap)
    args = ((abstract_array((cap,), jnp.int64),),
            abstract_array((cap,), jnp.int32),
            abstract_scalar(jnp.int32),
            tuple(bcols[i] for i in join._b_sel),
            dummy_columns(pschema, cap),
            abstract_scalar(jnp.int32))
    return jax.jit(join._lookup_fn(pschema)).lower(*_placed(args, one_chip))


def _ici_agg(topo, df_of):
    """The TpuIciShuffleAggExec of ``df_of(session)`` on a 4-device mesh
    of the described chips."""
    from spark_rapids_tpu.exec.ici import TpuIciShuffleAggExec
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.shuffle.mode": "ICI",
                    "spark.rapids.tpu.mesh.enabled": True,
                    "spark.rapids.tpu.mesh.devices": 4})
    df = df_of(s)
    ici = _find_exec(df._planned()[0], TpuIciShuffleAggExec)
    assert ici is not None, df.explain()
    ici.mesh = Mesh(np.array(topo.devices[:4]), (ici.axis,))
    return ici


def _lower_ici_agg(ici, cap, groups_cap, quota):
    """TpuIciShuffleAggExec's two programs, ``cap`` rows over the mesh:
    (a) the local partial aggregate, murmur3 ids and the send matrix,
    (b) the all-to-all at ``quota`` rows a peer of the partial's first
    ``groups_cap`` rows, then the final aggregate.  Returns both
    lowerings, (b) placed on (a)'s output shapes."""
    from spark_rapids_tpu.compilecache.aot import (abstract_scalar,
                                                   dummy_columns)

    rows = NamedSharding(ici.mesh, P(ici.axis))
    one = _placed(abstract_scalar(jnp.int32), NamedSharding(ici.mesh, P()))
    partial = ici._build_partial_program().lower(
        _placed(dummy_columns(ici.children[0].output, cap), rows), one, one)
    exchange = ici._build_exchange_program(groups_cap, quota, 0, True).lower(
        *_placed(partial.out_info, rows))
    return partial, exchange


def _lower_ici_epoch(cap, topo, program):
    """One of the mesh aggregate's programs for a grouped sum and count,
    ``cap`` rows over the mesh, the exchange at the densest quota."""
    from spark_rapids_tpu.session import count_, sum_

    ici = _ici_agg(topo, lambda s: _long_df(s, k=37, v=1000).group_by(
        "k").agg(sum_("v", "s"), count_(None, "c")))
    local = cap // 4
    return _lower_ici_agg(ici, cap, local, local)[program]


# expect trouble from the (_TILE, 128) uint32 blocks and from x64 grid
# scalars; bw=32 is past what the reader dispatches (MAX_BIT_WIDTH) but
# is the widest window the kernel body can express
@pytest.mark.parametrize("tiles", [1, 64])
@pytest.mark.parametrize("bw", [1, 6, 13, 32])
def test_pallas_unpack_compiles_for_v5e(one_chip, bw, tiles):
    from spark_rapids_tpu.pallas import decode as PD

    with jax.enable_x64(False):
        kernel = PD._build_unpack(tiles, bw, interpret=False)
        mat = jax.ShapeDtypeStruct((tiles * PD._TILE, PD._LANES),
                                   jnp.uint32, sharding=one_chip)
        compiled = _compile(kernel, mat)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_q6_stage_compiles_at_2_26_rows(one_chip):
    import __graft_entry__ as G

    q6_step, example = G.entry()
    n = 1 << 26
    args = [jax.ShapeDtypeStruct((n,) + a.shape[1:] if a.ndim else (),
                                 a.dtype, sharding=one_chip)
            for a in example]
    _compile(jax.jit(q6_step), *args)


# program -> (its lowering at (rows, topo, one_chip), what the compiled
# text must hold one of)
_SORT_PROGRAMS = {
    "group_by": (
        lambda rows, topo, chip: _lower_group_by(rows, chip, bounded=False),
        ("sort",)),
    "sort_merge_join_probe": (
        lambda rows, topo, chip: _lower_sort_merge_join_probe(rows, chip),
        ()),
    "left_outer_join_lookup": (
        lambda rows, topo, chip: _lower_left_outer_join_lookup(rows, chip),
        ()),
    "ici_epoch_4_chips": (
        lambda rows, topo, chip: _lower_ici_epoch(rows, topo, 1),
        ("all-to-all", "all_to_all")),
    "ici_partial_4_chips": (
        lambda rows, topo, chip: _lower_ici_epoch(rows, topo, 0),
        ("sort",)),
}


@pytest.mark.parametrize(
    "rows", [TIER1_ROWS,
             pytest.param(PAST_CLIFF_ROWS, marks=pytest.mark.slow)],
    ids=["2_13_rows", "2_16_rows"])
@pytest.mark.parametrize("program", list(_SORT_PROGRAMS))
def test_sort_program_compiles_for_v5e(topo, one_chip, program, rows):
    """XLA:TPU itself (not only the lowering) accepts each of the three
    sort-bearing programs."""
    lower, expect_one_of = _SORT_PROGRAMS[program]
    text = _lower_or_compile(True, lower(rows, topo, one_chip))
    assert not expect_one_of or any(w in text for w in expect_one_of)


@SORT_PROGRAM
def test_bounded_group_by_for_v5e_at_2_25_rows(one_chip, full_compile):
    text = _lower_or_compile(
        full_compile, _lower_group_by(REAL_ROWS, one_chip, bounded=True))
    assert "sort" in text


@SORT_PROGRAM
def test_sort_merge_join_probe_for_v5e_at_2_25_rows(one_chip, full_compile):
    _lower_or_compile(
        full_compile, _lower_sort_merge_join_probe(REAL_ROWS, one_chip))


@SORT_PROGRAM
def test_left_outer_join_lookup_for_v5e_at_2_25_rows(one_chip, full_compile):
    text = _lower_or_compile(
        full_compile, _lower_left_outer_join_lookup(REAL_ROWS, one_chip))
    assert "sort" in text


@SORT_PROGRAM
def test_ici_hash_repartition_for_four_v5e_chips(topo, full_compile):
    text = _lower_or_compile(full_compile,
                             _lower_ici_epoch(REAL_ROWS, topo, 1))
    assert "all-to-all" in text or "all_to_all" in text


def test_q18_exchange_takes_the_counted_quota_on_four_v5e_chips(topo):
    """The Q18 cell at its full size: the send matrix of the generator's
    59,986,052 rows in the balanced layout (each shard's distinct
    l_orderkey by its murmur3 peer) puts Q at 2^20 and G at 2^22, and the
    lowered exchange program receives n_dev x Q = 2^22 rows a chip and
    merges there, where the one-program epoch merged 4 x 2^24."""
    from benchmark.harness.manifest import Manifest
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import (DEFAULT_ROW_BUCKETS,
                                                  DeviceColumn,
                                                  round_up_bucket)
    from spark_rapids_tpu.ops.hashing import spark_partition_ids

    cell = Manifest().cell("q18_mesh_groupby_4chip")
    rows = cell.fact_rows
    keys = cell.generators["lineitem"].make(
        rows, np.random.default_rng([2**31 + 18, 0]))["l_orderkey"]
    # the stage's balanced layout of the resident 2^26-row batch
    per = -(-rows // 4)
    cap = round_up_bucket(rows, DEFAULT_ROW_BUCKETS) // 4
    assert (per, cap) == (14_996_513, 1 << 24)
    sent = np.zeros((4, 4), np.int64)
    for d in range(4):
        shard = np.unique(keys[d * per:(d + 1) * per])
        col = DeviceColumn(T.LONG, jnp.ones(len(shard), jnp.bool_),
                           data=jnp.asarray(shard))
        sent[d] = np.bincount(np.asarray(spark_partition_ids([col], 4)),
                              minlength=4)
    # an order split by a shard boundary is a group on both chips
    assert 15_000_000 <= sent.sum() <= 15_000_003
    quota = round_up_bucket(int(sent.max()), DEFAULT_ROW_BUCKETS)
    groups_cap = round_up_bucket(int(sent.sum(1).max()), DEFAULT_ROW_BUCKETS)
    assert (quota, groups_cap) == (1 << 20, 1 << 22)

    from benchmark.harness.cell import _resident_frame

    gen = cell.generators["lineitem"]
    ici = _ici_agg(topo, lambda s: cell.query.build({"lineitem": (
        _resident_frame(s, gen.make(64, np.random.default_rng(18)),
                        gen.TYPES))}))
    partial, exchange = _lower_ici_agg(ici, 4 * cap, groups_cap, quota)
    (pkey, _), tgt, matrix = partial.out_info
    assert pkey.data.shape == (4 * cap,) and matrix.shape == (4, 4)
    (fkey, _), counts = exchange.out_info
    # the global shape of a row-sharded output: 4 chips x n_dev x Q
    assert fkey.data.shape == (4 * 4 * quota,) and counts.shape == (4,)
