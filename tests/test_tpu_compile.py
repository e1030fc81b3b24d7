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


# The TPU compiler unrolls lax.sort (depth ~ log^2 n): every program that
# holds a multi-operand int64 sort compiles in MINUTES here (PR 23, 8-core
# sandbox: bounded group-by 477 s and join probe 121 s at 2^25 rows, the
# ICI epoch program longer still), against a 1470 s budget for the whole
# suite.  Tier-1 therefore LOWERS those programs for the described chip
# (tracing + StableHLO for the placed operands: x64, sharding and shape
# errors) and the full compile is the `slow` case of the same test.
SORT_PROGRAM = pytest.mark.parametrize(
    "full_compile", [False, pytest.param(True, marks=pytest.mark.slow)],
    ids=["lower", "compile"])


def _lower_or_compile(full_compile, fn, *args):
    if full_compile:
        return _compile(fn, *args).as_text()
    return fn.lower(*args).as_text()


def _long_df(session, n=64, **cols):
    import bench
    from spark_rapids_tpu import types as T

    rng = np.random.default_rng(23)
    data = {name: rng.integers(0, hi, n) for name, hi in cols.items()}
    return bench._df(session, data, [T.LONG] * len(data))


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


@SORT_PROGRAM
def test_bounded_group_by_for_v5e_at_2_25_rows(one_chip, full_compile):
    from spark_rapids_tpu.compilecache.aot import dummy_batch_args
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.session import TpuSession, count_, sum_

    s = TpuSession({"spark.rapids.sql.enabled": True})
    df = _long_df(s, k=50, v=100).group_by("k").agg(
        sum_("v", "s"), count_(None, "c"))
    agg = _find_exec(df._planned()[0], TpuHashAggregateExec)
    cap = 1 << 25
    bound = agg._bounded_groups_cap(cap)
    assert bound, "the bounded-cardinality ladder does not apply"
    jitted, _ = agg._agg_program(bound)[1]()
    text = _lower_or_compile(full_compile, jitted, *_placed(
        dummy_batch_args(agg.input_schema, cap), one_chip))
    assert "sort" in text


@SORT_PROGRAM
def test_sort_merge_join_probe_for_v5e_at_2_25_rows(one_chip, full_compile):
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
    cap = 1 << 25
    pschema = join._probe_child().output
    args = ((abstract_array((cap,), jnp.int64),),
            abstract_scalar(jnp.int32),
            dummy_columns(pschema, cap),
            abstract_scalar(jnp.int32))
    _lower_or_compile(full_compile, jax.jit(join._probe_fn(pschema)),
                      *_placed(args, one_chip))


@SORT_PROGRAM
def test_ici_hash_repartition_for_four_v5e_chips(topo, full_compile):
    """The epoch program of TpuIciShuffleAggExec — local partial
    aggregate, murmur3 all-to-all over ICI, merge — on a 4-device mesh of
    the described chips, 2^25 rows over the mesh."""
    from spark_rapids_tpu.compilecache.aot import dummy_batch_args
    from spark_rapids_tpu.exec.ici import TpuIciShuffleAggExec
    from spark_rapids_tpu.session import TpuSession, count_, sum_

    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.shuffle.mode": "ICI",
                    "spark.rapids.tpu.mesh.enabled": True})
    df = _long_df(s, k=37, v=1000).group_by("k").agg(
        sum_("v", "s"), count_(None, "c"))
    ici = _find_exec(df._planned()[0], TpuIciShuffleAggExec)
    assert ici is not None, df.explain()
    ici.mesh = Mesh(np.array(topo.devices[:4]), (ici.axis,))
    rows = NamedSharding(ici.mesh, P(ici.axis))
    cols, num_rows = dummy_batch_args(ici.children[0].output, 1 << 25)
    program = ici._build_epoch_program(first=True)
    text = _lower_or_compile(
        full_compile, program, _placed(cols, rows),
        _placed(num_rows, NamedSharding(ici.mesh, P())))
    assert "all-to-all" in text or "all_to_all" in text
