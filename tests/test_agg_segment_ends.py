"""The end-row form of the full-width grouped aggregate
(``exec/aggregate.py`` ``_ends_form``): integer and decimal sums, counts and
averages formed at each group's last sorted row by scans, then moved to
their slots by one sort (``_compact_ends``), with no scatter.

``smallGroupsCap`` 0 turns the groups-cap ladder off, so every grouped
program is full width and takes the form at any capacity; 2^30 keeps the
scatter program at the same capacity.  Both must equal the CPU oracle."""
import hashlib
from decimal import Decimal

import numpy as np
import pytest

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import (TpuSession, avg_, count_, max_,
                                      min_, sum_)

from asserts import _rows_key

ENDS = {"spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.agg.smallGroupsCap": 0}
SCATTER = {"spark.rapids.sql.enabled": True,
           "spark.rapids.tpu.agg.smallGroupsCap": 1 << 30}
CPU = {"spark.rapids.sql.enabled": False}


def _frame(s, cols, n):
    """A frame of ``n`` rows from {name: (type, values or None-holed
    list)}."""
    schema = T.StructType([T.StructField(k, t, True)
                           for k, (t, _) in cols.items()])
    return s.create_dataframe({k: list(v)[:n] for k, (_, v) in cols.items()},
                              schema)


def _holes(values, rng, share=0.1):
    return [None if rng.random() < share else v for v in values]


def _ints(s, n=3000):
    rng = np.random.default_rng(1)
    return _frame(s, {
        "k": (T.INT, _holes(map(int, rng.integers(0, 400, n)), rng)),
        "v": (T.INT, _holes(map(int, rng.integers(-2**31, 2**31, n)), rng)),
        "w": (T.LONG, map(int, rng.integers(-2**62, 2**62, n)))}, n) \
        .group_by("k").agg(sum_("v", "sv"), sum_("w", "sw"),
                           count_(None, "n"), count_("v", "cv"),
                           avg_("v", "av"))


def _decimals(precision, scale, digits):
    """sum, avg and count of a DECIMAL(precision, scale) whose values hold
    up to ``digits`` digits; no avg past 18 digits of input, which the
    engine leaves to the CPU."""
    def build(s, n=3000):
        rng = np.random.default_rng(precision)
        dt = T.DecimalType(precision, scale)
        vals = [Decimal(int(x)).scaleb(-scale) for x in
                rng.integers(-10**min(digits, 18), 10**min(digits, 18), n)]
        if digits > 18:     # past int64: near the type's 38 digits
            vals = [v * 10**(digits - 18) for v in vals]
        aggs = [sum_("d", "sd"), count_("d", "cd")]
        if precision <= 18:
            aggs.append(avg_("d", "ad"))
        return _frame(s, {
            "k": (T.LONG, map(int, rng.integers(0, 300, n) * 7919)),
            "d": (dt, _holes(vals, rng))}, n).group_by("k").agg(*aggs)
    return build


def _two_batches(s, n=2000):
    """Two input batches: PARTIAL per batch, a merge, FINAL."""
    rng = np.random.default_rng(2)
    dt = T.DecimalType(12, 2)

    def half(seed):
        r = np.random.default_rng(seed)
        return _frame(s, {
            "k": (T.INT, _holes(map(int, r.integers(0, 200, n)), r)),
            "v": (T.LONG, _holes(map(int, r.integers(-10**9, 10**9, n)), r)),
            "d": (dt, _holes([Decimal(int(x)).scaleb(-2) for x in
                              r.integers(-10**11, 10**11, n)], r))}, n)

    return half(int(rng.integers(99))).union(half(5)) \
        .group_by("k").agg(sum_("v", "sv"), avg_("d", "ad"),
                           sum_("d", "sd"), count_(None, "n"))


def _keyed(keys, n):
    def build(s):
        rng = np.random.default_rng(3)
        return _frame(s, {
            "k": (T.LONG, keys(n)),
            "v": (T.LONG, map(int, rng.integers(-10**6, 10**6, n)))}, n) \
            .group_by("k").agg(sum_("v", "sv"), count_(None, "n"))
    return build


def _string_key(s, n=2000):
    rng = np.random.default_rng(4)
    return _frame(s, {
        "t": (T.STRING, _holes([f"key{x}" for x in rng.integers(0, 150, n)],
                               rng)),
        "v": (T.LONG, map(int, rng.integers(-10**6, 10**6, n)))}, n) \
        .group_by("t").agg(sum_("v", "sv"), count_("v", "cv"))


def _scattering(agg):
    def build(s, n=2000):
        rng = np.random.default_rng(5)
        return _frame(s, {
            "k": (T.INT, map(int, rng.integers(0, 100, n))),
            "v": (T.LONG, map(int, rng.integers(-10**6, 10**6, n))),
            "f": (T.DOUBLE, map(float, rng.integers(-10**6, 10**6, n)))}, n) \
            .group_by("k").agg(sum_("v", "sv"), agg)
    return build


# (build, the form the full-width programs take under ENDS)
CASES = {
    "int_long_count_avg": (_ints, "ends"),
    # a 64-bit sum, and 128-bit sum and average buffers of 64-bit input
    "decimal_7_2": (_decimals(7, 2, 7), "ends"),
    "decimal_12_2": (_decimals(12, 2, 12), "ends"),
    "decimal_38_2": (_decimals(38, 2, 30), "ends"),
    # sums of ~10 values of up to 10^38 in a DECIMAL(38,0): about half
    # the groups pass 38 digits and read NULL
    "decimal_38_past_precision": (_decimals(38, 0, 38), "ends"),
    "partial_final": (_two_batches, "ends"),
    "one_group": (_keyed(lambda n: [42] * n, 1500), "ends"),
    "null_key_only": (_keyed(lambda n: [None] * n, 1500), "ends"),
    # 1024 rows = the capacity bucket: every slot is a group, no padding
    # (and an empty input: no group, one launch of the 1024-row program)
    "every_row_a_group": (_keyed(lambda n: list(range(n, 0, -1)), 1024),
                          "ends"),
    "empty": (_keyed(lambda n: [], 0), "ends"),
    "string_key": (_string_key, "ends"),
    "float_sum": (_scattering(sum_("f", "sf")), "scatter"),
    "min_max": (_scattering(min_("v", "lo")), "scatter"),
    "max_double": (_scattering(max_("f", "hi")), "scatter"),
}


def _collect(conf, build):
    """(rows, launches counted in ``agg_segment_compactions``, the seg=
    markers of the aggregates and of a COMPLETE aggregate's two-phase
    twins)."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec

    s = TpuSession(dict(conf))
    df = build(s)
    snap = PC.snapshot()
    rows = df.collect()
    moved = PC.since(snap).get("agg_segment_compactions", 0)
    marks = []
    if conf is not CPU:
        todo = [df._planned()[0]]
        while todo:
            node = todo.pop()
            todo.extend(node.children)
            if isinstance(node, TpuHashAggregateExec):
                for a in (node,) + getattr(node, "_twin_cache", ()):
                    marks.append(getattr(a, "_seg_form", None))
    return rows, moved, [m for m in marks if m]


@pytest.mark.parametrize("case", list(CASES))
def test_the_end_row_program_equals_the_scatter_program_and_the_oracle(
        case):
    build, form = CASES[case]
    ends, n_ends, marks = _collect(ENDS, build)
    scatter, n_scatter, marks_s = _collect(SCATTER, build)
    want, _, _ = _collect(CPU, build)
    if case == "decimal_38_past_precision":
        # the CPU oracle keeps a sum past 38 digits, Spark reads it NULL
        want = [(k, None if s is not None and abs(s) >= 10**38 else s, n)
                for k, s, n in want]
        assert any(r[1] is None for r in want) \
            and any(r[1] is not None for r in want)
    assert _rows_key(ends, True) == _rows_key(scatter, True) \
        == _rows_key(want, True)
    assert n_scatter == 0 and set(marks_s) <= {"scatter"}
    # one launch a full-width program: the partial of each batch and the
    # final of ``partial_final``, the one COMPLETE program of the others
    launches = 3 if case == "partial_final" else 1
    assert n_ends == (launches if form == "ends" else 0)
    assert marks == [form] * min(launches, 2), marks


def _agg_program(conf):
    """(the StableHLO of the full-width program of a DECIMAL(12,2) sum,
    average and count by a LONG key, at the capacity it ran, and the
    launches counted over two collects)."""
    import jax.numpy as jnp

    s = TpuSession(dict(conf))
    df = _decimals(12, 2, 10)(s)
    snap = PC.snapshot()
    df.collect()
    df.collect()
    moved = PC.since(snap)["agg_segment_compactions"]
    agg = df._planned()[0]
    (batch,) = list(agg.children[0].execute_columnar())
    args = (tuple(batch.columns), jnp.int32(batch.num_rows))
    return agg._agg_jit(None).lower(*args).as_text(), moved


def _scatters(text):
    """The operand types of each scatter in a StableHLO text."""
    import re

    return re.findall(r'"stablehlo\.scatter"\(.*?\}\) : \(([^)]*)\)',
                      text, re.S)


def test_the_end_row_program_holds_no_full_width_scatter_and_is_counted():
    text, moved = _agg_program(ENDS)
    # the one scatter left sets row 0's segment start (group_segments),
    # as in every grouped program: one index, one element
    assert [t.split(", ")[1:] for t in _scatters(text)] == [
        ["tensor<1xi32>", "tensor<i1>"]]
    assert text.count('"stablehlo.sort"') == 2      # the keys', the slots'
    assert moved == 2                               # one launch a collect
    text, moved = _agg_program(SCATTER)
    assert len(_scatters(text)) > 10
    assert moved == 0
    assert hashlib.sha256(text.encode()).hexdigest() == FULL_WIDTH_SCATTER


# sha256 of the StableHLO (as test_star_join_2dim.FUSED_PROGRAMS) of the
# aggregate programs of the one-chip cells' plans, pinned from the commit
# before the end-row form, each lowered at 1,024 rows on the plan of the
# cell's tiny CPU rehearsal on the tests' eight devices: a grouped
# aggregate's ladder rung of 64 (the join's 40-group sum runs its rung at
# size) and its full-width program, which a capacity at or below the rung
# keeps; Q6's global aggregate; a COMPLETE aggregate's two-phase twins
# (the parquet scan's units) and their merges of buffer rows.  The star
# joins' are pinned there.
ONE_CHIP_PROGRAMS = {
    "q6_resident": {
        "agg:Complete:None":
            "19b33e694888b4247eb452fd4af185dd4c2a7be97c7f6a944d8027a673bccefc",
        "agg:Partial:None":
            "19b33e694888b4247eb452fd4af185dd4c2a7be97c7f6a944d8027a673bccefc",
        "merge:Partial":
            "3f0639ca379614ffbedc05d84f843ac76ea11677358d2f3df50f66b52229b845",
        "agg:Final:None":
            "7baa3118d805298762909c6cff8a93ab962e30da4efd29948eea92066c154674",
        "merge:Final":
            "3f0639ca379614ffbedc05d84f843ac76ea11677358d2f3df50f66b52229b845",
    },
    "q6_parquet_scan": {
        "agg:Complete:None":
            "1b726f5faf5810d2888bd5a8b01d350a45f9dc3b8b149b0d105ec3c813253abc",
        "agg:Partial:None":
            "1b726f5faf5810d2888bd5a8b01d350a45f9dc3b8b149b0d105ec3c813253abc",
        "merge:Partial":
            "3fee41d3b52f2f5fd62b4641649a8eea00c47ee991f6259f9519b798f41307f9",
        "agg:Final:None":
            "dd738b726d1c6c5fd3b93a28a4917dcba30825145aa06d8ae484fbe808fc27cf",
        "merge:Final":
            "3fee41d3b52f2f5fd62b4641649a8eea00c47ee991f6259f9519b798f41307f9",
    },
    "ds_shuffled_join": {
        "agg:Complete:64":
            "55ec6150dc60dd56057f431ebbeafb48a5f97ad87d2d937d0eb534560ee1bc79",
        "agg:Complete:None":
            "04d346c3383c93b339d44c408f2def465bd25b8fcfdaf1897a3e941224890df1",
        "agg:Partial:64":
            "55ec6150dc60dd56057f431ebbeafb48a5f97ad87d2d937d0eb534560ee1bc79",
        "agg:Partial:None":
            "04d346c3383c93b339d44c408f2def465bd25b8fcfdaf1897a3e941224890df1",
        "merge:Partial":
            "755632f5a62776e3673b24c53e985e864707f80705ceac050c05b740b4756af7",
        "agg:Final:64":
            "7003a6b9c080849deacc8d83a339c611c6135b62b50ee4406045ed3c4cec0d13",
        "agg:Final:None":
            "98a58a7e27a9378caef4bc83043b7a80cbb1e34200d6562398627a2ebf166910",
        "merge:Final":
            "755632f5a62776e3673b24c53e985e864707f80705ceac050c05b740b4756af7",
    },
}
# the scatter program at full width below the rung (``_agg_program``'s)
FULL_WIDTH_SCATTER = (
    "4bc0dc9f3185c2e10fc4f3a68e0be320f09eb5e69ad0e014927b816738a842a6")


def _cell_programs(name):
    """{program: digest} of the aggregate programs of cell ``name``'s plan,
    a COMPLETE aggregate's two-phase twins among them."""
    import os
    import sys
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
    from test_benchmark_harness import tiny

    from benchmark.harness import cell as C
    from benchmark.harness.manifest import Manifest
    from spark_rapids_tpu.compilecache.aot import dummy_batch_args
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.plan.nodes import AggregateMode

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = tiny(Manifest(root).cell(name))
    s = TpuSession(dict(cell.conf))
    with tempfile.TemporaryDirectory() as d:
        df = cell.query.build(C.make_frames(cell, s, C.make_tables(cell, 7),
                                            d))
        root_exec = df._planned()[0]

    def digest(jitted, schema):
        text = jitted.lower(*dummy_batch_args(schema, 1024)).as_text()
        return hashlib.sha256(text.encode()).hexdigest()

    out, todo = {}, [root_exec]
    while todo:
        node = todo.pop()
        todo.extend(node.children)
        if not isinstance(node, TpuHashAggregateExec):
            continue
        twins = (node._complete_twins()
                 if node.mode == AggregateMode.COMPLETE else ())
        for a in (node,) + twins:
            for B in (64, None) if a.grouping else (None,):
                out[f"agg:{a.mode.value}:{B}"] = digest(
                    a._agg_program(B)[1]()[0], a.input_schema)
            if a.mode != AggregateMode.COMPLETE:
                out[f"merge:{a.mode.value}"] = digest(a._merge_jit(),
                                                      a._buffer_schema())
    return out


@pytest.mark.parametrize("name", list(ONE_CHIP_PROGRAMS))
def test_the_one_chip_cells_aggregate_programs_keep_their_code(name):
    assert _cell_programs(name) == ONE_CHIP_PROGRAMS[name]
