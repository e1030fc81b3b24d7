"""The unfused LEFT OUTER join against a build side of unique keys
(``exec/join.py`` ``_lookup_unique``): one lookup a probe row, the probe's
columns passed through, no pair expansion.

Every case runs three ways and compares the rows: the CPU row oracle, the
lookup path, and the pair path of the same plan (the gate
``_unique_build`` forced to say "not unique", here alone).  The counters
say which path ran: ``join_lookups_unique`` one a probe batch of the
lookup, ``join_rows_materialized`` the pair path's rows."""
import zlib
from decimal import Decimal

import numpy as np
import pytest

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.join import _BaseTpuJoinExec
from spark_rapids_tpu.expr.predicates import GreaterThan
from spark_rapids_tpu.session import TpuSession, col, lit

ON = {1: ["k"], 2: ["t", "k"]}


def _table(rng, n, keys, value, nulls=0.0, key_nulls=0.0, words=1):
    """A table of ``n`` rows: key columns ``ON[words]`` drawn from
    ``keys`` (a list of key tuples, each row's in turn when ``n`` equals
    its length), a decimal and an int column named by ``value``."""
    rows = keys if n == len(keys) else [keys[i] for i in
                                        rng.integers(0, len(keys), n)]
    data = {"t": [r[0] for r in rows], "k": [r[-1] for r in rows]}
    null_key = rng.random(n) < key_nulls
    data["k"] = [None if z else v for z, v in zip(null_key, data["k"])]
    data[value] = [None if rng.random() < nulls else
                   Decimal(int(rng.integers(-99999, 99999))).scaleb(-2)
                   for _ in range(n)]
    data[value + "_n"] = [int(x) for x in rng.integers(0, 1000, n)]
    fields = ([T.StructField("t", T.LONG)] if words == 2 else []) + [
        T.StructField("k", T.INT), T.StructField(value, T.DecimalType(9, 2)),
        T.StructField(value + "_n", T.INT)]
    return data, T.StructType(fields)


def _keys(rng, n, words, lo=1):
    """``n`` distinct key tuples (ticket, item) of ``words`` words, none
    of them 0."""
    seen = set()
    while len(seen) < n:
        t = int(rng.integers(-2**40, 2**40)) if words == 2 else 0
        seen.add((t, int(rng.integers(lo, lo + 4 * n))))
    return sorted(seen)


def _case(name):
    """(build(session) -> DataFrame, conf, how many probe batches)."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    words = 2 if "two_words" in name else 1
    n_probe, n_build, conf, batches = 300, 40, {}, 1
    if name == "large_build_vpu_merge":
        n_probe, n_build = 20000, 10000      # capacities 32,768 and 16,384
    elif name == "build_larger_than_probe":
        n_probe, n_build = 3000, 10000       # 4,096 against 16,384
    elif name == "several_probe_batches":
        conf = {"spark.rapids.sql.reader.batchSizeRows": 64}
        n_build, batches = 60, 5
    elif name == "shuffled":
        # both sides through an exchange: one probe batch a partition
        conf = {"spark.sql.autoBroadcastJoinThreshold": "-1",
                "spark.sql.shuffle.partitions": 4}
        batches = 4
    pool = _keys(rng, n_build * 2, words)
    bkeys = [pool[i] for i in sorted(rng.choice(len(pool), n_build,
                                                replace=False))]
    build_data, build_schema = _table(rng, n_build, bkeys, "rv", nulls=0.1,
                                      words=words)
    # probe keys: half of them matching, half from the rest of the pool
    probe_data, probe_schema = _table(rng, n_probe, pool, "lv", nulls=0.1,
                                      key_nulls=0.1, words=words)
    if name == "null_keys_repeat_a_valid_key":
        # a null key's data is 0, and so is a valid build key's
        build_data["k"] = [0] + [None] * 5 + build_data["k"][6:]
        probe_data["k"][:3] = [0, None, 0]
    if name == "filtered_rows_repeat_a_valid_key":
        # each key twice: once kept (rv_n over 500), once filtered out
        kept = dict(build_data, rv_n=[600 + v for v in build_data["rv_n"]])
        gone = dict(build_data, rv_n=[v % 500 for v in build_data["rv_n"]])
        build_data = {c: kept[c] + gone[c] for c in build_data}
    if name == "empty_build":
        build_data = {c: [] for c in build_data}
    how = "right" if name.startswith("right") else "left"

    def build(s):
        probe = s.create_dataframe(probe_data, probe_schema)
        bside = s.create_dataframe(build_data, build_schema)
        if name == "filtered_rows_repeat_a_valid_key":
            bside = bside.filter(GreaterThan(col("rv_n"), lit(500)))
        if how == "right":
            # RIGHT OUTER looks the probe up in the LEFT side
            out = bside.join(probe, on=ON[words], how="right")
        else:
            out = probe.join(bside, on=ON[words], how="left")
        if name == "pruned_emit":
            out = out.select(col("rv"), col("lv_n"))
        return out

    return build, conf, batches


def _join_node(node):
    """The join that ran, also inside an adaptive join's wrapper."""
    if isinstance(node, _BaseTpuJoinExec):
        return node
    for c in (*getattr(node, "inner_execs", tuple)(),
              *getattr(node, "children", [])):
        found = _join_node(c)
        if found is not None:
            return found
    return None


def _collect(build, conf):
    """(sorted rows, counters moved, the join node's describe())."""
    s = TpuSession({"spark.rapids.sql.enabled": True, **conf})
    df = build(s)
    snap = PC.snapshot()
    rows = df.collect()
    delta = PC.since(snap)
    join = _join_node(df._planned()[0])
    return (sorted(rows, key=repr),
            {k: delta[k] for k in ("join_lookups_unique",
                                   "join_rows_materialized")},
            join.describe())


CASES = ["left_one_word", "left_two_words", "right_one_word",
         "right_two_words", "null_keys_repeat_a_valid_key",
         "filtered_rows_repeat_a_valid_key", "empty_build", "pruned_emit",
         "several_probe_batches", "shuffled", "large_build_vpu_merge",
         "build_larger_than_probe"]


@pytest.mark.parametrize("name", CASES)
def test_the_lookup_answers_as_the_pairs_and_the_oracle(name, monkeypatch):
    build, conf, batches = _case(name)
    want = sorted(build(TpuSession({"spark.rapids.sql.enabled": False,
                                    **conf})).collect(), key=repr)
    assert any(None in r for r in want)     # unmatched or null somewhere
    rows, moved, desc = _collect(build, conf)
    assert rows == want
    assert moved == {"join_lookups_unique": batches,
                     "join_rows_materialized": 0}
    assert desc.endswith(" path=lookup")
    with monkeypatch.context() as m:
        m.setattr(_BaseTpuJoinExec, "_unique_build", lambda self, b: False)
        rows, moved, desc = _collect(build, conf)
    assert rows == want
    assert moved["join_lookups_unique"] == 0
    assert moved["join_rows_materialized"] == len(want)
    assert desc.endswith(" path=pairs")


def _dup_case(dup: bool):
    """A LEFT join whose build side repeats one valid key, or not."""
    probe = {"k": list(range(50)), "lv": list(range(50))}
    bkeys = list(range(0, 60, 3))
    if dup:
        bkeys[5] = bkeys[4]
    bside = {"k": bkeys, "rv": [10 * k for k in range(len(bkeys))]}

    def build(s):
        return s.create_dataframe(probe, T.StructType(
            [T.StructField("k", T.INT), T.StructField("lv", T.INT)])).join(
            s.create_dataframe(bside, T.StructType(
                [T.StructField("k", T.INT), T.StructField("rv", T.INT)])),
            on="k", how="left")

    return build


@pytest.mark.parametrize("dup", [True, False], ids=["repeated", "unique"])
def test_a_repeated_valid_key_takes_the_pair_path(dup):
    build = _dup_case(dup)
    want = sorted(build(TpuSession({"spark.rapids.sql.enabled": False}))
                  .collect(), key=repr)
    rows, moved, desc = _collect(build, {})
    assert rows == want and len(want) == 50 + dup
    assert moved["join_lookups_unique"] == (0 if dup else 1)
    assert (moved["join_rows_materialized"] > 0) == dup
    assert desc.endswith(" path=pairs" if dup else " path=lookup")


def test_each_collect_asks_its_own_build_side():
    """One session, plans of the same shape (the same programs from the
    registry) over build sides that differ in uniqueness: each collect
    takes the path its build side allows, nothing remembered."""
    s = TpuSession({"spark.rapids.sql.enabled": True})
    oracle = TpuSession({"spark.rapids.sql.enabled": False})
    for dup in (False, True, False, True):
        build = _dup_case(dup)
        df = build(s)
        for _ in range(2):
            snap = PC.snapshot()
            rows = sorted(df.collect(), key=repr)
            moved = PC.since(snap)
            assert rows == sorted(build(oracle).collect(), key=repr)
            assert moved["join_lookups_unique"] == (0 if dup else 1)
            assert (moved["join_rows_materialized"] == 51) == dup
