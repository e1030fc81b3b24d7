"""AQE skew-split for the mesh join.

A 100:1 hot key routes most probe rows (and their join output) to one
device; the exec detects it from the per-epoch matched totals it syncs
anyway and splits the epoch in half (OptimizeSkewedJoin analog over
epochs/devices).  The tests pin the split-count evidence and oracle
agreement, plus the kill switch.
"""
import jax
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, col

import sys

sys.path.insert(0, "tests")
from asserts import assert_tpu_and_cpu_are_equal_collect  # noqa: E402

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")

# every test here EXECUTES the mesh join (multi-capacity SPMD compiles,
# minutes on CPU XLA) — outside the tier-1 'not slow' budget for the
# same reason as test_multichip's collective tests (ISSUE 10)
pytestmark = pytest.mark.slow

_CONF = {
    "spark.rapids.sql.enabled": True,
    "spark.rapids.shuffle.mode": "ICI",
    "spark.rapids.tpu.mesh.enabled": True,
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.tpu.mesh.skewJoin.minEpochRows": 256,
}


def _skewed(session, n=4000):
    # hot key 7 on ~99% of probe rows; build has several rows for it
    lk = [7 if i % 100 else i % 37 for i in range(n)]
    left = session.create_dataframe(
        {"k": lk, "v": list(range(n))},
        T.StructType([T.StructField("k", T.LONG, False),
                      T.StructField("v", T.LONG)]))
    rk = list(range(30)) + [7, 7, 7]
    right = session.create_dataframe(
        {"k": rk, "w": [x * 10 for x in rk]},
        T.StructType([T.StructField("k", T.LONG, False),
                      T.StructField("w", T.LONG)]))
    return left.join(right, on="k")


def _find_ici_join(e):
    from spark_rapids_tpu.exec.ici import TpuIciShuffleJoinExec

    if isinstance(e, TpuIciShuffleJoinExec):
        return e
    for c in getattr(e, "children", []):
        r = _find_ici_join(c)
        if r is not None:
            return r
    return None


@needs_mesh
def test_skewed_key_splits_epochs_and_matches_oracle():
    s = TpuSession(dict(_CONF))
    df = _skewed(s)
    root, _ = df._planned()
    j = _find_ici_join(root)
    assert j is not None, "mesh join must be installed"
    tpu_rows = sorted(df.collect())
    assert j.skew_splits > 0, "100:1 hot key must trigger epoch splits"
    assert j.metrics["skewSplits"].value == j.skew_splits

    cpu = TpuSession({"spark.rapids.sql.enabled": False})
    cpu_rows = sorted(_skewed(cpu).collect())
    assert tpu_rows == cpu_rows


@needs_mesh
def test_skew_split_kill_switch():
    conf = dict(_CONF)
    conf["spark.sql.adaptive.skewJoin.enabled"] = False
    s = TpuSession(conf)
    df = _skewed(s)
    root, _ = df._planned()
    j = _find_ici_join(root)
    tpu_rows = sorted(df.collect())
    assert j.skew_splits == 0

    cpu = TpuSession({"spark.rapids.sql.enabled": False})
    assert tpu_rows == sorted(_skewed(cpu).collect())


@needs_mesh
def test_uniform_keys_do_not_split():
    s = TpuSession(dict(_CONF))
    n = 4000
    left = s.create_dataframe(
        {"k": [i % 64 for i in range(n)], "v": list(range(n))},
        T.StructType([T.StructField("k", T.LONG, False),
                      T.StructField("v", T.LONG)]))
    right = s.create_dataframe(
        {"k": list(range(64)), "w": list(range(64))},
        T.StructType([T.StructField("k", T.LONG, False),
                      T.StructField("w", T.LONG)]))
    df = left.join(right, on="k")
    root, _ = df._planned()
    j = _find_ici_join(root)
    rows = df.collect()
    assert len(rows) == n
    assert j.skew_splits == 0
