"""Plan-time AOT compilation + persistent executable cache (compilecache/).

Pins the acceptance behaviors of docs/compile_cache.md:

* a re-planned query in the same process compiles nothing
  (``compile_cache_misses == 0`` AND ``compiles == 0`` on the second run),
* plan-time AOT demonstrably overlaps: with >= 3 stage programs in a plan,
  every downstream program is compiled by the background pool BEFORE the
  iterator first requests it,
* shape-bucket re-bucketing bounds compile amplification: many distinct
  row counts through one operator cost one compile per BUCKET, not per
  row count (the retracing-regression guard),
* tools/warm_cache.py populates the caches so a subsequent collect
  reports zero registry misses,
* with ``spark.rapids.tpu.compile.cacheDir`` set, a FRESH PROCESS
  re-running the same plan gets persistent-cache hits (subprocess test).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from spark_rapids_tpu import perfcounters as PC
from spark_rapids_tpu import types as T
from spark_rapids_tpu.session import TpuSession, col, lit, sum_

sys.path.insert(0, "tests")


def _conf(**extra):
    c = {"spark.rapids.sql.enabled": True}
    c.update({k.replace("__", "."): v for k, v in extra.items()})
    return c


def _agg_query(sess, bias=0):
    df = sess.create_dataframe(
        {"k": [1, 2, 1, 3, 2, 1, 4, 4], "v": [10, 20, 30, 40, 50, 60, 5, 7]},
        T.StructType([T.StructField("k", T.INT),
                      T.StructField("v", T.LONG)]))
    return (df.select(col("k"), (col("v") + lit(1 + bias)).alias("v1"))
            .filter(col("v1") > lit(2))
            .group_by("k").agg(sum_("v1", "s")))


def test_registry_shares_programs_and_counts():
    from spark_rapids_tpu.compilecache.registry import (
        cached_program,
        get_registry,
    )
    from spark_rapids_tpu.perfcounters import tpu_jit

    built = []

    def factory():
        built.append(1)
        return tpu_jit(lambda x: x + 1), ("aux",)

    key = ("test-registry", os.urandom(8).hex())
    snap = PC.snapshot()
    e1 = cached_program(key, factory)
    e2 = cached_program(key, factory)
    d = PC.since(snap)
    assert e1 is e2
    assert built == [1]            # factory ran once
    assert e2.aux == ("aux",)
    assert d["compile_cache_misses"] == 1
    assert d["compile_cache_hits"] == 1
    assert get_registry().peek(e1.key) is e1


def test_unsafe_expressions_bypass_registry():
    """Expressions closing over Python callables (UDFs) cannot be
    fingerprinted — exprs_fp must refuse rather than risk a collision."""
    from spark_rapids_tpu.compilecache.keys import exprs_fp
    from spark_rapids_tpu.expr.udf import UserDefinedExpression

    e = UserDefinedExpression(lambda x: x, [col("a")], T.LONG)
    assert exprs_fp([e]) is None
    from spark_rapids_tpu.compilecache.registry import cached_program
    from spark_rapids_tpu.perfcounters import tpu_jit

    snap = PC.snapshot()
    entry = cached_program(None, lambda: (tpu_jit(lambda x: x), None))
    d = PC.since(snap)
    assert entry.key == "<unregistered>"
    assert d["compile_cache_misses"] == 0 and d["compile_cache_hits"] == 0


def test_repeated_plan_zero_misses_zero_compiles():
    """The tentpole acceptance: a fresh session re-planning the same
    query (new exec tree, new jit wrappers) compiles NOTHING the second
    time — every program is a registry hit."""
    rows1 = sorted(_agg_query(TpuSession(_conf())).collect())
    snap = PC.snapshot()
    rows2 = sorted(_agg_query(TpuSession(_conf())).collect())
    d = PC.since(snap)
    assert rows2 == rows1
    assert d["compile_cache_misses"] == 0, \
        "second run of an identical plan must not build any program"
    assert d["compiles"] == 0, \
        "second run of an identical plan must not trigger any XLA compile"
    assert d["compile_cache_hits"] >= 1


def test_conf_change_keys_new_programs():
    """Trace-time conf reads are part of program identity: a different
    setting must MISS, not silently reuse the other conf's executable."""
    _agg_query(TpuSession(_conf())).collect()
    snap = PC.snapshot()
    _agg_query(TpuSession(_conf(**{
        "spark.rapids.sql.hasNans": False}))).collect()
    d = PC.since(snap)
    assert d["compile_cache_misses"] >= 1


def test_aot_overlap_downstream_ready_before_first_batch():
    """>= 3 stage programs in one plan: after plan-time submission, every
    downstream program is compiled (or in flight) before the iterator
    requests it — the collect then performs zero registry builds."""
    from spark_rapids_tpu.compilecache import submit_plan
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.ops.sortkeys import SortSpec
    from spark_rapids_tpu.plan.nodes import WindowFunction

    sess = TpuSession(_conf(**{
        # keep window / agg / stage as three distinct programs
        "spark.rapids.tpu.windowChainFusion.enabled": False,
        "spark.rapids.tpu.compile.aot.enabled": False,  # submit manually
    }))
    df = sess.create_dataframe(
        {"k": [1, 2, 1, 3, 2, 1, 2, 3],
         "v": [10, 20, 30, 40, 50, 60, 70, 80]},
        T.StructType([T.StructField("k", T.INT),
                      T.StructField("v", T.LONG)]))
    q = (df.select(col("k"), (col("v") * lit(3)).alias("v3"))
         .group_by("k").agg(sum_("v3", "s"))
         .window([WindowFunction("row_number", None, "rn")],
                 partition_by=["k"],
                 order_by=[(col("s"), SortSpec(ascending=False,
                                               nulls_first=False))])
         .filter(col("rn") <= lit(1))
         .order_by(col("s")))
    root, _ = q._planned()
    assert isinstance(root, TpuExec)
    sub = submit_plan(root, wait=True)
    assert len(sub.items) >= 3, \
        f"expected >=3 enumerable programs, got {sub.programs} " \
        f"(skipped: {sub.skipped})"
    states = sub.states()
    assert all(v == "ready" for v in states.values()), states
    # every enumerated program was compiled by the BACKGROUND pool, i.e.
    # before the iterator could have requested it
    assert all(e.compiled_by == "aot" for _, e, _ in sub.items), \
        [(l, e.compiled_by) for l, e, _ in sub.items]
    snap = PC.snapshot()
    rows = q.collect()
    d = PC.since(snap)
    assert d["compile_cache_misses"] == 0, \
        "AOT should have registered every program the iterator needs"
    assert len(rows) == 3   # rn == 1 row per distinct k
    # differential: same answer with the whole pipeline disabled
    off = TpuSession(_conf(**{
        "spark.rapids.tpu.windowChainFusion.enabled": False,
        "spark.rapids.tpu.compile.registry.enabled": False,
        "spark.rapids.tpu.compile.aot.enabled": False}))
    df2 = off.create_dataframe(
        {"k": [1, 2, 1, 3, 2, 1, 2, 3],
         "v": [10, 20, 30, 40, 50, 60, 70, 80]},
        T.StructType([T.StructField("k", T.INT),
                      T.StructField("v", T.LONG)]))
    q2 = (df2.select(col("k"), (col("v") * lit(3)).alias("v3"))
          .group_by("k").agg(sum_("v3", "s"))
          .window([WindowFunction("row_number", None, "rn")],
                  partition_by=["k"],
                  order_by=[(col("s"), SortSpec(ascending=False,
                                                nulls_first=False))])
          .filter(col("rn") <= lit(1))
          .order_by(col("s")))
    assert rows == q2.collect()


def test_shape_bucket_bounded_compiles():
    """Satellite: many distinct row counts through TpuCoalesceBatchesExec
    re-bucketing compile ONE program per shape bucket, not one per row
    count (guards against accidental retracing regressions)."""
    import numpy as np

    from spark_rapids_tpu.config import TpuConf, set_conf

    # exec-level drive (no session): pin the ambient conf so an earlier
    # test's set_conf (e.g. registry disabled) cannot leak in
    set_conf(TpuConf({"spark.rapids.sql.enabled": True}))
    from spark_rapids_tpu.columnar.column import HostColumn
    from spark_rapids_tpu.exec.basic import (
        TpuLocalTableScanExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.exec.coalesce import (
        CoalesceGoal,
        TpuCoalesceBatchesExec,
    )
    from spark_rapids_tpu.expr.base import Alias

    n = 23
    host = [HostColumn.from_numpy(np.arange(n, dtype=np.int64), T.LONG)]
    schema = T.StructType([T.StructField("v", T.LONG, False)])
    # 5-row chunks -> batches of 5,5,5,5,3: distinct row counts, one
    # 1024-row capacity bucket
    scan = TpuLocalTableScanExec(host, schema, target_batch_rows=5)
    # target_bytes=1 flushes every batch alone -> re-bucketing passthrough
    coal = TpuCoalesceBatchesExec(CoalesceGoal(target_bytes=1), scan)
    # unique literal so earlier tests cannot have pre-registered this key
    e = Alias((col("v") + lit(987123)).resolve(schema), "v1")
    e.resolve(schema)
    proj = TpuProjectExec([e], coal)
    snap = PC.snapshot()
    outs = list(proj.execute_columnar())
    d = PC.since(snap)
    assert [b.num_rows for b in outs] == [5, 5, 5, 5, 3]
    assert {b.capacity for b in outs} == {1024}   # one bucket
    assert d["compiles"] == 1, \
        f"expected 1 compile for 1 shape bucket, got {d['compiles']}"
    assert d["compile_cache_misses"] == 1


def test_warm_cache_tool_then_zero_miss_collect(capsys):
    """Satellite CLI: plan-time enumeration only populates the caches; a
    later collect of the same query reports zero registry misses."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "warm_cache", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "warm_cache.py"))
    wc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wc)
    rc = wc.main(["--queries", "q6", "--rows", "3000", "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["queries"]["q6"]["programs"] >= 1
    import bench as B

    li = B.make_lineitem(3000)
    df = B.build_q6(TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.scan.cacheDeviceBatches": True}), li)
    snap = PC.snapshot()
    rows = df.collect()
    d = PC.since(snap)
    assert rows and rows[0][0] is not None
    assert d["compile_cache_misses"] == 0, \
        "warm_cache should have pre-registered every program q6 needs"


_CHILD = textwrap.dedent("""
    import glob, json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    events = {"persistentHits": 0, "persistentMisses": 0}
    try:
        from jax._src import monitoring

        def _listen(event, **kw):
            if "cache_hit" in event:
                events["persistentHits"] += 1
            elif "cache_miss" in event:
                events["persistentMisses"] += 1

        monitoring.register_event_listener(_listen)
    except Exception:
        pass
    from spark_rapids_tpu.session import TpuSession, col, lit, sum_
    from spark_rapids_tpu import types as T

    cache_dir = sys.argv[1]
    s = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.compile.cacheDir": cache_dir,
        "spark.rapids.tpu.compile.aot.enabled": False,
    })
    # tiny programs: drop the persistence thresholds AFTER the session
    # pointed jax at the dir
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except Exception:
        pass
    df = s.create_dataframe(
        {"k": [1, 2, 1, 3], "v": [10, 20, 30, 40]},
        T.StructType([T.StructField("k", T.INT),
                      T.StructField("v", T.LONG)]))
    q = (df.select(col("k"), (col("v") + lit(5)).alias("v5"))
         .group_by("k").agg(sum_("v5", "s")))
    rows = sorted(q.collect())
    files = [p for p in glob.glob(os.path.join(cache_dir, "**"),
                                  recursive=True) if os.path.isfile(p)]
    print(json.dumps({"rows": rows, "files": len(files), **events}))
""")


def test_persistent_cache_fresh_process_hits(tmp_path):
    """Acceptance: with spark.rapids.tpu.compile.cacheDir set, a FRESH
    process re-running the same plan deserializes executables from the
    on-disk cache instead of compiling."""
    cache_dir = str(tmp_path / "xla-cache")
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    # the conf places the cache only when the environment does not
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run():
        out = subprocess.run(
            [sys.executable, str(script), cache_dir], env=env,
            capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    r1 = run()
    if r1["files"] == 0:
        pytest.skip("persistent compilation cache unsupported on this "
                    "backend/jax version")
    r2 = run()
    assert r2["rows"] == r1["rows"]
    assert r2["persistentHits"] > 0, \
        f"fresh process should hit the on-disk cache: {r2}"
    # and the second process wrote nothing new for this plan
    assert r2["files"] == r1["files"]
