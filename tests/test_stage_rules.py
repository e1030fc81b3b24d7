"""Transition-stage rule registry: explain/fallback parity for the
collective (ICI) and fused execs.

Reference analog: GpuOverrides.execs entries get per-exec tagging with
``spark.rapids.sql.explain`` fallback reasons; the stages installed by
``TpuTransitionOverrides`` (mesh collectives, whole-stage fusions, the
adaptive shuffle reader) report through the same channel via the
``StageRule`` registry + per-apply decision ledger.
"""
import jax
import pytest

from spark_rapids_tpu.session import TpuSession, col, lit, sum_

import sys

sys.path.insert(0, "tests")
from data_gen import IntegerGen, gen_df  # noqa: E402

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")

_ICI_CONF = {
    "spark.rapids.sql.enabled": True,
    "spark.rapids.shuffle.mode": "ICI",
    "spark.rapids.tpu.mesh.enabled": True,
    "spark.sql.autoBroadcastJoinThreshold": "-1",
}


def _decisions(df):
    _, meta = df._planned()
    return {(n, ok): reason for n, ok, reason in meta.stage_decisions}


def _grouped(s):
    df = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                ["k", "v"], length=64)
    return df.group_by("k").agg(sum_("v", "s"))


def test_registry_lists_all_stage_execs():
    from spark_rapids_tpu.overrides.transitions import stage_rules

    names = set(stage_rules())
    assert names == {
        "TpuIciShuffleAggExec", "TpuIciShuffleJoinExec", "TpuIciSortExec",
        "TpuIciWindowExec", "TpuIciRepartitionExec", "TpuJoinAggFusedExec",
        "TpuWindowChainFusedExec", "TpuAdaptiveShuffleReaderExec",
        "TpuFusedPipelineExec"}
    for r in stage_rules().values():
        assert r.conf_key and r.desc


@needs_mesh
def test_ici_agg_install_recorded():
    d = _decisions(_grouped(TpuSession(dict(_ICI_CONF))))
    assert ("TpuIciShuffleAggExec", True) in d


@needs_mesh
def test_ici_agg_kill_switch_reason_recorded():
    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.agg.enabled"] = False
    # keep the Final<-Exchange<-Partial pattern alive so the rejected mesh
    # stage is observable (the complete-agg collapse would claim it first)
    conf["spark.rapids.tpu.completeAggCollapse.enabled"] = False
    d = _decisions(_grouped(TpuSession(conf)))
    assert d.get(("TpuIciShuffleAggExec", False)) == \
        "spark.rapids.tpu.mesh.agg.enabled is false"


@needs_mesh
def test_ici_join_unsupported_type_reason():
    from spark_rapids_tpu.exec.ici import TpuIciShuffleJoinExec  # noqa: F401

    def build(s, how):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                      ["k", "v"], length=64)
        right = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                       ["k", "w"], length=32, seed=5)
        return left.join(right, on="k", how=how)

    d = _decisions(build(TpuSession(dict(_ICI_CONF)), "inner"))
    assert ("TpuIciShuffleJoinExec", True) in d

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.join.enabled"] = False
    d = _decisions(build(TpuSession(conf), "inner"))
    assert d.get(("TpuIciShuffleJoinExec", False)) == \
        "spark.rapids.tpu.mesh.join.enabled is false"


@needs_mesh
def test_ici_repartition_kill_switch_reason():
    # (the nested-schema guard inside the rewrite is defensive: nested
    # columns already fall back at tag time via the Exchange type sig, so
    # the observable stage reason is the kill switch)
    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                    ["k", "v"], length=64)
        return df.repartition(4, "k")

    d = _decisions(build(TpuSession(dict(_ICI_CONF))))
    assert ("TpuIciRepartitionExec", True) in d

    conf = dict(_ICI_CONF)
    conf["spark.rapids.tpu.mesh.repartition.enabled"] = False
    d = _decisions(build(TpuSession(conf)))
    assert d.get(("TpuIciRepartitionExec", False)) == \
        "spark.rapids.tpu.mesh.repartition.enabled is false"


def test_join_agg_fusion_kill_switch_reason():
    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                      ["k", "v"], length=64)
        right = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                       ["k", "w"], length=16, seed=5)
        return (left.join(right, on="k")
                .group_by("w").agg(sum_("v", "sv")))

    base = {"spark.rapids.sql.enabled": True}
    d = _decisions(build(TpuSession(base)))
    assert ("TpuJoinAggFusedExec", True) in d

    off = dict(base)
    off["spark.rapids.tpu.joinAggFusion.enabled"] = False
    d = _decisions(build(TpuSession(off)))
    assert d.get(("TpuJoinAggFusedExec", False)) == \
        "spark.rapids.tpu.joinAggFusion.enabled is false"


def test_adaptive_reader_recorded():
    def build(s):
        df = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                    ["k", "v"], length=64)
        return df.repartition(4, "k").group_by("k").agg(sum_("v", "s"))

    base = {"spark.rapids.sql.enabled": True,
            "spark.rapids.tpu.singleDeviceShuffleCoalesce.enabled": False}
    d = _decisions(build(TpuSession(base)))
    assert ("TpuAdaptiveShuffleReaderExec", True) in d

    off = dict(base)
    off["spark.sql.adaptive.enabled"] = False
    d = _decisions(build(TpuSession(off)))
    assert d.get(("TpuAdaptiveShuffleReaderExec", False)) == \
        "spark.sql.adaptive.enabled is false"


def test_stage_explain_lines_printed(capsys):
    conf = {"spark.rapids.sql.enabled": True,
            "spark.rapids.sql.explain": "NOT_ON_GPU",
            "spark.rapids.tpu.joinAggFusion.enabled": False}

    def build(s):
        left = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                      ["k", "v"], length=64)
        right = gen_df(s, [IntegerGen(min_val=0, max_val=9), IntegerGen()],
                       ["k", "w"], length=16, seed=5)
        return (left.join(right, on="k")
                .group_by("w").agg(sum_("v", "sv")))

    build(TpuSession(conf))._planned()
    out = capsys.readouterr().out
    assert "!stage! TpuJoinAggFusedExec cannot install because " \
           "spark.rapids.tpu.joinAggFusion.enabled is false" in out
