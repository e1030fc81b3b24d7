"""Cross-slice (DCN analog) two-level mesh repartition: hierarchical
ICI-then-host routing over a (host x ici)
virtual mesh, verified against host-side partition ids.  See
parallel/crossslice.py for the documented protocol."""
import jax
import pytest

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


@needs_mesh
@pytest.mark.slow  # compiles 2-level SPMD programs — minutes on CPU XLA
@pytest.mark.parametrize("shape", [(2, 4), (4, 2)])
def test_cross_slice_repartition_matches_reference(shape):
    from spark_rapids_tpu.parallel.crossslice import dryrun_cross_slice

    res = dryrun_cross_slice(*shape, rows_per_dev=48)
    assert res["rows_routed"] > 0
    assert "DCN" in res["protocol"]


@needs_mesh
def test_mesh2_axes():
    from spark_rapids_tpu.parallel.crossslice import make_mesh2

    m = make_mesh2(2, 4)
    assert m.shape["host"] == 2 and m.shape["ici"] == 4
