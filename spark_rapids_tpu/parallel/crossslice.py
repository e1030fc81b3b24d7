"""Cross-slice (DCN-analog) hierarchical repartition — two-level mesh.

Reference analog: the reference's shuffle spans executors on different
NODES — UCX within a host, TCP/IB across hosts (SURVEY.md §2.7, §5.8).
The TPU counterpart is a two-level ``jax.sharding.Mesh``:

    Mesh(devices.reshape(n_host, n_ici), ("host", "ici"))

where the inner axis rides ICI (intra-slice links) and the outer axis
models the slice-to-slice fabric (DCN).  XLA lowers a collective over
each axis to that axis's interconnect, so laying the routing out
hierarchically keeps the heavy traffic on ICI and sends each row over
DCN at most once.

Protocol (hierarchical all-to-all, the standard two-phase route):

  phase 1 (ICI):  every row moves WITHIN its slice to the local device
                  index it will occupy at the destination —
                  ``dev = hash(key) %% n_ici``.  All traffic stays on
                  intra-slice links.
  phase 2 (DCN):  an all-to-all over the "host" axis per device column
                  delivers each row to its destination slice —
                  ``host = (hash(key) // n_ici) %% n_host``.  Each row
                  crosses DCN exactly once, and the n_ici device columns
                  exchange independently (the DCN fan-in per link is
                  n_host-1, matching the reference's inter-node shuffle
                  fan).

Single-process containers cannot present multiple slices, so this module
is exercised by the driver dryrun over a virtual n_host x n_ici CPU mesh
(``dryrun_multichip``) — the same code lowers unchanged on real
multi-slice topologies where jax.devices() spans slices.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh2(n_host: int, n_ici: int,
               devices: Optional[list] = None) -> Mesh:
    """Two-level mesh: outer "host" axis (DCN analog) x inner "ici"
    axis (intra-slice)."""
    devs = devices or jax.devices()
    need = n_host * n_ici
    if len(devs) < need:
        raise ValueError(
            f"need {need} devices for a {n_host}x{n_ici} mesh, "
            f"have {len(devs)}")
    return Mesh(np.array(devs[:need]).reshape(n_host, n_ici),
                ("host", "ici"))


def cross_slice_all_to_all_columns(cols, row_valid, pid,
                                   n_host: int, n_ici: int,
                                   host_axis: str = "host",
                                   ici_axis: str = "ici"):
    """Whole-batch hierarchical routing (ISSUE 10): generalizes
    :func:`cross_slice_repartition`'s (keys, values) pair to ANY list of
    ``DeviceColumn`` (flat / string / array layouts — everything
    ``ici_all_to_all_columns`` carries).  Row i moves to global
    partition ``pid[i] in [0, n_host*n_ici)``, living on device
    ``(pid // n_ici, pid %% n_ici)``:

      phase 1 (ICI):  all-to-all over the inner axis to the
                      destination's LOCAL device index, the destination
                      host id riding along as one extra int32 column;
      phase 2 (DCN):  all-to-all over the host axis delivers each row
                      to its destination slice — each row crosses the
                      slice-to-slice fabric exactly once.

    Returns (received columns, received-row mask).  Must run inside a
    shard_map over a 2-level (host x ici) mesh."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.parallel.mesh import ici_all_to_all_columns

    tgt_dev = (pid % n_ici).astype(jnp.int32)
    tgt_host = (pid // n_ici).astype(jnp.int32)
    carry = DeviceColumn(T.INT, row_valid, data=tgt_host)
    r1, ok1 = ici_all_to_all_columns(list(cols) + [carry], row_valid,
                                     tgt_dev, n_ici, ici_axis)
    r2, ok2 = ici_all_to_all_columns(
        list(r1[:-1]), ok1, r1[-1].data.astype(jnp.int32), n_host,
        host_axis)
    return r2, ok2


def cross_slice_repartition(mesh: Mesh):
    """Jittable hierarchical repartition of (keys, values, row_valid):
    returns (keys, values, received-mask) laid out so that partition
    ``p = hash(key) %% (n_host*n_ici)`` lives on device
    ``(p // n_ici, p %% n_ici)`` of the mesh."""
    from spark_rapids_tpu.parallel.mesh import (_local_hash_partition_ids,
                                                ici_all_to_all_columns)
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu import types as T

    n_host, n_ici = (int(mesh.shape["host"]), int(mesh.shape["ici"]))

    def per_device(keys, vals, valid):
        pid = _local_hash_partition_ids(keys, valid, n_host * n_ici)
        tgt_dev = pid % n_ici
        tgt_host = pid // n_ici
        cols = [DeviceColumn(T.LONG, valid, data=keys),
                DeviceColumn(T.LONG, valid, data=vals),
                DeviceColumn(T.LONG, valid,
                             data=tgt_host.astype(jnp.int64))]
        # phase 1: intra-slice (ICI) — move to the destination's local
        # device index, carrying the host id along
        r1, ok1 = ici_all_to_all_columns(cols, valid, tgt_dev, n_ici,
                                         "ici")
        # phase 2: cross-slice (DCN) — per device column, deliver to the
        # destination slice
        r2, ok2 = ici_all_to_all_columns(
            list(r1[:2]), ok1, r1[2].data.astype(jnp.int32), n_host,
            "host")
        return r2[0].data, r2[1].data, ok2

    return shard_map(
        per_device, mesh=mesh,
        in_specs=(P(("host", "ici")), P(("host", "ici")),
                  P(("host", "ici"))),
        out_specs=(P(("host", "ici")), P(("host", "ici")),
                   P(("host", "ici"))),
        check_vma=False)


def dryrun_cross_slice(n_host: int = 2, n_ici: int = 4,
                       rows_per_dev: int = 64) -> dict:
    """Route a random table over the 2-level mesh and verify against the
    host-side reference partitioning.  Returns routing evidence for the
    driver artifact."""
    from spark_rapids_tpu.ops.hashing import spark_partition_ids
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu import types as T

    mesh = make_mesh2(n_host, n_ici)
    n_dev = n_host * n_ici
    n = rows_per_dev * n_dev
    rng = np.random.default_rng(5)
    keys = jnp.asarray(rng.integers(0, 1 << 40, n), jnp.int64)
    vals = jnp.asarray(rng.integers(0, 1 << 30, n), jnp.int64)
    valid = jnp.asarray(rng.random(n) < 0.9)

    spec = NamedSharding(mesh, P(("host", "ici")))
    args = [jax.device_put(x, spec) for x in (keys, vals, valid)]
    rk, rv, rok = jax.jit(cross_slice_repartition(mesh))(*args)
    rk, rv, rok = (np.asarray(rk), np.asarray(rv), np.asarray(rok))

    # host-side reference: partition id of each VALID row
    kcol = DeviceColumn(T.LONG, valid, data=keys)
    pid = np.asarray(jnp.where(
        valid, spark_partition_ids([kcol], n_dev), -1))
    per_dev_cap = rk.shape[0] // n_dev
    got_rows = 0
    for p in range(n_dev):
        sl = slice(p * per_dev_cap, (p + 1) * per_dev_cap)
        got = sorted(zip(rk[sl][rok[sl]].tolist(),
                         rv[sl][rok[sl]].tolist()))
        want_mask = pid == p
        want = sorted(zip(np.asarray(keys)[want_mask].tolist(),
                          np.asarray(vals)[want_mask].tolist()))
        assert got == want, (
            f"cross-slice partition {p}: {len(got)} rows vs "
            f"expected {len(want)}")
        got_rows += len(got)
    assert got_rows == int(np.asarray(valid).sum())
    return {"mesh": f"{n_host}x{n_ici}", "rows_routed": got_rows,
            "protocol": "ICI phase (local device index) then DCN phase "
                        "(host axis all-to-all), one DCN hop per row"}
