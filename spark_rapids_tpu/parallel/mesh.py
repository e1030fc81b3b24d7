"""Multi-chip execution over a jax.sharding.Mesh — the ICI shuffle backend.

Reference analog (SURVEY.md §2.7, §5.8): the reference's distributed story is
(a) Spark netty shuffle with multithreaded GPU (de)serialization and (b) a
UCX peer-to-peer transport for device-direct transfers over NVLink/RDMA,
with driver-coordinated peer discovery.

TPU-first replacement: there is no peer-to-peer pull — the pod slice IS the
interconnect.  Shuffle mode "ICI" keeps batches device-resident and
repartitions them with a single XLA all-to-all across the mesh; broadcast is
an all-gather; global aggregation merges with psum-style collectives.  The
Spark-task-async vs SPMD-collective impedance mismatch (SURVEY.md §7 hard
part #1) is resolved by epoching: each shuffle exchange is one collective
step over the whole mesh, scheduled when all upstream partitions of the
stage are ready (the exchange is already a full barrier in Spark semantics,
so this loses no generality).

Parallelism mapping (the framework's DP/TP equivalent, SURVEY.md §2.9):
  * rows are data-parallel across the mesh axis ("dp");
  * repartitioning (hash/range) is the collective (all_to_all);
  * broadcast joins replicate the build side (all_gather);
  * within-chip parallelism is XLA's vectorization (VPU/MXU).

Everything here is built with shard_map so the per-device program is the
same single-chip code path operating on local shards.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


# ---------------------------------------------------------------------------
# Collective building blocks
# ---------------------------------------------------------------------------

def _local_hash_partition_ids(key_data, valid, n_parts: int):
    """Spark-compatible murmur3 pmod partition ids for an int64 key column."""
    from spark_rapids_tpu.ops.hashing import _hash_long, _fmix

    h = _hash_long(jnp.uint32(42), key_data.astype(jnp.int64).view(jnp.uint64)
                   if key_data.dtype == jnp.int64
                   else key_data.astype(jnp.int64).astype(jnp.uint64))
    h = jnp.where(valid, h.astype(jnp.int32), 42)
    p = h % jnp.int32(n_parts)
    return jnp.where(p < 0, p + n_parts, p)


def ici_all_to_all(values: jax.Array, validity: jax.Array,
                   target_dev: jax.Array, n_dev: int, axis: str):
    """Device-resident shuffle of one value column inside shard_map.

    Each device owns `cap` rows; row i goes to device target_dev[i].
    Dense quota scheme: each device reserves cap slots per peer.

    ragged_all_to_all: measured-and-deferred.  The
    dense quota moves up to n_dev x the ragged byte volume, BUT its send
    shapes are static — one compiled program regardless of skew — while
    jax.lax.ragged_all_to_all needs per-epoch group sizes on device and,
    on this jax build, lowers through a path that recompiles when the
    offset metadata layout changes, and one extra compile (seconds) costs
    more than many padded epochs.  Revisit with a four-chip measurement
    of ICI bytes against compiles.  Returns (values, validity) of the
    rows received.
    """
    cap = values.shape[0]
    # stable sort rows by target device so each peer's rows are contiguous
    perm = jax.lax.sort(
        (jnp.where(validity, target_dev, n_dev).astype(jnp.int32),
         jnp.arange(cap, dtype=jnp.int32)), num_keys=1, is_stable=True)[-1]
    v_s = values[perm]
    ok_s = validity[perm]
    tgt_s = jnp.where(ok_s, target_dev[perm], n_dev)
    # slot each row into its peer bucket [peer * cap + rank_within_peer]
    is_start = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                tgt_s[1:] != tgt_s[:-1]])
    pos = jnp.arange(cap, dtype=jnp.int32)
    seg_start = jnp.where(is_start, pos, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start)
    rank = pos - seg_start
    slot = tgt_s * cap + rank
    send_vals = jnp.zeros((n_dev * cap,), values.dtype).at[slot].set(
        v_s, mode="drop")
    send_ok = jnp.zeros((n_dev * cap,), jnp.bool_).at[slot].set(
        ok_s & (tgt_s < n_dev), mode="drop")
    send_vals = send_vals.reshape(n_dev, cap)
    send_ok = send_ok.reshape(n_dev, cap)
    recv_vals = jax.lax.all_to_all(send_vals, axis, 0, 0, tiled=False)
    recv_ok = jax.lax.all_to_all(send_ok, axis, 0, 0, tiled=False)
    return recv_vals.reshape(-1), recv_ok.reshape(-1)


def peer_counts(row_valid: jax.Array, target_dev: jax.Array, n_dev: int):
    """(n_dev,) int32: how many valid rows go to each peer — one row of
    the send matrix a counted quota is read from."""
    return jnp.stack([jnp.sum(row_valid & (target_dev == p), dtype=jnp.int32)
                      for p in range(n_dev)])


def off_chip_rows(row_valid: jax.Array, target_dev: jax.Array, axis):
    """(1,) int32: this device's valid rows whose target is another
    device — the rows an exchange moves across chips."""
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    return jnp.sum(row_valid & (target_dev != me),
                   dtype=jnp.int32).reshape(1)


def _slot_plan(validity: jax.Array, target_dev: jax.Array, n_dev: int,
               quota: int):
    """Shared slotting for a multi-column all-to-all: row i goes to slot
    [peer * quota + its rank among the rows for that peer], input order
    kept within a peer.  The rank is one prefix count per peer (no sort).
    A row past its peer's quota, and a row not sent, gets a slot of its
    own past the send buffer, which the scatter drops.  Returns
    (slot, ok_send)."""
    cap = validity.shape[0]
    tgt = jnp.where(validity, target_dev, n_dev).astype(jnp.int32)
    rank = jnp.zeros(cap, jnp.int32)
    for p in range(n_dev):
        hit = tgt == p
        rank = jnp.where(hit, jnp.cumsum(hit.astype(jnp.int32)) - 1, rank)
    ok = (tgt < n_dev) & (rank < quota)
    slot = jnp.where(ok, tgt * quota + rank,
                     n_dev * quota + jnp.arange(cap, dtype=jnp.int32))
    return slot, ok


def _a2a_array(arr: jax.Array, slot, n_dev: int, quota: int, axis: str):
    """Route one array (any trailing shape) through the all-to-all at
    ``quota`` slots per peer, using a precomputed slot plan."""
    send = jnp.zeros((n_dev * quota,) + arr.shape[1:], arr.dtype
                     ).at[slot].set(arr, mode="drop", unique_indices=True)
    send = send.reshape((n_dev, quota) + arr.shape[1:])
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
    return recv.reshape((n_dev * quota,) + arr.shape[1:])


def ici_all_to_all_columns(cols, row_valid: jax.Array,
                           target_dev: jax.Array, n_dev: int, axis: str,
                           quota: Optional[int] = None):
    """Device-resident shuffle of a whole batch (list of DeviceColumn)
    inside shard_map: every array (validity/data/chars/lengths) of every
    column rides the same all-to-all routing plan.

    Returns (received columns, received-row mask).  Each device reserves
    ``quota`` slots per peer, so the received capacity is n_dev * quota.
    Left at None the quota is the input's capacity (a dense layout that
    never drops a row); a caller that has counted what each device sends
    each peer (``peer_counts``) passes at least the largest count."""
    from spark_rapids_tpu.columnar.column import DeviceColumn

    quota = row_valid.shape[0] if quota is None else quota
    slot, ok_send = _slot_plan(row_valid, target_dev, n_dev, quota)
    rok = _a2a_array(ok_send, slot, n_dev, quota, axis)

    def route(arr):
        return _a2a_array(arr, slot, n_dev, quota, axis)

    out = []
    for c in cols:
        validity = route(c.validity)
        if c.is_string:
            out.append(DeviceColumn(c.dtype, validity & rok,
                                    chars=route(c.chars),
                                    lengths=route(c.lengths)))
        elif c.is_array:
            out.append(DeviceColumn(c.dtype, validity & rok,
                                    data=route(c.data),
                                    lengths=route(c.lengths),
                                    elem_valid=route(c.elem_valid)))
        else:
            out.append(DeviceColumn(c.dtype, validity & rok,
                                    data=route(c.data)))
    return out, rok


# ---------------------------------------------------------------------------
# Demonstration steps (used by tests and the driver's dryrun_multichip)
# ---------------------------------------------------------------------------

def distributed_agg_step(mesh: Mesh, axis: str = "dp"):
    """Global (no keys) filtered aggregation: local partial + psum merge.

    The multi-chip TPC-H Q6 shape: scan shards rows across the mesh,
    each chip filters+multiplies+sums its shard, one psum merges."""

    def step(price, discount, quantity, shipdate, valid):
        lo = jnp.int32(8766)   # 1994-01-01 in days
        hi = jnp.int32(9131)   # 1995-01-01
        keep = (valid
                & (shipdate >= lo) & (shipdate < hi)
                & (discount >= 5) & (discount <= 7)
                & (quantity < 24 * 100))
        contrib = jnp.where(keep, price * discount, 0).astype(jnp.int64)
        local = jnp.sum(contrib)
        total = jax.lax.psum(local, axis)
        count = jax.lax.psum(jnp.sum(keep.astype(jnp.int64)), axis)
        return total, count

    return shard_map(step, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
                     out_specs=(P(), P()))


def distributed_shuffle_agg_step(mesh: Mesh, axis: str = "dp"):
    """Grouped aggregation with an ICI all-to-all repartition:
    local partial agg -> hash all-to-all by key -> local final agg.

    This is the full distributed pipeline of the framework: the exchange in
    HashAggregate(partial) -> Exchange(hash) -> HashAggregate(final) runs as
    one collective instead of a disk/netty shuffle."""
    n_dev = mesh.devices.size

    def step(keys, vals, valid):
        cap = keys.shape[0]
        # ---- local partial aggregate (sort-based) ----
        kw = jnp.where(valid, keys, jnp.int64(2**62))
        perm = jax.lax.sort((kw, jnp.arange(cap, dtype=jnp.int32)),
                            num_keys=1, is_stable=True)[-1]
        ks = kw[perm]
        vs = jnp.where(valid, vals, 0)[perm]
        ok = valid[perm]
        change = jnp.concatenate([jnp.ones(1, jnp.bool_), ks[1:] != ks[:-1]])
        seg = jnp.cumsum(change.astype(jnp.int32)) - 1
        seg = jnp.where(ok, seg, cap - 1)
        psum_ = jax.ops.segment_sum(vs, seg, num_segments=cap)
        first = jax.ops.segment_min(
            jnp.where(ok, jnp.arange(cap, dtype=jnp.int32), cap), seg,
            num_segments=cap)
        gkeys = ks[jnp.clip(first, 0, cap - 1)]
        gvalid = first < cap
        # ---- ICI all-to-all repartition by key hash ----
        tgt = _local_hash_partition_ids(gkeys, gvalid, n_dev)
        rk, rok = ici_all_to_all(gkeys, gvalid, tgt, n_dev, axis)
        rv, _ = ici_all_to_all(psum_, gvalid, tgt, n_dev, axis)
        # ---- local final aggregate over received partials ----
        rcap = rk.shape[0]
        rkw = jnp.where(rok, rk, jnp.int64(2**62))
        perm2 = jax.lax.sort((rkw, jnp.arange(rcap, dtype=jnp.int32)),
                             num_keys=1, is_stable=True)[-1]
        ks2 = rkw[perm2]
        vs2 = jnp.where(rok, rv, 0)[perm2]
        ok2 = rok[perm2]
        change2 = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                   ks2[1:] != ks2[:-1]])
        seg2 = jnp.cumsum(change2.astype(jnp.int32)) - 1
        seg2 = jnp.where(ok2, seg2, rcap - 1)
        fsum = jax.ops.segment_sum(vs2, seg2, num_segments=rcap)
        f2 = jax.ops.segment_min(
            jnp.where(ok2, jnp.arange(rcap, dtype=jnp.int32), rcap), seg2,
            num_segments=rcap)
        fkeys = ks2[jnp.clip(f2, 0, rcap - 1)]
        fvalid = (f2 < rcap) & (fkeys < 2**62)
        return fkeys, fsum, fvalid

    return shard_map(step, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis)),
                     out_specs=(P(axis), P(axis), P(axis)))


def broadcast_build_side(mesh: Mesh, axis: str = "dp"):
    """Broadcast-join build replication: all_gather of the local build shard
    (GpuBroadcastExchangeExec on ICI)."""

    def step(build_keys, build_vals):
        bk = jax.lax.all_gather(build_keys, axis, tiled=True)
        bv = jax.lax.all_gather(build_vals, axis, tiled=True)
        return bk, bv

    return shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                     out_specs=(P(None), P(None)), check_vma=False)
