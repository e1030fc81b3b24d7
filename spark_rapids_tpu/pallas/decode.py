"""Pallas Parquet decode kernels — the cuDF decode-kernel analog.

Reference analog: cuDF's parquet device decode (SURVEY.md §2.10 item 9:
"dictionary/RLE/bit-pack decode are TPU-feasible"; §3.4's
``Table.readParquet`` hot path).

Device layout insight: parquet's bit-packed runs repeat every 8 values
(8*bw bits = bw bytes), so reshaping the payload to (groups, bw) makes
every output's byte indices/shifts STATIC — the kernel is pure vector
shifts/ors over 8-wide lanes, no gathers, exactly what the VPU wants.
``unpack_bitpacked`` runs as a Pallas kernel on TPU (interpret mode
elsewhere); run expansion + dictionary gather compose around it with
stock XLA ops.

Supported bit widths: 1..24 (u32 windows never straddle more than 4
bytes); wider dictionary indices fall back to the host decode.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

MAX_BIT_WIDTH = 24
_TILE = 512


def _unpack_body(bytes_ref, out_ref, *, bw: int):
    # bytes arrive pre-widened to u32: Mosaic's u8 lane indexing miscompiles
    # on this platform (observed: silent zero lanes at bw=13)
    b = bytes_ref[...]  # (tile, 128) uint32; cols >= bw are 0
    cols = []
    mask = jnp.uint32((1 << bw) - 1)
    for i in range(8):
        lo_bit = i * bw
        b0 = lo_bit // 8
        sh = lo_bit % 8
        nb = (bw + sh + 7) // 8
        acc = jnp.zeros_like(b[:, 0])
        for k in range(nb):
            if b0 + k < bw:
                # multiply-add, not shift-or: Mosaic miscompiles chained
                # u32 shift-or accumulation here (silent dropped byte at
                # e.g. bw=11/13); byte lanes are disjoint so + == |
                acc = acc + b[:, b0 + k] * jnp.uint32(1 << (8 * k))
        cols.append((acc >> jnp.uint32(sh)) & mask)
    out = jnp.stack(cols, axis=1)
    pad = out_ref.shape[1] - out.shape[1]
    out_ref[...] = jnp.pad(out, ((0, 0), (0, pad)))


def _use_real_pallas() -> bool:
    return jax.default_backend() == "tpu"


_LANES = 128


_UNPACK_JITS: dict = {}


def _build_unpack(tiles: int, bw: int, interpret: bool):
    """The jitted program for one (tiles, bw) bucket: maps a
    (tiles*_TILE, 128) uint32 byte matrix to the same shape with the 8
    unpacked values of each group in columns 0..7.  Trace, lower and call
    it under ``jax.enable_x64(False)``."""
    from jax.experimental import pallas as pl

    from spark_rapids_tpu.perfcounters import tpu_jit

    block = pl.BlockSpec((_TILE, _LANES), lambda i: (i, 0))
    return tpu_jit(pl.pallas_call(
        partial(_unpack_body, bw=bw),
        out_shape=jax.ShapeDtypeStruct((tiles * _TILE, _LANES), jnp.uint32),
        grid=(tiles,),
        in_specs=[block],
        out_specs=block,
        interpret=interpret,
    ))


def _unpack_call(padded: jax.Array, bw: int, groups: int) -> jax.Array:
    tiles = (groups + _TILE - 1) // _TILE
    # pow2 tile ladder: each (tiles, bw) pair is one Pallas compilation;
    # unbucketed page sizes would compile once per page (seconds each)
    p2 = 1
    while p2 < tiles:
        p2 <<= 1
    tiles = p2
    pad_groups = tiles * _TILE
    # Mosaic rejects the i64 grid scalars jax_enable_x64 produces; the
    # kernel itself is pure u8/u32, so trace it in an x64-free scope
    # (jax.enable_x64 is the context manager; the jax.experimental alias
    # is gone in jax 0.9).
    # Blocks pad the byte dimension to the 128-lane register width —
    # narrower last dims hit Mosaic relayout hazards (observed: silent
    # wrong lanes at bw=13).
    with jax.enable_x64(False):
        mat = jnp.zeros((pad_groups, _LANES), jnp.uint32)
        mat = mat.at[:groups, :bw].set(
            padded.reshape(groups, bw).astype(jnp.uint32))
        # one JITTED program per (tiles, bw) bucket: the bare pallas_call
        # re-traced (and in interpret mode re-interpreted) on EVERY run
        # of every page — a multi-run page paid seconds of pure Python
        # re-tracing per scan (ISSUE 6: the scan path is now hot enough
        # to see it)
        fn = _UNPACK_JITS.get((tiles, bw))
        if fn is None:
            fn = _UNPACK_JITS[(tiles, bw)] = _build_unpack(
                tiles, bw, not _use_real_pallas())
        return fn(mat)[:, :8]


def unpack_bitpacked(payload: np.ndarray, bw: int,
                     count: int) -> jax.Array:
    """LSB-first parquet bit-packed payload -> (count,) uint32 on device."""
    if bw == 0:
        return jnp.zeros(count, jnp.uint32)
    groups = (count + 7) // 8
    need = groups * bw
    buf = np.zeros(need, np.uint8)
    buf[:min(len(payload), need)] = payload[:need]
    from spark_rapids_tpu.perfcounters import count_h2d

    count_h2d(buf.nbytes)
    out = _unpack_call(jnp.asarray(buf), bw, groups)
    return out.reshape(-1)[:count]


def unpack_bitpacked_dev(payload: jax.Array, bw: int,
                         count: int) -> jax.Array:
    """Device-resident twin of :func:`unpack_bitpacked`: the payload is
    already in HBM (the compressed-transfer path decompressed it there),
    so no bytes cross the link here."""
    if bw == 0:
        return jnp.zeros(count, jnp.uint32)
    groups = (count + 7) // 8
    need = groups * bw
    n = int(payload.shape[0])
    if n < need:
        payload = jnp.concatenate(
            [payload, jnp.zeros(need - n, jnp.uint8)])
    elif n > need:
        payload = payload[:need]
    out = _unpack_call(payload, bw, groups)
    return out.reshape(-1)[:count]


def expand_runs_host(runs, buf: bytes, total: int,
                     bw: int) -> np.ndarray:
    """Host (numpy) run expansion — for the tiny definition-level streams,
    where a device dispatch per run would dominate (values still decode on
    device)."""
    out = np.zeros(total, np.uint32)
    got = 0
    for r in runs:
        take = min(r.count, total - got)
        if take <= 0:
            break
        if r.is_packed:
            payload = np.frombuffer(buf, np.uint8, count=r.nbytes,
                                    offset=r.byte_off)
            if bw == 0:
                # bw=0 (all-dictionary single-entry stream): zero-width
                # packed values are all index 0 — mirror the device
                # path's uint32 zeros instead of dividing by zero below
                vals = np.zeros(take, np.uint32)
            elif bw == 1:
                vals = np.unpackbits(payload, bitorder="little")[:take]
            else:
                bits = np.unpackbits(payload, bitorder="little")
                usable = (len(bits) // bw) * bw
                vals = (bits[:usable].reshape(-1, bw).astype(np.uint32)
                        * (1 << np.arange(bw, dtype=np.uint32))).sum(
                    axis=1)[:take]
            out[got:got + take] = vals
        else:
            out[got:got + take] = r.value
        got += take
    return out


def expand_runs(runs, buf: bytes, total: int, bw: int) -> jax.Array:
    """RLE/bit-packed hybrid runs -> (total,) uint32 (device).

    Run headers were host-parsed (io/parquet_native.split_hybrid_runs);
    payload bytes expand on device.  ``bw`` is the stream's bit width
    (1 for definition levels, index_bit_width for dictionary indices)."""
    parts: List[jax.Array] = []
    got = 0
    for r in runs:
        take = min(r.count, total - got)
        if take <= 0:
            break
        if r.is_packed:
            payload = np.frombuffer(buf, np.uint8, count=r.nbytes,
                                    offset=r.byte_off)
            parts.append(unpack_bitpacked(payload, bw, take))
        else:
            parts.append(jnp.full(take, np.uint32(r.value), jnp.uint32))
        got += take
    if not parts:
        return jnp.zeros(total, jnp.uint32)
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if out.shape[0] < total:
        out = jnp.concatenate(
            [out, jnp.zeros(total - out.shape[0], jnp.uint32)])
    return out[:total]


def expand_runs_dev(runs, dev_buf: jax.Array, base_off: int, total: int,
                    bw: int) -> jax.Array:
    """Device-resident twin of :func:`expand_runs`: payload bytes live in
    ``dev_buf`` (a device-decompressed page region) at ``base_off`` plus
    each run's host-parsed ``byte_off`` — no link bytes, the expansion
    consumes HBM-resident slices directly."""
    parts: List[jax.Array] = []
    got = 0
    for r in runs:
        take = min(r.count, total - got)
        if take <= 0:
            break
        if r.is_packed:
            lo = base_off + r.byte_off
            parts.append(unpack_bitpacked_dev(
                dev_buf[lo:lo + r.nbytes], bw, take))
        else:
            parts.append(jnp.full(take, np.uint32(r.value), jnp.uint32))
        got += take
    if not parts:
        return jnp.zeros(total, jnp.uint32)
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if out.shape[0] < total:
        out = jnp.concatenate(
            [out, jnp.zeros(total - out.shape[0], jnp.uint32)])
    return out[:total]
