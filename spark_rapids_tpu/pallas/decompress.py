"""Device snappy decompression — ship compressed bytes, expand at HBM
bandwidth.

Reference analog: "GPU Acceleration of SQL Analytics on Compressed Data"
(arXiv:2506.10092) and cuDF's gpuinflate/snappy device decompressors: the
winning trade on a bandwidth-starved host->device link is to transfer the
SMALLEST representation (the compressed page) and let the accelerator do
the byte movement: the host->device link is an order of magnitude
slower than HBM, so a decoded byte shipped costs several times what a
compressed one does.

TPU adaptation (the same host-parses-structure / device-moves-bytes split
as pallas/decode.py): a snappy stream is a sequence of ops — literal runs
(bytes sit verbatim in the compressed buffer) and copies (back-references
into the output, including overlapping RLE-style copies).  The host walks
the TAG BYTES only (O(#ops) — literal payloads are skipped
arithmetically, never touched) and ships three int32 op arrays alongside
the raw compressed bytes.  The device resolves every output byte's
ULTIMATE literal source with pointer doubling:

    pass 0:  S[p] = comp offset        (p inside a literal op)
             S[p] = p - dist           (p inside a copy op)
    pass k:  S[p] = S[S[p]] where unresolved

Each pass is one vectorized gather over the output; back-reference
chains halve every pass, so ceil(log2(page)) + 1 passes resolve any
stream — including dist-1 RLE chains — with no sequential walk and no
host-side byte movement.  A final gather pulls the bytes from the
compressed buffer.  Stock XLA ops (searchsorted + gathers), one jitted
program per pow2 shape bucket (same rationale as decode._unpack_call).

When compressed bytes + op descriptors would cross the link heavier
than what the decoded-transfer path ships (incompressible pages),
:class:`TooFragmented` routes the caller there instead — bad trades
cost a fallback, never a wrong byte.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_SENTINEL = np.int32(2**31 - 1)


class TooFragmented(Exception):
    """Shipping this page compressed would cross the link heavier than
    the decoded path — the caller ships it decoded (transport cost
    only; correctness is identical either way)."""


def _parse_ops(data: bytes) -> Tuple[int, List[Tuple[int, int, int, int]]]:
    """Structural walk of a raw snappy block: (usize, ops).

    Each op is ``(kind, out_off, length, arg)`` with kind 0 = literal
    (arg = byte offset of the payload inside ``data``) and kind 1 = copy
    (arg = back-reference distance).  O(#ops) host work — literal
    payloads are skipped by length arithmetic, never touched."""
    n = len(data)
    pos = 0
    usize = 0
    shift = 0
    while True:
        if pos >= n:
            raise ValueError("malformed snappy varint")
        b = data[pos]
        pos += 1
        usize |= (b & 0x7F) << shift
        if not b & 0x80:
            break
        shift += 7
    ops: List[List[int]] = []
    out = 0

    def push(kind: int, o: int, length: int, arg: int) -> None:
        # coalesce: snappy splits a long match into 64-byte copies at
        # the SAME distance, and long literals into 60-byte runs with
        # adjacent payloads — merged they keep identical per-byte
        # semantics (out[p] = out[p - d] / comp payload) and the op
        # arrays ship ~100x smaller for structured pages
        if ops:
            k0, o0, l0, a0 = ops[-1]
            if k0 == kind and o0 + l0 == o and (
                    (kind == 1 and a0 == arg)
                    or (kind == 0 and a0 + l0 == arg)):
                ops[-1][2] = l0 + length
                return
        ops.append([kind, o, length, arg])

    while pos < n and out < usize:
        tag = data[pos]
        pos += 1
        t = tag & 3
        if t == 0:
            ln = tag >> 2
            if ln >= 60:
                nb = ln - 59
                if pos + nb > n:
                    raise ValueError("malformed snappy literal length")
                ln = int.from_bytes(data[pos:pos + nb], "little")
                pos += nb
            length = ln + 1
            if pos + length > n:
                raise ValueError("malformed snappy literal")
            push(0, out, length, pos)
            pos += length
        else:
            if t == 1:
                length = ((tag >> 2) & 0x7) + 4
                if pos + 1 > n:
                    raise ValueError("malformed snappy copy")
                dist = ((tag >> 5) << 8) | data[pos]
                pos += 1
            elif t == 2:
                length = (tag >> 2) + 1
                if pos + 2 > n:
                    raise ValueError("malformed snappy copy")
                dist = int.from_bytes(data[pos:pos + 2], "little")
                pos += 2
            else:
                length = (tag >> 2) + 1
                if pos + 4 > n:
                    raise ValueError("malformed snappy copy")
                dist = int.from_bytes(data[pos:pos + 4], "little")
                pos += 4
            if dist <= 0 or dist > out:
                raise ValueError("malformed snappy copy offset")
            push(1, out, length, dist)
        out += length
    if out != usize:
        raise ValueError("snappy length mismatch")
    return usize, [tuple(op) for op in ops]


def _p2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


_GATHER_JITS: Dict[Tuple[int, int, int], object] = {}


def _gather_fn(out_cap: int, comp_cap: int, op_cap: int):
    key = (out_cap, comp_cap, op_cap)
    fn = _GATHER_JITS.get(key)
    if fn is None:
        from spark_rapids_tpu.perfcounters import tpu_jit

        # chains halve each pass: log2(out_cap)+1 passes resolve any
        # back-reference chain the page can hold
        npasses = max(out_cap - 1, 1).bit_length() + 1

        def gather(comp, op_out, op_src, op_lit):
            p = jnp.arange(out_cap, dtype=jnp.int32)
            j = jnp.searchsorted(op_out, p, side="right") - 1
            j = jnp.clip(j, 0, op_cap - 1)
            rel = p - op_out[j]
            # resolved sources encode as -(comp offset) - 1; unresolved
            # stay as an earlier OUTPUT position (the copy's source)
            s = jnp.where(op_lit[j] > 0,
                          -(op_src[j] + rel) - 1,
                          p - op_src[j])
            for _ in range(npasses):
                hop = s[jnp.clip(s, 0, out_cap - 1)]
                s = jnp.where(s >= 0, hop, s)
            src = -s - 1
            return comp[jnp.clip(src, 0, comp_cap - 1)]

        fn = _GATHER_JITS[key] = tpu_jit(gather)
    return fn


def snappy_to_device(data: bytes, decoded_cost: int = 0) -> jax.Array:
    """Raw snappy block -> decompressed (usize,) uint8 DEVICE array.

    Only the compressed bytes + 12 B/op descriptor arrays cross the
    link (``bytes_h2d`` counts them; ``bytes_h2d_logical`` counts the
    decoded size).  ``decoded_cost`` is what the DECODED-transfer path
    would ship for this page (value payload + expanded def levels;
    defaults to the decompressed size): when the compressed
    representation is heavier, :class:`TooFragmented` routes the caller
    there.  Raises ValueError on malformed input."""
    from spark_rapids_tpu import perfcounters as PC

    usize, ops = _parse_ops(data)
    if usize == 0:
        return jnp.zeros(0, jnp.uint8)
    ship = len(data) + 12 * len(ops)
    if ship >= max(decoded_cost, usize):
        raise TooFragmented(
            f"compressed transfer larger than decoded ({ship} vs "
            f"{max(decoded_cost, usize)})")
    n_ops = len(ops)
    op_out = np.fromiter((o[1] for o in ops), np.int32, n_ops)
    op_src = np.fromiter((o[3] for o in ops), np.int32, n_ops)
    op_lit = np.fromiter((1 - o[0] for o in ops), np.int32, n_ops)
    comp_np = np.frombuffer(data, np.uint8)
    PC.count_h2d(comp_np.nbytes + 12 * n_ops, logical=usize)
    PC.bump("pages_device_decompressed")
    # exact-size uploads, device-side pow2 padding: padding bytes must
    # never cross the link (they would defeat the compressed transfer)
    import time as _time

    t0 = _time.perf_counter_ns()
    out_cap, comp_cap, op_cap = _p2(usize), _p2(len(data)), _p2(n_ops)
    comp = jnp.asarray(comp_np)
    o_np = jnp.asarray(op_out)
    s_np = jnp.asarray(op_src)
    lt_np = jnp.asarray(op_lit)
    PC.bump("scan_transfer_ns", _time.perf_counter_ns() - t0)
    comp = jnp.zeros(comp_cap, jnp.uint8).at[:len(data)].set(comp)
    o = jnp.full(op_cap, _SENTINEL, jnp.int32).at[:n_ops].set(o_np)
    s = jnp.zeros(op_cap, jnp.int32).at[:n_ops].set(s_np)
    lt = jnp.ones(op_cap, jnp.int32).at[:n_ops].set(lt_np)
    out = _gather_fn(out_cap, comp_cap, op_cap)(comp, o, s, lt)
    return out[:usize]


def raw_to_device(data: bytes) -> jax.Array:
    """UNCOMPRESSED page region -> (n,) uint8 device array (the identity
    twin of :func:`snappy_to_device`; same accounting contract)."""
    import time as _time

    from spark_rapids_tpu import perfcounters as PC

    buf = np.frombuffer(data, np.uint8)
    PC.count_h2d(buf.nbytes)
    t0 = _time.perf_counter_ns()
    out = jnp.asarray(buf)
    PC.bump("scan_transfer_ns", _time.perf_counter_ns() - t0)
    return out


def decompress_to_host(data: bytes) -> bytes:
    """Host (numpy) reference for the device gather (tests + docs): the
    same op stream executed sequentially."""
    usize, ops = _parse_ops(data)
    out = np.zeros(usize, np.uint8)
    comp = np.frombuffer(data, np.uint8)
    for kind, o, length, arg in ops:
        if kind == 0:
            out[o:o + length] = comp[arg:arg + length]
        elif arg >= length:
            out[o:o + length] = out[o - arg:o - arg + length]
        else:
            reps = -(-length // arg)
            out[o:o + length] = np.tile(out[o - arg:o], reps)[:length]
    return out.tobytes()
