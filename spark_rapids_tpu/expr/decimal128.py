"""128-bit decimal limb arithmetic on TPU.

Reference analog: spark-rapids-jni ``decimal_utils.cu`` (SURVEY.md §2.5
Arithmetic/decimal row) — CUDA kernels for decimal128 multiply/divide and
overflow checks.  TPU-first redesign: a decimal with precision > 18 is a
two-limb value ``(hi, lo)`` where ``hi`` is the signed high 64 bits and
``lo`` holds the unsigned low 64 bits *as an int64 bit pattern*.  All limb
math is ordinary wrapping int64 vector arithmetic, which XLA lowers to fast
32-bit pair ops on TPU (no f64 custom-call penalty, no host round trips).

Column storage: a decimal128 DeviceColumn packs the limbs as ``data`` of
shape ``(capacity, 2)`` with ``data[:, 0] = hi`` and ``data[:, 1] = lo``.
Kernels in this file work on unpacked ``(hi, lo)`` pairs.

Segmented sums use 32-bit limb splitting so up to 2^31 rows accumulate in
int64 without overflow, with an explicit sign-extension limb making the
reconstruction exact past 2^128 (so wraparound cannot silently produce an
in-bounds wrong answer).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# python ints (NOT jnp arrays): module-level jax arrays become closure
# constants hoisted as executable parameters, which breaks jit re-dispatch
# and pins a backend at import time
_M32 = 0xFFFFFFFF
_SIGN64 = -0x8000000000000000   # 1 << 63 bit


def _i64(x) -> jax.Array:
    return jnp.asarray(x, jnp.int64)


# -- basic limb helpers ------------------------------------------------------

def ult(a: jax.Array, b: jax.Array) -> jax.Array:
    """Unsigned < on int64 bit patterns."""
    return (a ^ _SIGN64) < (b ^ _SIGN64)


def from64(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sign-extend an int64 unscaled value to (hi, lo)."""
    x = _i64(x)
    return x >> 63, x


def pack(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """(hi, lo) -> (n, 2) column storage."""
    return jnp.stack([hi, lo], axis=-1)


def unpack(data: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(n, 2) column storage -> (hi, lo)."""
    return data[..., 0], data[..., 1]


def to_py(hi: int, lo: int) -> int:
    """Host-side: limbs -> arbitrary-precision python int."""
    return (int(hi) << 64) | (int(lo) & 0xFFFFFFFFFFFFFFFF)


def limbs_of(v: int) -> Tuple[int, int]:
    """Host-side: python int -> (hi, lo) int64 bit patterns."""
    masked = v & ((1 << 128) - 1)
    lo = masked & 0xFFFFFFFFFFFFFFFF
    hi = (masked >> 64) & 0xFFFFFFFFFFFFFFFF
    if lo >= 1 << 63:
        lo -= 1 << 64
    if hi >= 1 << 63:
        hi -= 1 << 64
    return hi, lo


# -- arithmetic --------------------------------------------------------------

def add128(ah, al, bh, bl) -> Tuple[jax.Array, jax.Array]:
    lo = al + bl                       # wraps mod 2^64
    carry = ult(lo, al).astype(jnp.int64)
    hi = ah + bh + carry
    return hi, lo


def neg128(h, l) -> Tuple[jax.Array, jax.Array]:
    lo = -l
    hi = -h - (l != 0).astype(jnp.int64)
    return hi, lo


def sub128(ah, al, bh, bl) -> Tuple[jax.Array, jax.Array]:
    nh, nl = neg128(bh, bl)
    return add128(ah, al, nh, nl)


def is_neg(h, l) -> jax.Array:
    return h < 0


def abs128(h, l) -> Tuple[jax.Array, jax.Array]:
    nh, nl = neg128(h, l)
    n = is_neg(h, l)
    return jnp.where(n, nh, h), jnp.where(n, nl, l)


def eq128(ah, al, bh, bl) -> jax.Array:
    return (ah == bh) & (al == bl)


def lt128(ah, al, bh, bl) -> jax.Array:
    """Signed 128-bit <."""
    return (ah < bh) | ((ah == bh) & ult(al, bl))


def umulhi64(a, b) -> jax.Array:
    """High 64 bits of the unsigned 64x64 product (int64 bit patterns)."""
    a0 = a & _M32
    a1 = (a >> 32) & _M32
    b0 = b & _M32
    b1 = (b >> 32) & _M32
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = ((p00 >> 32) & _M32) + (p01 & _M32) + (p10 & _M32)
    return (p11 + ((p01 >> 32) & _M32) + ((p10 >> 32) & _M32)
            + ((mid >> 32) & _M32))


def mul64_to_128(a, b) -> Tuple[jax.Array, jax.Array]:
    """Signed 64x64 -> exact signed 128-bit product."""
    a = _i64(a)
    b = _i64(b)
    lo = a * b                         # low 64 bits, signed == unsigned
    uhi = umulhi64(a, b)
    # signed correction: mulhs = umulh - (a<0 ? b : 0) - (b<0 ? a : 0)
    hi = uhi - jnp.where(a < 0, b, 0) - jnp.where(b < 0, a, 0)
    return hi, lo


def umul128_by_u32(h, l, m) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Unsigned 128-bit value times a uint32 scalar -> (carry, hi, lo).

    ``carry`` is the overflow limb (bits 128..159); zero iff the product
    still fits in 128 bits."""
    m = _i64(m)
    l0 = l & _M32
    l1 = (l >> 32) & _M32
    h0 = h & _M32
    h1 = (h >> 32) & _M32
    p0 = l0 * m
    p1 = l1 * m + ((p0 >> 32) & _M32)
    p2 = h0 * m + ((p1 >> 32) & _M32)
    p3 = h1 * m + ((p2 >> 32) & _M32)
    lo = (p0 & _M32) | (p1 << 32)
    hi = (p2 & _M32) | (p3 << 32)
    carry = (p3 >> 32) & _M32
    return carry, hi, lo


_POW10_32 = [10 ** k for k in range(10)]   # fits uint32 up to 10^9


def mul128_pow10(h, l, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Signed 128 x 10^k -> (overflowed, hi, lo); k is a static python int."""
    if k == 0:
        return jnp.zeros_like(h, jnp.bool_), h, l
    neg = is_neg(h, l)
    uh, ul = abs128(h, l)
    over = jnp.zeros_like(h, jnp.bool_)
    kk = k
    while kk > 0:
        step = min(kk, 9)
        carry, uh, ul = umul128_by_u32(uh, ul, _POW10_32[step])
        over = over | (carry != 0)
        kk -= step
    over = over | (uh < 0)             # magnitude crossed into the sign bit
    rh, rl = neg128(uh, ul)
    return over, jnp.where(neg, rh, uh), jnp.where(neg, rl, ul)


def udivmod128_by_u32(h, l, d):
    """Unsigned 128-bit // d -> (qhi, qlo, rem) for 1 <= d <= 2^31-1.

    Long division over four 32-bit limbs; the divisor bound keeps every
    partial remainder in a signed int64.  ``d`` may be a python int or an
    int64 vector (per-element divisors, e.g. group counts for decimal avg)."""
    d64 = jnp.asarray(d, jnp.int64)
    limbs = [(h >> 32) & _M32, h & _M32, (l >> 32) & _M32, l & _M32]
    q = []
    rem = jnp.zeros_like(h)
    for limb in limbs:
        cur = (rem << 32) | limb
        q.append(cur // d64)
        rem = cur - q[-1] * d64
    qhi = (q[0] << 32) | q[1]
    qlo = (q[2] << 32) | q[3]
    return qhi, qlo, rem


def div128_pow10_trunc(h, l, k: int) -> Tuple[jax.Array, jax.Array]:
    """Signed 128 / 10^k truncating toward zero."""
    if k == 0:
        return h, l
    neg = is_neg(h, l)
    uh, ul = abs128(h, l)
    kk = k
    while kk > 0:
        step = min(kk, 9)
        uh, ul, _ = udivmod128_by_u32(uh, ul, _POW10_32[step])
        kk -= step
    rh, rl = neg128(uh, ul)
    return jnp.where(neg, rh, uh), jnp.where(neg, rl, ul)


def div128_pow10_half_up(h, l, k: int) -> Tuple[jax.Array, jax.Array]:
    """Signed 128 / 10^k with HALF_UP rounding (Spark decimal scale change)."""
    if k == 0:
        return h, l
    neg = is_neg(h, l)
    uh0, ul0 = abs128(h, l)
    # truncating quotient: divide by 10^k in <=9-digit chunks (divisor < 2^31)
    uh, ul = uh0, ul0
    kk = k
    while kk > 0:
        step = min(kk, 9)
        uh, ul, _ = udivmod128_by_u32(uh, ul, _POW10_32[step])
        kk -= step
    # exact remainder in 128 bits: rem = |v| - q * 10^k
    _, qph, qpl = mul128_pow10(uh, ul, k)
    rem_h, rem_l = sub128(uh0, ul0, qph, qpl)
    # HALF_UP: round away from zero when rem >= 10^k / 2 (comparing against
    # the halved divisor instead of doubling rem, which would overflow
    # signed 128 bits at k=38)
    bh_, bl_ = limbs_of(10 ** k // 2)
    round_up = ~lt128(rem_h, rem_l, jnp.full_like(h, bh_),
                      jnp.full_like(l, bl_))
    one = round_up.astype(jnp.int64)
    uh, ul = add128(uh, ul, jnp.zeros_like(h), one)
    rh, rl = neg128(uh, ul)
    return jnp.where(neg, rh, uh), jnp.where(neg, rl, ul)


def bound128(precision: int) -> Tuple[int, int]:
    """(hi, lo) limbs of 10^precision (the exclusive overflow bound)."""
    return limbs_of(10 ** precision)


def in_bounds(h, l, precision: int) -> jax.Array:
    """|value| < 10^precision."""
    bh, bl = bound128(precision)
    ah, al = abs128(h, l)
    return lt128(ah, al, jnp.full_like(h, bh), jnp.full_like(l, bl))


# -- sums --------------------------------------------------------------------

def _limbs32(h, l):
    """Two's-complement 128-bit -> five int64 limb vectors (4x32-bit value
    limbs + one 32-bit sign-extension limb)."""
    return (
        l & _M32,
        (l >> 32) & _M32,
        h & _M32,
        (h >> 32) & _M32,
        jnp.where(h < 0, _M32, jnp.int64(0)),
    )


def _recombine(sums) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Limb sums -> (ok, hi, lo).  ``ok`` is False where the true sum does
    not fit in signed 128 bits."""
    s0, s1, s2, s3, s4 = sums
    c0 = s0
    r0 = c0 & _M32
    c1 = s1 + ((c0 >> 32) & _M32)
    r1 = c1 & _M32
    c2 = s2 + ((c1 >> 32) & _M32)
    r2 = c2 & _M32
    c3 = s3 + ((c2 >> 32) & _M32)
    r3 = c3 & _M32
    # extension limbs: rows contribute the same sign mask at every position
    # >= 4, so limb 4 and limb 5 share s4; propagate two of them and require
    # pure sign extension (all-ones or all-zero matching the result sign).
    c4 = s4 + ((c3 >> 32) & _M32)
    r4 = c4 & _M32
    c5 = s4 + ((c4 >> 32) & _M32)
    r5 = c5 & _M32
    lo = r0 | (r1 << 32)
    hi = r2 | (r3 << 32)
    sign_limb = jnp.where(hi < 0, _M32, jnp.int64(0))
    ok = (r4 == sign_limb) & (r5 == sign_limb)
    return ok, hi, lo


def sum128_global(h, l, validity) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Masked global sum -> (ok, any_valid, hi, lo); each a scalar-shaped
    (1,) array.  Exact for up to 2^31 rows."""
    limbs = _limbs32(h, l)
    sums = [jnp.sum(jnp.where(validity, x, 0), keepdims=True) for x in limbs]
    ok, hi, lo = _recombine(sums)
    any_valid = jnp.sum(validity.astype(jnp.int32), keepdims=True) > 0
    return ok, any_valid, hi, lo


def sum128_segments(h, l, validity, seg_ids, num_segments: int):
    """Masked segmented sum -> (ok, any_valid, hi, lo) per segment, or at
    each segment's end row under the end-row form (``ops/segment.py``
    ``SegEnds``: the limbs are non-negative, so their cumsums never
    decrease).  The bounded form is not taken here: a bounded 128-bit
    sum scatters at full width."""
    from spark_rapids_tpu.ops.segment import active_ends

    if num_segments == 1:
        return sum128_global(h, l, validity)
    limbs = _limbs32(h, l)
    ends = active_ends(num_segments)
    if ends is not None:
        ok, hi, lo = _recombine([ends.nonneg_sum(jnp.where(validity, x, 0))
                                 for x in limbs])
        return ok, ends.counts(validity) > 0, hi, lo
    sums = [jax.ops.segment_sum(jnp.where(validity, x, 0), seg_ids,
                                num_segments=num_segments) for x in limbs]
    ok, hi, lo = _recombine(sums)
    any_valid = jax.ops.segment_sum(validity.astype(jnp.int32), seg_ids,
                                    num_segments=num_segments) > 0
    return ok, any_valid, hi, lo


def column_limbs(c) -> Tuple[jax.Array, jax.Array]:
    """Any decimal DeviceColumn -> (hi, lo): unpack two-limb storage or
    sign-extend 64-bit storage."""
    if c.is_dec128:
        return unpack(c.data)
    return from64(c.data)


# -- ordering ---------------------------------------------------------------

def key_words(h, l) -> Tuple[jax.Array, jax.Array]:
    """Sort-key words: (hi signed, lo rebased to signed) — lexicographic
    signed ordering of the pair equals signed 128-bit numeric ordering."""
    return h, l ^ _SIGN64
