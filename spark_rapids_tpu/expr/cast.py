"""GpuCast analog — Spark-exact cast matrix on TPU.

Reference analog: com/nvidia/spark/rapids/GpuCast.scala + spark-rapids-jni
cast_string.cu / cast_string_to_float.cu / cast_decimal_to_string.cu.  The
reference spent years making casts Spark-exact; this module reproduces the
semantics the differential harness exercises, entirely as fused vector ops:

  * numeric<->numeric: Java narrowing (wraps), double->integral saturates at
    long then narrows (Java (long)d then (int)), NaN -> 0; ANSI raises on
    out-of-range instead.
  * decimal rescale: HALF_UP rounding, overflow -> null (legacy) / error.
  * integral/decimal -> string: digit decomposition on device.
  * string -> integral: vectorized trim+parse, invalid -> null (legacy).
  * string <-> date (yyyy-MM-dd with civil-calendar day math on device, the
    Hinnant algorithm — branch-free integer ops, TPU-friendly).
  * date/timestamp conversions (micros <-> days, floor semantics).
  * string -> timestamp/date: vectorized variable-width civil parsing of
    Spark's stringToTimestamp grammar (see _FieldCursor for the documented
    subset; named timezones fall out as nulls).
  * float<->string run as host kernels (Java shortest-repr formatting,
    Spark's float grammar) — see ``Cast.is_host_kernel``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.expr.base import EvalContext, UnaryExpression

_I_MIN = {T.ByteType: -(2 ** 7), T.ShortType: -(2 ** 15),
          T.IntegerType: -(2 ** 31), T.LongType: -(2 ** 63)}
_I_MAX = {T.ByteType: 2 ** 7 - 1, T.ShortType: 2 ** 15 - 1,
          T.IntegerType: 2 ** 31 - 1, T.LongType: 2 ** 63 - 1}


# ---------------------------------------------------------------------------
# civil-calendar day math (device, vectorized)
# ---------------------------------------------------------------------------

def civil_from_days(days):
    """days since 1970-01-01 -> (year, month, day); Hinnant algorithm."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def days_from_civil(y, m, d):
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


class Cast(UnaryExpression):
    def __init__(self, child, to: T.DataType, ansi: bool = False):
        super().__init__(child)
        self.to = to
        self._dataType = to
        self.ansi_override = ansi

    def sql_string(self):
        return f"CAST({self.child.sql_string()} AS {self.to.simpleString})"

    def _resolve_type(self):
        self._dataType = self.to
        self._nullable = True

    def resolve(self, schema):
        if schema is not None and not self.child.resolved:
            self.children = [self.child.resolve(schema)]
        self._resolve_type()
        self.resolved = True
        return self

    @property
    def is_host_kernel(self):
        """fp<->string casts run as host kernels (Java shortest-repr
        formatting / Spark float parsing), routed through the eager
        Project/Filter stage path like the JSON family."""
        srcdt = self.child._dataType
        if srcdt is None:
            return False
        fp = (T.FloatType, T.DoubleType)
        return ((isinstance(srcdt, fp) and isinstance(self.to, T.StringType))
                or (isinstance(srcdt, T.StringType)
                    and isinstance(self.to, fp)))

    def do_columnar_eval(self, ctx: EvalContext, cols):
        c = cols[0]
        src, dst = self.child.dataType, self.to
        ansi = ctx.ansi or self.ansi_override
        if src == dst:
            return c
        fn = _dispatch(src, dst)
        if fn is None:
            raise TypeError(f"cast {src} -> {dst} not implemented on TPU")
        return fn(ctx, c, src, dst, ansi)


def _dispatch(src: T.DataType, dst: T.DataType):
    def k(t):
        if isinstance(t, T.DecimalType):
            return "dec"
        if isinstance(t, (T.FloatType, T.DoubleType)):
            return "fp"
        if isinstance(t, (T.ByteType, T.ShortType, T.IntegerType, T.LongType)):
            return "int"
        if isinstance(t, T.BooleanType):
            return "bool"
        if isinstance(t, T.StringType):
            return "str"
        if isinstance(t, T.DateType):
            return "date"
        if isinstance(t, T.TimestampType):
            return "ts"
        if isinstance(t, T.NullType):
            return "null"
        return "?"

    return _CASTS.get((k(src), k(dst)))


# -- numeric ---------------------------------------------------------------

def _int_to_int(ctx, c, src, dst, ansi):
    narrowing = (_I_MIN[type(dst)] > _I_MIN[type(src)]
                 or _I_MAX[type(dst)] < _I_MAX[type(src)])
    if ansi and narrowing:
        # narrowing only: a widening cast cannot overflow — and its
        # bound constants may not be representable in the SOURCE dtype
        # (2^63-1 wraps to -1 as an int32 operand, flagging every
        # non-negative row)
        mn, mx = _I_MIN[type(dst)], _I_MAX[type(dst)]
        bad = (c.data < mn) | (c.data > mx)
        ctx.add_error(bad & c.validity, f"cast overflow to {dst} (ANSI)")
    data = c.data.astype(T.storage_dtype(dst))  # wraps, Java semantics
    return DeviceColumn(dst, c.validity, data=data)


def _int_to_fp(ctx, c, src, dst, ansi):
    return DeviceColumn(dst, c.validity,
                        data=c.data.astype(T.storage_dtype(dst)))


def _fp_to_int(ctx, c, src, dst, ansi):
    mn, mx = _I_MIN[type(dst)], _I_MAX[type(dst)]
    x = c.data
    nan = jnp.isnan(x)
    tr = jnp.trunc(x)
    if ansi:
        bad = nan | (tr < mn) | (tr > mx)
        ctx.add_error(bad & c.validity, f"cast overflow to {dst} (ANSI)")
    # Java: (long) saturates, then narrowing wraps.  2^63-1 is not
    # representable as a double (rounds to 2^63, which wraps on convert),
    # so saturate explicitly by comparison.
    lmax_f = 9.223372036854775808e18  # == 2^63 exactly as a double
    safe = jnp.clip(tr, -9.2233720368547748e18, 9.2233720368547748e18)
    as_long = jnp.where(
        nan, 0,
        jnp.where(tr >= lmax_f, jnp.int64(_I_MAX[T.LongType]),
                  jnp.where(tr < -lmax_f, jnp.int64(_I_MIN[T.LongType]),
                            safe.astype(jnp.int64))))
    data = as_long.astype(T.storage_dtype(dst))  # narrowing wraps like Java
    return DeviceColumn(dst, c.validity, data=data)


def _fp_to_fp(ctx, c, src, dst, ansi):
    return DeviceColumn(dst, c.validity,
                        data=c.data.astype(T.storage_dtype(dst)))


def _num_to_bool(ctx, c, src, dst, ansi):
    return DeviceColumn(dst, c.validity, data=c.data != 0)


def _bool_to_num(ctx, c, src, dst, ansi):
    return DeviceColumn(dst, c.validity,
                        data=c.data.astype(T.storage_dtype(dst)))


# -- decimal ---------------------------------------------------------------

def _p10(k):
    return 10 ** int(min(max(k, 0), 18))


def _dec_rescale(ctx, data, validity, from_scale, to: T.DecimalType, ansi, op):
    from spark_rapids_tpu.expr.arithmetic import _decimal_bound_check

    diff = to.scale - from_scale
    if diff >= 0:
        out = data * _p10(diff)
    else:
        den = _p10(-diff)
        q = data // den
        rem = data - q * den
        q = q + jnp.where((rem != 0) & (data < 0), 1, 0)  # trunc toward 0
        rem2 = data - q * den
        round_away = jnp.abs(rem2) * 2 >= den
        out = q + jnp.where(round_away, jnp.sign(data), 0)
    validity = _decimal_bound_check(ctx, out, to, validity, ansi, op)
    return out, validity


def _dec128_rescale(ctx, hi, lo, validity, from_scale, dst: T.DecimalType,
                    ansi, op):
    """(hi, lo) at from_scale -> dst scale/precision; 128-bit limb path."""
    from spark_rapids_tpu.expr import decimal128 as D

    diff = dst.scale - from_scale
    over = jnp.zeros_like(validity)
    if diff >= 0:
        over, hi, lo = D.mul128_pow10(hi, lo, diff)
    else:
        hi, lo = D.div128_pow10_half_up(hi, lo, -diff)
    ok = D.in_bounds(hi, lo, dst.precision) & ~over
    if ansi:
        ctx.add_error(~ok & validity, f"decimal {op} overflow (ANSI)")
    else:
        validity = validity & ok
    return hi, lo, validity


def _dec_to_dec(ctx, c, src: T.DecimalType, dst: T.DecimalType, ansi):
    if not src.is_128 and not dst.is_128:
        data, validity = _dec_rescale(ctx, c.data, c.validity, src.scale, dst,
                                      ansi, "cast")
        return DeviceColumn(dst, validity, data=data)
    from spark_rapids_tpu.expr import decimal128 as D

    hi, lo = D.column_limbs(c)
    hi, lo, validity = _dec128_rescale(ctx, hi, lo, c.validity, src.scale,
                                       dst, ansi, "cast")
    if dst.is_128:
        return DeviceColumn(dst, validity, data=D.pack(hi, lo))
    # narrowing: bound check guarantees |v| < 10^18, so lo IS the value
    return DeviceColumn(dst, validity, data=lo)


def _int_to_dec(ctx, c, src, dst: T.DecimalType, ansi):
    if dst.is_128:
        from spark_rapids_tpu.expr import decimal128 as D

        hi, lo = D.from64(c.data.astype(jnp.int64))
        hi, lo, validity = _dec128_rescale(ctx, hi, lo, c.validity, 0, dst,
                                           ansi, "cast")
        return DeviceColumn(dst, validity, data=D.pack(hi, lo))
    data, validity = _dec_rescale(ctx, c.data.astype(jnp.int64), c.validity, 0,
                                  dst, ansi, "cast")
    return DeviceColumn(dst, validity, data=data)


def _dec_to_int(ctx, c, src: T.DecimalType, dst, ansi):
    if src.is_128:
        from spark_rapids_tpu.expr import decimal128 as D

        hi, lo = D.unpack(c.data)
        qh, ql = D.div128_pow10_trunc(hi, lo, src.scale)
        fits64 = (qh == (ql >> 63))      # pure sign extension
        mn, mx = _I_MIN[type(dst)], _I_MAX[type(dst)]
        bad = ~fits64 | (ql < mn) | (ql > mx)
        if ansi:
            ctx.add_error(bad & c.validity, f"cast overflow to {dst} (ANSI)")
            validity = c.validity
        else:
            validity = c.validity & ~bad
        return DeviceColumn(dst, validity,
                            data=ql.astype(T.storage_dtype(dst)))
    den = _p10(src.scale)
    q = c.data // den
    rem = c.data - q * den
    q = q + jnp.where((rem != 0) & (c.data < 0), 1, 0)
    mn, mx = _I_MIN[type(dst)], _I_MAX[type(dst)]
    bad = (q < mn) | (q > mx)
    if ansi:
        ctx.add_error(bad & c.validity, f"cast overflow to {dst} (ANSI)")
        validity = c.validity
    else:
        validity = c.validity & ~bad
    return DeviceColumn(dst, validity,
                        data=q.astype(T.storage_dtype(dst)))


def _dec_to_fp(ctx, c, src: T.DecimalType, dst, ansi):
    if src.is_128:
        from spark_rapids_tpu.expr import decimal128 as D

        hi, lo = D.unpack(c.data)
        lo_f = lo.astype(jnp.float64)
        lo_u = jnp.where(lo < 0, lo_f + 18446744073709551616.0, lo_f)
        val = hi.astype(jnp.float64) * 18446744073709551616.0 + lo_u
        data = val / (10.0 ** src.scale)
        return DeviceColumn(dst, c.validity,
                            data=data.astype(T.storage_dtype(dst)))
    data = c.data.astype(jnp.float64) / float(_p10(src.scale))
    return DeviceColumn(dst, c.validity,
                        data=data.astype(T.storage_dtype(dst)))


def _fp_to_dec(ctx, c, src, dst: T.DecimalType, ansi):
    from spark_rapids_tpu.expr.arithmetic import _decimal_bound_check

    scaled = c.data.astype(jnp.float64) * float(_p10(dst.scale))
    nan = jnp.isnan(scaled) | jnp.isinf(scaled)
    data = jnp.where(nan, 0.0, jnp.round(scaled)).astype(jnp.int64)
    validity = c.validity & ~nan
    if ansi:
        ctx.add_error(nan & c.validity, "cast NaN/Inf to decimal (ANSI)")
    validity = _decimal_bound_check(ctx, data, dst, validity, ansi, "cast")
    return DeviceColumn(dst, validity, data=data)


# -- to string (device digit decomposition) --------------------------------

_MAX_I64_DIGITS = 19


def _digits_of(absval, ndig_max):
    """(n,) uint64/int64 magnitudes -> (n, ndig_max) digits (at 10^i) plus
    (n,) significant digit count (>=1).  uint64 input handles 2^63
    (|Long.MIN_VALUE|)."""
    work = absval.astype(jnp.uint64)
    pows = jnp.asarray([10 ** i for i in range(ndig_max)], jnp.uint64)
    ds = (work[:, None] // pows[None, :]) % jnp.uint64(10)
    ndig = jnp.sum(work[:, None] >= pows[None, :], axis=1)
    ndig = jnp.maximum(ndig, 1)
    return ds.astype(jnp.int64), ndig.astype(jnp.int64)  # ds[:, i] = digit at 10^i


def _emit_int_string(absval, neg, ndig_max, width):
    """Build (n, width) char matrix + lengths for signed integers."""
    n = absval.shape[0]
    ds, ndig = _digits_of(absval, ndig_max)
    lengths = ndig + neg.astype(jnp.int32)
    # position p in output (0-based): if p==0 and neg: '-'
    # digit index from msd: p - neg ; value digit exponent = ndig-1-(p-neg)
    pos = jnp.arange(width)[None, :]
    digit_idx = ndig[:, None] - 1 - (pos - neg[:, None].astype(jnp.int32))
    in_digits = (digit_idx >= 0) & (digit_idx < ndig_max) & (pos < lengths[:, None])
    safe_idx = jnp.clip(digit_idx, 0, ndig_max - 1)
    dig = jnp.take_along_axis(ds, safe_idx, axis=1)
    chars = jnp.where(in_digits, dig + ord("0"), 0)
    chars = jnp.where((pos == 0) & neg[:, None], ord("-"), chars)
    return chars.astype(jnp.uint8), lengths.astype(jnp.int32)


def _magnitude_u64(x_i64):
    """|x| as uint64 — exact for Long.MIN_VALUE (2^63)."""
    u = x_i64.astype(jnp.int64).view(jnp.uint64)
    return jnp.where(x_i64 < 0, jnp.uint64(0) - u, u)


def _int_to_string(ctx, c, src, dst, ansi):
    width = 20
    neg = c.data < 0
    absval = _magnitude_u64(c.data)
    chars, lengths = _emit_int_string(absval, neg, _MAX_I64_DIGITS, width)
    return DeviceColumn(T.STRING, c.validity, chars=chars, lengths=lengths)


def _bool_to_string(ctx, c, src, dst, ansi):
    width = 5
    t = np.zeros(width, np.uint8)
    t[:4] = np.frombuffer(b"true", np.uint8)
    f = np.frombuffer(b"false", np.uint8)
    chars = jnp.where(c.data[:, None], jnp.asarray(t)[None, :],
                      jnp.asarray(f)[None, :])
    lengths = jnp.where(c.data, 4, 5).astype(jnp.int32)
    return DeviceColumn(T.STRING, c.validity, chars=chars, lengths=lengths)


def _dec_to_string(ctx, c, src: T.DecimalType, dst, ansi):
    """Spark: unscaled/10^s with exactly s fractional digits."""
    s = src.scale
    neg = c.data < 0
    absval = _magnitude_u64(c.data)
    if s == 0:
        return _int_to_string(ctx, c, src, dst, ansi)
    intpart = absval // jnp.uint64(_p10(s))
    frac = absval % jnp.uint64(_p10(s))
    width = _MAX_I64_DIGITS + s + 3
    ds_int, ndig_int = _digits_of(intpart, _MAX_I64_DIGITS)
    ds_frac, _ = _digits_of(frac, s)
    lengths = (ndig_int + 1 + s + neg.astype(jnp.int32)).astype(jnp.int32)
    pos = jnp.arange(width)[None, :]
    negi = neg[:, None].astype(jnp.int32)
    int_idx = ndig_int[:, None] - 1 - (pos - negi)
    in_int = (int_idx >= 0) & (int_idx < _MAX_I64_DIGITS)
    dot_pos = negi + ndig_int[:, None]
    frac_idx = s - 1 - (pos - dot_pos - 1)
    in_frac = (pos > dot_pos) & (frac_idx >= 0) & (frac_idx < s)
    dig_i = jnp.take_along_axis(ds_int, jnp.clip(int_idx, 0, _MAX_I64_DIGITS - 1), axis=1)
    dig_f = jnp.take_along_axis(ds_frac, jnp.clip(frac_idx, 0, max(s - 1, 0)), axis=1)
    chars = jnp.zeros((c.capacity, width), jnp.int64)
    chars = jnp.where(in_int, dig_i + ord("0"), chars)
    chars = jnp.where(pos == dot_pos, ord("."), chars)
    chars = jnp.where(in_frac, dig_f + ord("0"), chars)
    chars = jnp.where((pos == 0) & neg[:, None], ord("-"), chars)
    chars = jnp.where(pos < lengths[:, None], chars, 0)
    return DeviceColumn(T.STRING, c.validity, chars=chars.astype(jnp.uint8),
                        lengths=lengths)


def _date_to_string(ctx, c, src, dst, ansi):
    y, m, d = civil_from_days(c.data)
    width = 10
    neg_year = y < 0
    ya = jnp.abs(y)
    chars = jnp.zeros((c.capacity, width), jnp.int64)
    # yyyy-MM-dd (years padded to 4)
    chars = chars.at[:, 0].set(ord("0") + (ya // 1000) % 10)
    chars = chars.at[:, 1].set(ord("0") + (ya // 100) % 10)
    chars = chars.at[:, 2].set(ord("0") + (ya // 10) % 10)
    chars = chars.at[:, 3].set(ord("0") + ya % 10)
    chars = chars.at[:, 4].set(ord("-"))
    chars = chars.at[:, 5].set(ord("0") + (m // 10) % 10)
    chars = chars.at[:, 6].set(ord("0") + m % 10)
    chars = chars.at[:, 7].set(ord("-"))
    chars = chars.at[:, 8].set(ord("0") + (d // 10) % 10)
    chars = chars.at[:, 9].set(ord("0") + d % 10)
    del neg_year  # years <0 / >9999 rare; differential tests bound the range
    lengths = jnp.full(c.capacity, width, jnp.int32)
    return DeviceColumn(T.STRING, c.validity, chars=chars.astype(jnp.uint8),
                        lengths=lengths)


def _ts_to_string(ctx, c, src, dst, ansi):
    """yyyy-MM-dd HH:mm:ss[.ffffff] in UTC (session-tz support: later round)."""
    us = c.data
    days = jnp.floor_divide(us, 86_400_000_000)
    rem = us - days * 86_400_000_000
    y, m, d = civil_from_days(days)
    hh = rem // 3_600_000_000
    mm = (rem // 60_000_000) % 60
    ss = (rem // 1_000_000) % 60
    frac = rem % 1_000_000
    width = 26
    ch = jnp.zeros((c.capacity, width), jnp.int64)
    ya = jnp.abs(y)

    def put2(ch, i, v):
        ch = ch.at[:, i].set(ord("0") + (v // 10) % 10)
        return ch.at[:, i + 1].set(ord("0") + v % 10)

    ch = ch.at[:, 0].set(ord("0") + (ya // 1000) % 10)
    ch = ch.at[:, 1].set(ord("0") + (ya // 100) % 10)
    ch = ch.at[:, 2].set(ord("0") + (ya // 10) % 10)
    ch = ch.at[:, 3].set(ord("0") + ya % 10)
    ch = ch.at[:, 4].set(ord("-"))
    ch = put2(ch, 5, m)
    ch = ch.at[:, 7].set(ord("-"))
    ch = put2(ch, 8, d)
    ch = ch.at[:, 10].set(ord(" "))
    ch = put2(ch, 11, hh)
    ch = ch.at[:, 13].set(ord(":"))
    ch = put2(ch, 14, mm)
    ch = ch.at[:, 16].set(ord(":"))
    ch = put2(ch, 17, ss)
    # fractional seconds: Spark trims trailing zeros; compute sig digits
    has_frac = frac > 0
    ds, _ = _digits_of(frac, 6)
    # trailing zeros count
    tz = jnp.argmax(jnp.where(ds > 0, 1, 0), axis=1)  # first nonzero from lsd
    ndigits = 6 - jnp.where(has_frac, tz, 6)
    ch = ch.at[:, 19].set(jnp.where(has_frac, ord("."), 0))
    for i in range(6):
        digit = ds[:, 5 - i] + ord("0")
        ch = ch.at[:, 20 + i].set(jnp.where(i < ndigits, digit, 0))
    lengths = jnp.where(has_frac, 20 + ndigits, 19).astype(jnp.int32)
    pos = jnp.arange(width)[None, :]
    ch = jnp.where(pos < lengths[:, None], ch, 0)
    return DeviceColumn(T.STRING, c.validity, chars=ch.astype(jnp.uint8),
                        lengths=lengths)


# -- from string -----------------------------------------------------------

def _parse_trim(c: DeviceColumn):
    """Strip ASCII whitespace both ends: returns (chars, start, end)."""
    pos = jnp.arange(c.width)[None, :]
    is_ws = (c.chars == ord(" ")) | ((c.chars >= 9) & (c.chars <= 13))
    in_str = pos < c.lengths[:, None]
    nonws = in_str & ~is_ws
    any_nonws = jnp.any(nonws, axis=1)
    first = jnp.argmax(nonws, axis=1)
    last = c.width - 1 - jnp.argmax(nonws[:, ::-1], axis=1)
    return any_nonws, first, last


def _string_to_int(ctx, c, src, dst, ansi):
    any_nonws, first, last = _parse_trim(c)
    pos = jnp.arange(c.width)[None, :]
    active = (pos >= first[:, None]) & (pos <= last[:, None])
    ch = jnp.where(active, c.chars, 0)
    sign_pos = first
    rows = jnp.arange(c.capacity)
    sign_char = ch[rows, sign_pos]
    neg = sign_char == ord("-")
    has_sign = neg | (sign_char == ord("+"))
    dig_start = first + has_sign.astype(jnp.int32)
    is_digit = (ch >= ord("0")) & (ch <= ord("9"))
    digit_active = (pos >= dig_start[:, None]) & (pos <= last[:, None])
    all_digits = jnp.all(~digit_active | is_digit, axis=1)
    ndig = last - dig_start + 1
    valid_parse = any_nonws & all_digits & (ndig >= 1) & (ndig <= 19)
    # magnitude = sum digit * 10^(last - pos), in uint64 (10^19-1 fits)
    exp = last[:, None] - pos
    p10 = jnp.where((exp >= 0) & (exp < 19) & digit_active,
                    jnp.asarray([10 ** i for i in range(19)] + [0],
                                jnp.uint64)[jnp.clip(exp, 0, 19)],
                    jnp.uint64(0))
    mag = jnp.sum(jnp.where(digit_active & is_digit,
                            (ch - ord("0")).astype(jnp.uint64) * p10,
                            jnp.uint64(0)), axis=1)
    # fits signed 64? positive <= 2^63-1, negative magnitude <= 2^63
    fits_i64 = jnp.where(neg, mag <= jnp.uint64(2 ** 63),
                         mag <= jnp.uint64(2 ** 63 - 1))
    val = jnp.where(neg, jnp.uint64(0) - mag, mag).view(jnp.int64)
    mn, mx = _I_MIN[type(dst)], _I_MAX[type(dst)]
    in_range = fits_i64 & (val >= mn) & (val <= mx)
    ok = valid_parse & in_range
    if ansi:
        ctx.add_error(~ok & c.validity, f"invalid cast string->{dst} (ANSI)")
        validity = c.validity
    else:
        validity = c.validity & ok
    return DeviceColumn(dst, validity, data=val.astype(T.storage_dtype(dst)))


def _string_to_date(ctx, c, src, dst, ansi):
    """Parse yyyy-MM-dd (also yyyy-M-d per Spark leniency: later round)."""
    ok_len = c.lengths == 10
    ch = c.chars[:, :10] if c.width >= 10 else jnp.pad(
        c.chars, ((0, 0), (0, 10 - c.width)))
    dig = (ch - ord("0")).astype(jnp.int64)
    is_d = (ch >= ord("0")) & (ch <= ord("9"))
    pattern_ok = (is_d[:, 0] & is_d[:, 1] & is_d[:, 2] & is_d[:, 3]
                  & (ch[:, 4] == ord("-")) & is_d[:, 5] & is_d[:, 6]
                  & (ch[:, 7] == ord("-")) & is_d[:, 8] & is_d[:, 9])
    y = dig[:, 0] * 1000 + dig[:, 1] * 100 + dig[:, 2] * 10 + dig[:, 3]
    m = dig[:, 5] * 10 + dig[:, 6]
    d = dig[:, 8] * 10 + dig[:, 9]
    range_ok = (m >= 1) & (m <= 12) & (d >= 1) & (d <= 31)
    days = days_from_civil(y, m, d)
    # round-trip check rejects e.g. Feb 30
    y2, m2, d2 = civil_from_days(days)
    rt_ok = (y2 == y) & (m2 == m) & (d2 == d)
    ok = ok_len & pattern_ok & range_ok & rt_ok
    if ansi:
        ctx.add_error(~ok & c.validity, "invalid cast string->date (ANSI)")
        validity = c.validity
    else:
        validity = c.validity & ok
    return DeviceColumn(T.DATE, validity, data=days.astype(jnp.int32))


def _string_to_bool(ctx, c, src, dst, ansi):
    def match(s):
        b = s.encode()
        w = max(c.width, len(b))
        padded = jnp.pad(c.chars, ((0, 0), (0, w - c.width)))
        tgt = np.zeros(w, np.uint8)
        tgt[: len(b)] = np.frombuffer(b, np.uint8)
        # case-insensitive ASCII
        lower = jnp.where((padded >= 65) & (padded <= 90), padded + 32, padded)
        return (c.lengths == len(b)) & jnp.all(lower == jnp.asarray(tgt), axis=1)

    true_m = match("true") | match("t") | match("yes") | match("y") | match("1")
    false_m = match("false") | match("f") | match("no") | match("n") | match("0")
    ok = true_m | false_m
    if ansi:
        ctx.add_error(~ok & c.validity, "invalid cast string->boolean (ANSI)")
        validity = c.validity
    else:
        validity = c.validity & ok
    return DeviceColumn(T.BOOLEAN, validity, data=true_m)


# -- date/timestamp --------------------------------------------------------

def _date_to_ts(ctx, c, src, dst, ansi):
    return DeviceColumn(T.TIMESTAMP, c.validity,
                        data=c.data.astype(jnp.int64) * 86_400_000_000)


def _ts_to_date(ctx, c, src, dst, ansi):
    days = jnp.floor_divide(c.data, 86_400_000_000)
    return DeviceColumn(T.DATE, c.validity, data=days.astype(jnp.int32))


def _ts_to_long(ctx, c, src, dst, ansi):
    secs = jnp.floor_divide(c.data, 1_000_000)
    return DeviceColumn(dst, c.validity, data=secs.astype(T.storage_dtype(dst)))


def _long_to_ts(ctx, c, src, dst, ansi):
    return DeviceColumn(T.TIMESTAMP, c.validity,
                        data=c.data.astype(jnp.int64) * 1_000_000)


def _null_to_any(ctx, c, src, dst, ansi):
    from spark_rapids_tpu.expr.base import Literal

    return Literal(None, dst).eval_tpu(ctx)


def java_fp_to_string(v: float, is_float: bool) -> str:
    """Java Float/Double.toString: shortest round-trip digits, positional
    for 1e-3 <= |v| < 1e7, else "d.dddEnn".  Shared by the device
    host-kernel cast and the CPU oracle (reference: cast_string.cu /
    format_float.cu, SURVEY.md §2.5 Cast)."""
    import math

    import numpy as np

    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "Infinity" if v > 0 else "-Infinity"
    if v == 0.0:
        return "-0.0" if math.copysign(1.0, v) < 0 else "0.0"
    x = np.float32(v) if is_float else np.float64(v)
    s = np.format_float_scientific(x, unique=True, trim="-")
    mant, _, exps = s.partition("e")
    exp = int(exps)
    neg = mant.startswith("-")
    if neg:
        mant = mant[1:]
    digits = (mant.replace(".", "").rstrip("0")) or "0"
    if -3 <= exp <= 6:
        if exp >= 0:
            ip = digits[: exp + 1].ljust(exp + 1, "0")
            fp = digits[exp + 1:] or "0"
        else:
            ip = "0"
            fp = "0" * (-exp - 1) + digits
        out = f"{ip}.{fp}"
    else:
        out = f"{digits[0]}.{digits[1:] or '0'}E{exp}"
    return ("-" if neg else "") + out


def _fp_to_string(ctx, c, src, dst, ansi):
    """HOST kernel (eager path): Java shortest-repr formatting."""
    from spark_rapids_tpu.columnar.column import HostColumn

    cap = c.capacity
    n = int(ctx.batch.num_rows)
    vals = c.to_host(n).to_pylist()
    is_f = isinstance(src, T.FloatType)
    out = [None if v is None else java_fp_to_string(float(v), is_f)
           for v in vals]
    host = HostColumn.from_pylist(out, T.STRING)
    return DeviceColumn.from_host(host, capacity=cap)


def spark_string_to_double(s: str):
    """Spark's cast(string as double): trimmed Java Double.parseDouble
    grammar (shared by the device host-kernel and the CPU oracle).
    Returns None for Spark-invalid input.  Python-only syntax Java
    rejects — digit underscores and the bare 'inf'/'-inf' spellings —
    is rejected; Java's trailing d/f suffix is accepted."""
    t = s.strip()
    if not t or "_" in t:
        return None
    low = t.lower()
    if low.lstrip("+-") in ("inf",):
        return None              # Java wants 'Infinity'
    if low and low[-1] in "df" and any(ch.isdigit() for ch in low[:-1]) \
            and "x" not in low:
        t = t[:-1]               # Java FP suffix
    try:
        return float(t)
    except ValueError:
        return None


def _string_to_fp(ctx, c, src, dst, ansi):
    """HOST kernel: Spark string->float parse via the shared
    spark_string_to_double grammar; invalid -> null (ANSI: error)."""
    import numpy as np

    from spark_rapids_tpu.columnar.column import HostColumn

    cap = c.capacity
    n = int(ctx.batch.num_rows)
    vals = c.to_host(n).to_pylist()
    out = []
    bad = np.zeros(cap, np.bool_)
    for i, v in enumerate(vals):
        if v is None:
            out.append(None)
            continue
        f = spark_string_to_double(str(v))
        if f is None:
            out.append(None)
            bad[i] = True
        else:
            out.append(f)
    if ansi:
        ctx.add_error(jnp.asarray(bad),
                      "invalid input syntax for type numeric (ANSI)")
    host = HostColumn.from_pylist(out, dst)
    return DeviceColumn.from_host(host, capacity=cap)


_CASTS = {
    ("int", "int"): _int_to_int,
    ("int", "fp"): _int_to_fp,
    ("fp", "int"): _fp_to_int,
    ("fp", "fp"): _fp_to_fp,
    ("int", "bool"): _num_to_bool,
    ("fp", "bool"): _num_to_bool,
    ("bool", "int"): _bool_to_num,
    ("bool", "fp"): _bool_to_num,
    ("dec", "dec"): _dec_to_dec,
    ("int", "dec"): _int_to_dec,
    ("dec", "int"): _dec_to_int,
    ("dec", "fp"): _dec_to_fp,
    ("fp", "dec"): _fp_to_dec,
    ("int", "str"): _int_to_string,
    ("fp", "str"): _fp_to_string,
    ("str", "fp"): _string_to_fp,
    ("bool", "str"): _bool_to_string,
    ("dec", "str"): _dec_to_string,
    ("date", "str"): _date_to_string,
    ("ts", "str"): _ts_to_string,
    ("str", "int"): _string_to_int,
    ("str", "date"): _string_to_date,
    ("str", "bool"): _string_to_bool,
    ("date", "ts"): _date_to_ts,
    ("ts", "date"): _ts_to_date,
    ("ts", "int"): _ts_to_long,
    ("int", "ts"): _long_to_ts,
    ("null", "int"): _null_to_any,
    ("null", "fp"): _null_to_any,
    ("null", "str"): _null_to_any,
    ("null", "bool"): _null_to_any,
    ("null", "dec"): _null_to_any,
    ("null", "date"): _null_to_any,
    ("null", "ts"): _null_to_any,
}


def cast_supported(src: T.DataType, dst: T.DataType) -> bool:
    """Tag-time check used by overrides; mirrors GpuCast.canCast."""
    if src == dst:
        return True
    return _dispatch(src, dst) is not None


# ---------------------------------------------------------------------------
# string -> timestamp / date: variable-width civil parsing (GpuCast analog
# of spark-rapids-jni cast_string.cu's stringToTimestamp kernel)
# ---------------------------------------------------------------------------

_P10_I64 = [10 ** i for i in range(19)]


class _FieldCursor:
    """Vectorized cursor over trimmed char windows: digit-run extraction and
    single-char matches, all as masked vector ops (no per-row loops).

    Grammar supported (documented subset of Spark's stringToTimestamp):
      [y]yyyy[-[m]m[-[d]d[( |T)[h]h[:[m]m[:[s]s[.f{1,9}]]]][tz]]]]
      tz := Z | z | +-h[h] | +-hh:mm | +-h:mm | +-hhmm
    Named zones (e.g. "UTC", "America/New_York") are not recognized and
    parse as invalid (the reference handles them via GpuTimeZoneDB)."""

    def __init__(self, c: DeviceColumn):
        self.c = c
        self.any_nonws, self.first, self.last = _parse_trim(c)
        w = c.width
        self.w = w
        self.pos = jnp.arange(w)[None, :]
        self.rows = jnp.arange(c.capacity)
        active = ((self.pos >= self.first[:, None])
                  & (self.pos <= self.last[:, None])
                  & (self.pos < c.lengths[:, None]))
        self.ch = jnp.where(active, c.chars, 0)
        self.is_digit = (self.ch >= ord("0")) & (self.ch <= ord("9"))

    def char_at(self, p):
        safe = jnp.clip(p, 0, self.w - 1)
        v = self.ch[self.rows, safe]
        return jnp.where((p >= 0) & (p < self.w), v, 0)

    def digit_run_end(self, p):
        """Exclusive end of the digit run starting at p (<= last+1)."""
        nd = ((self.pos >= p[:, None]) & ~self.is_digit
              & (self.pos <= self.last[:, None]))
        has = jnp.any(nd, axis=1)
        idx = jnp.argmax(nd, axis=1).astype(jnp.int32)
        return jnp.where(has, idx, self.last + 1).astype(jnp.int32)

    def parse_int(self, start, end_excl, max_digits):
        """Integer from digits [start, end_excl); caller validates length."""
        exp = end_excl[:, None] - 1 - self.pos
        dig_active = ((self.pos >= start[:, None])
                      & (self.pos < end_excl[:, None]))
        p10 = jnp.asarray(_P10_I64[:max_digits] + [0], jnp.int64)
        mult = p10[jnp.clip(exp, 0, max_digits)]
        contrib = jnp.where(dig_active,
                            (self.ch - ord("0")).astype(jnp.int64) * mult,
                            jnp.int64(0))
        return jnp.sum(contrib, axis=1)


def _parse_civil_string(c: DeviceColumn):
    """Parse the shared date prefix + optional time/tz suffix.

    Returns a dict of fields and per-shape validity flags; consumers pick
    the shapes they accept (date cast ignores everything after the day)."""
    cur = _FieldCursor(c)
    last = cur.last
    ys = cur.first
    ye = cur.digit_run_end(ys)
    ylen = ye - ys
    y = cur.parse_int(ys, ye, 6)
    # year capped at 9999: the collect layer renders python datetimes
    year_ok = cur.any_nonws & (ylen >= 4) & (ylen <= 6) & (y <= 9999)
    only_year = ye > last
    dash1 = cur.char_at(ye) == ord("-")
    ms = ye + 1
    me = cur.digit_run_end(ms)
    mlen = me - ms
    m = cur.parse_int(ms, me, 2)
    month_ok = (mlen >= 1) & (mlen <= 2)
    only_ym = me > last
    dash2 = cur.char_at(me) == ord("-")
    ds = me + 1
    de = cur.digit_run_end(ds)
    dlen = de - ds
    d = cur.parse_int(ds, de, 2)
    day_ok = (dlen >= 1) & (dlen <= 2)
    only_date = de > last
    sepc = cur.char_at(de)
    sep = (sepc == ord(" ")) | (sepc == ord("T"))
    # time fields
    hs = de + 1
    he = cur.digit_run_end(hs)
    hlen = he - hs
    h = cur.parse_int(hs, he, 2)
    hour_ok = (hlen >= 1) & (hlen <= 2)
    colon1 = cur.char_at(he) == ord(":")
    mins = he + 1
    mine = cur.digit_run_end(mins)
    minlen = mine - mins
    mi = cur.parse_int(mins, mine, 2)
    min_ok = (minlen >= 1) & (minlen <= 2)
    colon2 = cur.char_at(mine) == ord(":")
    ss = mine + 1
    se = cur.digit_run_end(ss)
    slen = se - ss
    s = cur.parse_int(ss, se, 2)
    sec_ok = (slen >= 1) & (slen <= 2)
    dot = cur.char_at(se) == ord(".")
    fs = se + 1
    fe = cur.digit_run_end(fs)
    flen = fe - fs
    frac_ok = (flen >= 1) & (flen <= 9)
    frac_raw = cur.parse_int(fs, fe, 9)
    # fraction -> micros (truncating past 6 digits)
    scale_up = jnp.asarray([_P10_I64[i] for i in range(7)], jnp.int64)
    up = scale_up[jnp.clip(6 - flen, 0, 6)]
    down = scale_up[jnp.clip(flen - 6, 0, 6)]
    frac_us = jnp.where(flen <= 6, frac_raw * up, frac_raw // down)
    # time shape: hour [: min [: sec [.frac]]], ending at time_end
    time_end = jnp.where(
        dot & frac_ok, fe,
        jnp.where(colon2 & sec_ok, se,
                  jnp.where(colon1 & min_ok, mine, he)))
    has_min = colon1 & min_ok
    has_sec = has_min & colon2 & sec_ok
    has_frac = has_sec & dot & frac_ok
    mi = jnp.where(has_min, mi, 0)
    s = jnp.where(has_sec, s, 0)
    frac_us = jnp.where(has_frac, frac_us, 0)
    time_shape_ok = hour_ok & (
        (time_end == he)
        | (has_min & (time_end == mine))
        | (has_sec & (time_end == se))
        | (has_frac & (time_end == fe)))
    # tz suffix after the time
    tzp = time_end
    tz_none = tzp > last
    tzc = cur.char_at(tzp)
    tz_z = ((tzc == ord("Z")) | (tzc == ord("z"))) & (tzp == last)
    tz_sign = jnp.where(tzc == ord("+"), 1,
                        jnp.where(tzc == ord("-"), -1, 0)).astype(jnp.int64)
    ths = tzp + 1
    the = cur.digit_run_end(ths)
    thlen = the - ths
    th_raw = cur.parse_int(ths, the, 4)
    # forms: hhmm (4 digits), h/hh (then optional :mm)
    tz_hhmm = thlen == 4
    tzh = jnp.where(tz_hhmm, th_raw // 100, th_raw)
    tcolon = cur.char_at(the) == ord(":")
    tms = the + 1
    tme = cur.digit_run_end(tms)
    tmlen = tme - tms
    tzm_c = cur.parse_int(tms, tme, 2)
    has_tzm = tcolon & (tmlen == 2)
    tzm = jnp.where(tz_hhmm, th_raw % 100,
                    jnp.where(has_tzm, tzm_c, 0))
    tz_num_end = jnp.where(has_tzm & ~tz_hhmm, tme, the)
    tz_num_ok = ((tz_sign != 0)
                 & ((tz_hhmm & ~tcolon)
                    | ((thlen >= 1) & (thlen <= 2)))
                 & (tz_num_end > last))
    tz_off_ok = (tzh <= 18) & (tzm <= 59) \
        & ((tzh * 60 + tzm) <= 18 * 60)
    tz_ok = tz_none | tz_z | (tz_num_ok & tz_off_ok)
    tz_offset_s = jnp.where(tz_none | tz_z, 0,
                            tz_sign * (tzh * 3600 + tzm * 60))
    return dict(
        cur=cur, y=y, m=m, d=d, h=h, mi=mi, s=s, frac_us=frac_us,
        year_ok=year_ok, only_year=only_year,
        dash1=dash1, month_ok=month_ok, only_ym=only_ym, dash2=dash2,
        day_ok=day_ok, only_date=only_date, sep=sep,
        time_shape_ok=time_shape_ok, tz_ok=tz_ok,
        tz_offset_s=tz_offset_s, h_ok=(h <= 23), mi_ok=(mi <= 59),
        s_ok=(s <= 59))


def _string_to_timestamp(ctx, c, src, dst, ansi):
    """Spark stringToTimestamp subset — see _FieldCursor for the grammar."""
    f = _parse_civil_string(c)
    m_eff = jnp.where(f["only_year"], 1, f["m"])
    d_eff = jnp.where(f["only_year"] | f["only_ym"], 1, f["d"])
    days = days_from_civil(f["y"], jnp.maximum(m_eff, 1),
                           jnp.maximum(d_eff, 1))
    y2, m2, d2 = civil_from_days(days)
    civil_ok = ((y2 == f["y"]) & (m2 == jnp.maximum(m_eff, 1))
                & (d2 == jnp.maximum(d_eff, 1)))
    date_part_ok = (
        f["only_year"]
        | (f["dash1"] & f["month_ok"]
           & (f["only_ym"]
              | (f["dash2"] & f["day_ok"]))))
    time_part_ok = (
        f["only_date"] | f["only_year"] | f["only_ym"]
        | (f["sep"] & f["time_shape_ok"] & f["tz_ok"]
           & f["h_ok"] & f["mi_ok"] & f["s_ok"]))
    has_time = ~(f["only_date"] | f["only_year"] | f["only_ym"])
    ok = (f["year_ok"] & date_part_ok & time_part_ok & civil_ok
          & (m_eff >= 1) & (d_eff >= 1))
    h = jnp.where(has_time, f["h"], 0)
    mi = jnp.where(has_time, f["mi"], 0)
    s = jnp.where(has_time, f["s"], 0)
    frac = jnp.where(has_time, f["frac_us"], 0)
    off = jnp.where(has_time, f["tz_offset_s"], 0)
    micros = (days.astype(jnp.int64) * 86_400_000_000
              + h * 3_600_000_000 + mi * 60_000_000 + s * 1_000_000
              + frac - off * 1_000_000)
    if ansi:
        ctx.add_error(~ok & c.validity,
                      "invalid cast string->timestamp (ANSI)")
        validity = c.validity
    else:
        validity = c.validity & ok
    return DeviceColumn(T.TIMESTAMP, validity, data=micros)


def _string_to_date_v2(ctx, c, src, dst, ansi):
    """Spark stringToDate: [y]yyyy[-[m]m[-[d]d]], with anything after the
    day accepted when separated by ' ' or 'T' (Spark ignores the tail)."""
    f = _parse_civil_string(c)
    m_eff = jnp.where(f["only_year"], 1, f["m"])
    d_eff = jnp.where(f["only_year"] | f["only_ym"], 1, f["d"])
    days = days_from_civil(f["y"], jnp.maximum(m_eff, 1),
                           jnp.maximum(d_eff, 1))
    y2, m2, d2 = civil_from_days(days)
    civil_ok = ((y2 == f["y"]) & (m2 == jnp.maximum(m_eff, 1))
                & (d2 == jnp.maximum(d_eff, 1)))
    tail_ok = f["only_date"] | f["sep"]
    ok = (f["year_ok"] & civil_ok & (m_eff >= 1) & (d_eff >= 1)
          & (f["only_year"]
             | (f["dash1"] & f["month_ok"]
                & (f["only_ym"] | (f["dash2"] & f["day_ok"] & tail_ok)))))
    if ansi:
        ctx.add_error(~ok & c.validity, "invalid cast string->date (ANSI)")
        validity = c.validity
    else:
        validity = c.validity & ok
    return DeviceColumn(T.DATE, validity, data=days.astype(jnp.int32))


_CASTS[("str", "ts")] = _string_to_timestamp
_CASTS[("str", "date")] = _string_to_date_v2
