"""Spark-strict CSV / JSON-lines parsing.

Reference analog: GpuTextBasedPartitionReader + GpuCSVScan / GpuJsonScan
(SURVEY.md §2.6 CSV/JSON row): the reference reproduces Spark's Univocity/
Jackson parse semantics in cuDF kernels; here the host parse (sanctioned by
SURVEY §2.10 item 10 — "host parse -> device, then incremental Pallas")
reproduces them in one place shared by the device pipeline and the CPU
oracle, with pinned-expectation tests guarding the semantics.

Supported semantics (the PERMISSIVE core):

  * modes: PERMISSIVE (default), DROPMALFORMED, FAILFAST
  * ``columnNameOfCorruptRecord`` (default ``_corrupt_record``): when that
    column appears in the schema, malformed records land there as the raw
    line while successfully-converted fields keep their values (Spark
    PERMISSIVE keeps partial rows)
  * CSV: header/sep/quote options; a record is malformed when its token
    count differs from the schema or any field fails conversion; empty
    tokens (== ``nullValue``, default "") are null
  * CSV field conversion is Spark-strict: integers reject decimals and
    wrap-only values, booleans are true/false (case-insensitive), date/
    timestamp use the cast grammar (expr/cast.py twin _str_to_date_py /
    _str_to_ts_py), decimals HALF_UP-quantize and range-check
  * JSON lines: a record is malformed when the line is not a JSON object;
    missing fields are null; a present field of the wrong JSON type is
    null (numbers render into string fields like Spark's literal-text
    coercion); nested values into scalar fields are null
"""
from __future__ import annotations

import json as _json
import math
from decimal import ROUND_HALF_UP, Decimal, InvalidOperation
from typing import List, Optional

from spark_rapids_tpu import types as T

DEFAULT_CORRUPT_COL = "_corrupt_record"

_I_RANGE = {T.ByteType: (-2**7, 2**7 - 1), T.ShortType: (-2**15, 2**15 - 1),
            T.IntegerType: (-2**31, 2**31 - 1),
            T.LongType: (-2**63, 2**63 - 1)}


class _FieldError(Exception):
    pass


def _convert_csv_field(tok: Optional[str], dt: T.DataType,
                       null_value: str):
    """One CSV token -> python storage value (or None); raises _FieldError
    on a Spark-invalid token."""
    if tok is None or tok == null_value:
        return None
    if isinstance(dt, T.StringType):
        return tok
    if isinstance(dt, T.BooleanType):
        low = tok.strip().lower()
        if low == "true":
            return True
        if low == "false":
            return False
        raise _FieldError(tok)
    s = tok.strip()
    if not s:
        return None
    if dt.is_integral:
        body = s[1:] if s[:1] in "+-" else s
        if not body.isdigit():
            raise _FieldError(tok)
        v = int(s)
        lo, hi = _I_RANGE[type(dt)]
        if not lo <= v <= hi:
            raise _FieldError(tok)
        return v
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        try:
            return float(s)
        except ValueError:
            raise _FieldError(tok)
    if isinstance(dt, T.DecimalType):
        try:
            d = Decimal(s)
            # inf/nan parse as Decimal but quantize raises — malformed
            scaled = int(d.scaleb(dt.scale).quantize(
                Decimal(1), rounding=ROUND_HALF_UP))
        except InvalidOperation:
            raise _FieldError(tok)
        if abs(scaled) >= 10 ** dt.precision:
            raise _FieldError(tok)
        return scaled
    if isinstance(dt, T.DateType):
        from spark_rapids_tpu.cpu.oracle import _str_to_date_py

        days = _str_to_date_py(s)
        if days is None:
            raise _FieldError(tok)
        return days
    if isinstance(dt, T.TimestampType):
        from spark_rapids_tpu.cpu.oracle import _str_to_ts_py

        micros = _str_to_ts_py(s)
        if micros is None:
            raise _FieldError(tok)
        return micros
    raise _FieldError(f"unsupported CSV type {dt.simpleString}")


def _finish(rows, schema: T.StructType):
    """rows: list of per-field python value lists -> HostColumns.

    Decimal fields hold SCALED int64 values here (the converters return
    unscaled-integer representation); from_pylist expects true numeric
    values and rescales, so wrap them back into exact Decimals first —
    round-4 differential fuzzing caught the double-scaling."""
    from spark_rapids_tpu.columnar.column import HostColumn

    cols = []
    for i, f in enumerate(schema.fields):
        vals = [r[i] for r in rows]
        if isinstance(f.dataType, T.DecimalType):
            vals = [None if v is None
                    else Decimal(v).scaleb(-f.dataType.scale)
                    for v in vals]
        cols.append(HostColumn.from_pylist(vals, f.dataType))
    return cols, len(rows)


def _classify_tokens(toks_u, dt: T.DataType, null_value: str):
    """Vectorized Spark-strict classification of one CSV column's tokens.

    Returns (values, validity, uncertain): rows where ``uncertain`` is
    True could not be decided by a vectorized rule (exotic grammar,
    unicode digits, rounding decimals, timestamps...) and must re-run
    through the strict per-row loop — a row the vectorizer does claim
    always agrees with ``_convert_csv_field``.
    """
    import numpy as np

    n = len(toks_u)
    is_null = toks_u == null_value
    uncertain = np.zeros(n, np.bool_)
    if isinstance(dt, T.StringType):
        return toks_u, ~is_null, uncertain
    s = np.char.strip(toks_u)
    if isinstance(dt, T.BooleanType):
        low = np.char.lower(s)
        vals = low == "true"
        known = is_null | vals | (low == "false")
        return vals, ~is_null & known, ~known
    empty = s == ""
    is_null = is_null | empty
    first = s.astype("U1")
    signed = (first == "+") | (first == "-")
    body = np.where(signed, np.char.lstrip(s, "+-"), s)
    slen = np.char.str_len(s)
    blen = np.char.str_len(body)
    clean_sign = slen - blen <= 1     # exactly one sign char was stripped
    _DIGITS = str.maketrans("", "", "0123456789")
    ascii_digits = (np.char.translate(body, _DIGITS) == "") & (body != "")
    if dt.is_integral:
        lo, hi = _I_RANGE[type(dt)]
        cand = ~is_null & ascii_digits & (blen <= 18) & clean_sign
        vals = np.zeros(n, np.int64)
        if cand.any():
            vals[cand] = s[cand].astype(np.int64)
        in_range = (vals >= lo) & (vals <= hi)
        uncertain = ~is_null & ~(cand & in_range)
        return vals, cand & in_range, uncertain
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        _FCHARS = str.maketrans("", "", "0123456789+-.eE")
        cand = ~is_null & (np.char.translate(s, _FCHARS) == "")
        vals = np.zeros(n, np.float64)
        if cand.any():
            try:
                vals[cand] = s[cand].astype(np.float64)
            except ValueError:
                return vals, np.zeros(n, np.bool_), ~is_null
        return vals, cand, ~is_null & ~cand
    if isinstance(dt, T.DecimalType):
        # exact-scale fast case: [sign]digits[.digits] with frac digits
        # <= scale (no HALF_UP rounding) and no int64 overflow possible
        parts = np.char.partition(body, ".")
        intpart, dot, frac = parts[:, 0], parts[:, 1], parts[:, 2]
        digits_only = ((np.char.translate(intpart, _DIGITS) == "")
                       & (np.char.translate(frac, _DIGITS) == ""))
        flen = np.char.str_len(frac)
        ilen = np.char.str_len(intpart)
        cand = (~is_null & digits_only & clean_sign & (ilen + flen > 0)
                & (flen <= dt.scale) & (ilen + dt.scale <= 18)
                & ~((dot == ".") & (flen == 0) & (ilen == 0)))
        vals = np.zeros(n, np.int64)
        if cand.any():
            mant_s = np.char.add(np.where(ilen == 0, "0", intpart), frac)
            mant = np.zeros(n, np.int64)
            mant[cand] = mant_s[cand].astype(np.int64)
            exp = np.minimum(dt.scale - flen, 18)
            scale_up = np.power(10, np.maximum(exp, 0)).astype(np.int64)
            vals = mant * scale_up
            vals = np.where(first == "-", -vals, vals)
        in_range = np.abs(vals) < 10 ** dt.precision
        ok = cand & in_range
        return vals, ok, ~is_null & ~ok
    if isinstance(dt, T.DateType):
        vals = np.zeros(n, np.int64)
        ok = np.zeros(n, np.bool_)
        cand = ~is_null & (slen == 10)
        if cand.any():
            c = np.ascontiguousarray(s[cand].astype("U10"))
            ch = c.view(np.uint32).reshape(-1, 10)
            d0 = ord("0")
            dig = (ch >= d0) & (ch <= d0 + 9)
            shape_ok = (dig[:, [0, 1, 2, 3, 5, 6, 8, 9]].all(axis=1)
                        & (ch[:, 4] == ord("-")) & (ch[:, 7] == ord("-")))
            y = ((ch[:, 0] - d0) * 1000 + (ch[:, 1] - d0) * 100
                 + (ch[:, 2] - d0) * 10 + (ch[:, 3] - d0)).astype(np.int64)
            m = ((ch[:, 5] - d0) * 10 + (ch[:, 6] - d0)).astype(np.int64)
            d = ((ch[:, 8] - d0) * 10 + (ch[:, 9] - d0)).astype(np.int64)
            leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
            dim = np.array([0, 31, 28, 31, 30, 31, 30, 31, 31, 30, 31,
                            30, 31], np.int64)[np.clip(m, 0, 12)]
            dim = np.where((m == 2) & leap, 29, dim)
            valid_ymd = shape_ok & (y >= 1) & (m >= 1) & (m <= 12) \
                & (d >= 1) & (d <= dim)
            # days_from_civil (proleptic Gregorian, epoch 1970-01-01)
            yy = y - (m <= 2)
            era = np.floor_divide(yy, 400)
            yoe = yy - era * 400
            doy = (153 * (m + np.where(m > 2, -3, 9)) + 2) // 5 + d - 1
            doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
            days = era * 146097 + doe - 719468
            idx = np.flatnonzero(cand)
            vals[idx[valid_ymd]] = days[valid_ymd]
            ok[idx[valid_ymd]] = True
        return vals, ok, ~is_null & ~ok
    # timestamps and anything else: strict loop decides
    return np.zeros(n, np.int64), np.zeros(n, np.bool_), ~is_null


def _read_csv_fast(path: str, schema: T.StructType, options: dict):
    """Vectorized CSV fast path: pyarrow tokenizes
    (quote-aware splitting at C speed), numpy bulk-converts each column
    with Spark-strict semantics, and every row a vectorized rule cannot
    decide re-runs through the strict loop — so results are identical to
    the per-row reference parse below.  Returns None when preconditions
    fail (ragged rows, parse errors, exotic options); the caller then
    uses the strict loop for the whole file."""
    import numpy as np

    try:
        import pyarrow as pa
        import pyarrow.csv as pacsv
    except ImportError:
        return None
    mode = str(options.get("mode", "PERMISSIVE")).upper()
    header = str(options.get("header", "false")).lower() == "true"
    sep = str(options.get("sep", options.get("delimiter", ",")))
    quote = str(options.get("quote", '"')) or '"'
    null_value = str(options.get("nullValue", ""))
    corrupt_col = str(options.get("columnNameOfCorruptRecord",
                                  DEFAULT_CORRUPT_COL))
    if len(sep) != 1 or len(quote) != 1:
        return None
    fields = schema.fields
    data_idx = [i for i, f in enumerate(fields) if f.name != corrupt_col]
    corrupt_idx = next((i for i, f in enumerate(fields)
                        if f.name == corrupt_col), None)
    names = [f"c{j}" for j in range(len(data_idx))]

    def _arrow_type(dt):
        """The arrow type whose CSV parse agrees with Spark wherever it
        SUCCEEDS (probe-verified: every divergence raises ArrowInvalid,
        falling back a tier — it never silently differs).  Booleans are
        excluded (arrow accepts 1/0/True), timestamps too (session-tz
        grammar); both classify from strings instead."""
        if isinstance(dt, T.StringType):
            return pa.string()
        if dt.is_integral:
            return {T.ByteType: pa.int8(), T.ShortType: pa.int16(),
                    T.IntegerType: pa.int32(),
                    T.LongType: pa.int64()}[type(dt)]
        if isinstance(dt, (T.FloatType, T.DoubleType)):
            # FloatType parses as f64 then downcasts — the strict loop's
            # python float() + f32 storage double-rounds identically
            return pa.float64()
        if isinstance(dt, T.DateType):
            return pa.date32()
        if isinstance(dt, T.DecimalType) and not dt.is_128:
            return pa.decimal128(dt.precision, dt.scale)
        return None

    def _read(types_map):
        return pacsv.read_csv(
            path,
            read_options=pacsv.ReadOptions(
                column_names=names, skip_rows=1 if header else 0,
                use_threads=False),
            parse_options=pacsv.ParseOptions(
                delimiter=sep, quote_char=quote),
            convert_options=pacsv.ConvertOptions(
                column_types=types_map,
                null_values=[null_value],
                strings_can_be_null=True))

    typed_map = {}
    typed_cols = set()
    for j, fi in enumerate(data_idx):
        at = _arrow_type(fields[fi].dataType)
        if at is not None:
            typed_map[names[j]] = at
            typed_cols.add(fi)
        else:
            typed_map[names[j]] = pa.string()
    tbl = None
    try:
        tbl = _read(typed_map)
    except (pa.ArrowInvalid, pa.ArrowKeyError, OSError):
        typed_cols = set()
        try:
            # tier 2: tokenize only; numpy classifies, python decides
            # leftovers.  NOTE null_values=[] here — the classifiers see
            # the raw tokens
            tbl = pacsv.read_csv(
                path,
                read_options=pacsv.ReadOptions(
                    column_names=names, skip_rows=1 if header else 0,
                    use_threads=False),
                parse_options=pacsv.ParseOptions(
                    delimiter=sep, quote_char=quote),
                convert_options=pacsv.ConvertOptions(
                    column_types={nm: pa.string() for nm in names},
                    null_values=[], strings_can_be_null=False))
        except (pa.ArrowInvalid, pa.ArrowKeyError, OSError):
            return None  # ragged rows etc: the strict loop owns them
    n = tbl.num_rows
    if n == 0:
        return _finish([], schema)
    from spark_rapids_tpu.columnar.column import HostColumn

    out_vals = {}
    out_valid = {}
    arrow_cols = {}
    uncertain = np.zeros(n, np.bool_)
    for j, fi in enumerate(data_idx):
        col = tbl.column(names[j]).combine_chunks()
        dt = fields[fi].dataType
        if fi in typed_cols:
            if isinstance(dt, T.FloatType):
                validity = np.asarray(col.is_valid())
                vals = np.asarray(col.fill_null(0.0), np.float64).astype(
                    np.float32)
                arrow_cols[fi] = HostColumn(dt, validity, data=vals)
            else:
                hc = HostColumn.from_arrow(col, dt)
                if isinstance(dt, T.DateType) and len(hc.data):
                    lo_days, hi_days = -719162, 2932896  # 0001..9999
                    d_ = hc.data[hc.validity]
                    if len(d_) and (int(d_.min()) < lo_days
                                    or int(d_.max()) > hi_days):
                        return None  # strict loop owns out-of-grammar years
                arrow_cols[fi] = hc
            continue
        # tier-1 reads classify-columns as arrow string with null_values
        # matching; restore the raw token (exactly null_value) so the
        # classifier sees what the strict loop would
        toks_u = np.asarray(col.fill_null(null_value).to_numpy(
            zero_copy_only=False), dtype="U")
        vals, valid, unc = _classify_tokens(toks_u, dt, null_value)
        out_vals[fi] = (vals, toks_u)
        out_valid[fi] = valid
        uncertain |= unc
    malformed = np.zeros(n, np.bool_)
    fb_rows = np.flatnonzero(uncertain)
    fb_out = {}
    if len(fb_rows):
        for r in fb_rows:
            # typed columns already parsed whole-column clean; only the
            # string-classified columns can be uncertain
            row_out = [None] * len(fields)
            bad = False
            for j, fi in enumerate(data_idx):
                if fi not in out_vals:
                    continue
                tok = str(out_vals[fi][1][r])
                try:
                    row_out[fi] = _convert_csv_field(
                        tok, fields[fi].dataType, null_value)
                except _FieldError:
                    bad = True
            fb_out[int(r)] = row_out
            malformed[r] = bad
    raw_lines = None
    if malformed.any() and (mode == "FAILFAST" or mode == "PERMISSIVE"
                            and corrupt_idx is not None):
        with open(path, "rb") as fh:
            data = fh.read()
        if quote.encode() in data:
            return None  # raw-record mapping unsafe with quoting: strict
        lines = [ln.rstrip(b"\r").decode("utf-8", "replace")
                 for ln in data.split(b"\n")]
        lines = [ln for ln in lines[(1 if header else 0):] if ln != ""]
        if len(lines) != n:
            return None
        raw_lines = lines
        if mode == "FAILFAST":
            r = int(np.flatnonzero(malformed)[0])
            raise RuntimeError(
                f"Malformed CSV record (FAILFAST): {raw_lines[r]!r}")
    keep = ~malformed if mode == "DROPMALFORMED" else np.ones(n, np.bool_)
    from spark_rapids_tpu.columnar.column import HostColumn

    cols = []
    for fi, f in enumerate(fields):
        if fi == corrupt_idx:
            vals = [None] * n
            if raw_lines is not None:
                for r in np.flatnonzero(malformed):
                    vals[int(r)] = raw_lines[int(r)]
            cols.append(HostColumn.from_pylist(
                [v for v, k in zip(vals, keep) if k], f.dataType))
            continue
        dt = f.dataType
        if fi in arrow_cols:
            hc = arrow_cols[fi]
            if bool(keep.all()):
                cols.append(hc)
            elif hc.chars is not None:
                cols.append(HostColumn(dt, hc.validity[keep],
                                       chars=hc.chars[keep],
                                       lengths=hc.lengths[keep]))
            else:
                cols.append(HostColumn(dt, hc.validity[keep],
                                       data=hc.data[keep]))
            continue
        vals, toks_u = out_vals[fi]
        valid = out_valid[fi]
        if isinstance(dt, T.StringType):
            py = [str(t) if v else None for t, v in zip(toks_u, valid)]
            for r, row_out in fb_out.items():
                py[r] = row_out[fi]
            cols.append(HostColumn.from_pylist(
                [v for v, k in zip(py, keep) if k], dt))
            continue
        sd = T.storage_dtype(dt)
        arr = vals.astype(sd)
        validity = valid.copy()
        for r, row_out in fb_out.items():
            v = row_out[fi]
            if v is None:
                validity[r] = False
            else:
                arr[r] = np.asarray(v).astype(sd)
                validity[r] = True
        cols.append(HostColumn.from_numpy(arr[keep], dt, validity[keep]))
    return cols, int(keep.sum())


def read_csv_spark(path: str, schema: T.StructType, options: dict):
    """Spark-semantic CSV read -> (HostColumns, row count).  Escaping
    errors are annotated with ``file=<path>`` (io/faults.py) — FAILFAST
    parse errors keep their type (PROPAGATE semantics), unreadable bytes
    classify as corrupt at the scan layer."""
    from spark_rapids_tpu.io.faults import file_context

    with file_context(path, "csv", "host"):
        return _read_csv_spark(path, schema, options)


def _read_csv_spark(path: str, schema: T.StructType, options: dict):
    import csv as _csv

    if str(options.get("tpuFastParse", "true")).lower() != "false":
        try:
            fast = _read_csv_fast(path, schema, options)
        except RuntimeError:
            raise       # FAILFAST surfaced by the fast path
        except Exception as e:
            from spark_rapids_tpu.resilience import classify as _CL

            if _CL.classify_failure(e) == _CL.PROPAGATE:
                # QueryCancelled / deadline / ANSI errors are the
                # query's correct observable behavior — retrying the
                # strict loop would swallow a cancellation (ISSUE 9)
                raise
            fast = None  # any fast-path surprise: the strict loop decides
        if fast is not None:
            return fast

    mode = str(options.get("mode", "PERMISSIVE")).upper()
    header = str(options.get("header", "false")).lower() == "true"
    sep = str(options.get("sep", options.get("delimiter", ",")))
    quote = str(options.get("quote", '"')) or '"'
    null_value = str(options.get("nullValue", ""))
    corrupt_col = str(options.get("columnNameOfCorruptRecord",
                                  DEFAULT_CORRUPT_COL))
    fields = schema.fields
    data_idx = [i for i, f in enumerate(fields) if f.name != corrupt_col]
    corrupt_idx = next((i for i, f in enumerate(fields)
                        if f.name == corrupt_col), None)
    rows = []

    class _RawTee:
        """Line iterator that records what csv.reader consumed, so the
        corrupt column stores the RAW record (quoting intact), not a
        re-join of the parsed tokens."""

        def __init__(self, fh):
            self.fh = fh
            self.buf = []

        def __iter__(self):
            return self

        def __next__(self):
            line = next(self.fh)
            self.buf.append(line)
            return line

        def take_raw(self):
            raw = "".join(self.buf).rstrip("\r\n")
            self.buf = []
            return raw

    with open(path, "r", encoding="utf-8", newline="") as fh:
        tee = _RawTee(fh)
        reader = _csv.reader(tee, delimiter=sep, quotechar=quote)
        for li, toks in enumerate(reader):
            raw = tee.take_raw()
            if header and li == 0:
                continue
            if not toks:
                continue  # Spark drops blank lines
            out = [None] * len(fields)
            bad = len(toks) != len(data_idx)
            for j, fi in enumerate(data_idx):
                tok = toks[j] if j < len(toks) else None
                try:
                    out[fi] = _convert_csv_field(
                        tok, fields[fi].dataType, null_value)
                except _FieldError:
                    bad = True
            if bad:
                if mode == "FAILFAST":
                    raise RuntimeError(
                        f"Malformed CSV record (FAILFAST): {raw!r}")
                if mode == "DROPMALFORMED":
                    continue
                if corrupt_idx is not None:
                    out[corrupt_idx] = raw
            rows.append(out)
    return _finish(rows, schema)


def _convert_json_value(v, dt: T.DataType):
    """One parsed JSON value -> python storage value (None on mismatch)."""
    if v is None:
        return None
    if isinstance(dt, T.StringType):
        if isinstance(v, str):
            return v
        if isinstance(v, bool):
            return "true" if v else "false"
        if isinstance(v, (int, float)):
            # Spark keeps the literal number text; json round-trip is the
            # closest faithful rendering here
            return _json.dumps(v)
        return None
    if isinstance(dt, T.BooleanType):
        return v if isinstance(v, bool) else None
    if dt.is_integral:
        if isinstance(v, bool) or not isinstance(v, int):
            return None
        lo, hi = _I_RANGE[type(dt)]
        return v if lo <= v <= hi else None
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        return float(v)
    if isinstance(dt, T.DecimalType):
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        if isinstance(v, float) and not math.isfinite(v):
            return None
        try:
            scaled = int(Decimal(str(v)).scaleb(dt.scale).quantize(
                Decimal(1), rounding=ROUND_HALF_UP))
        except InvalidOperation:
            return None
        return scaled if abs(scaled) < 10 ** dt.precision else None
    if isinstance(dt, T.DateType):
        from spark_rapids_tpu.cpu.oracle import _str_to_date_py

        return _str_to_date_py(v) if isinstance(v, str) else None
    if isinstance(dt, T.TimestampType):
        from spark_rapids_tpu.cpu.oracle import _str_to_ts_py

        return _str_to_ts_py(v) if isinstance(v, str) else None
    return None


def _read_json_fast(path: str, schema: T.StructType, options: dict):
    """Vectorized JSON-lines fast path: pyarrow's NDJSON reader parses
    typed columns at C speed for the clean common case.  Every Spark/
    arrow semantic divergence (type coercion to null, number-to-string
    literal text, malformed lines, out-of-range...) makes arrow RAISE,
    so the strict loop still decides those files; integral range checks
    (Spark nulls out-of-range) run in numpy on the int64 parse."""
    import numpy as np

    try:
        import pyarrow as pa
        import pyarrow.json as pajson
    except ImportError:
        return None
    corrupt_col = str(options.get("columnNameOfCorruptRecord",
                                  DEFAULT_CORRUPT_COL))
    fields = schema.fields
    if any(f.name == corrupt_col for f in fields):
        return None     # malformed-record capture needs the strict loop

    def _arrow_type(dt):
        if isinstance(dt, T.StringType):
            return pa.string()
        if dt.is_integral:
            return pa.int64()   # range-checked to null below (Spark)
        if isinstance(dt, T.DoubleType):
            return pa.float64()
        if isinstance(dt, T.FloatType):
            return pa.float64()
        if isinstance(dt, T.BooleanType):
            return pa.bool_()
        return None             # date/ts/decimal/nested: strict loop

    atypes = [_arrow_type(f.dataType) for f in fields]
    if any(t is None for t in atypes):
        return None
    try:
        tbl = pajson.read_json(
            path,
            parse_options=pajson.ParseOptions(
                explicit_schema=pa.schema(
                    [(f.name, t) for f, t in zip(fields, atypes)]),
                unexpected_field_behavior="ignore"))
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError, OSError):
        return None
    from spark_rapids_tpu.columnar.column import HostColumn

    cols = []
    for f in fields:
        col = tbl.column(f.name).combine_chunks()
        dt = f.dataType
        if dt.is_integral and not isinstance(dt, T.LongType):
            validity = np.asarray(col.is_valid())
            vals = np.asarray(col.fill_null(0), np.int64)
            lo, hi = _I_RANGE[type(dt)]
            validity = validity & (vals >= lo) & (vals <= hi)
            cols.append(HostColumn(
                dt, validity,
                data=np.where(validity, vals, 0).astype(
                    T.storage_dtype(dt))))
        elif isinstance(dt, T.FloatType):
            validity = np.asarray(col.is_valid())
            vals = np.asarray(col.fill_null(0.0), np.float64).astype(
                np.float32)
            cols.append(HostColumn(dt, validity, data=vals))
        else:
            cols.append(HostColumn.from_arrow(col, dt))
    return cols, tbl.num_rows


def read_json_spark(path: str, schema: T.StructType, options: dict):
    """Spark-semantic JSON-lines read; file-context annotated like the
    CSV twin."""
    from spark_rapids_tpu.io.faults import file_context

    with file_context(path, "json", "host"):
        return _read_json_spark(path, schema, options)


def _read_json_spark(path: str, schema: T.StructType, options: dict):
    """Spark-semantic JSON-lines read -> (HostColumns, row count)."""
    if str(options.get("tpuFastParse", "true")).lower() != "false":
        try:
            fast = _read_json_fast(path, schema, options)
        except Exception as e:
            from spark_rapids_tpu.resilience import classify as _CL

            if _CL.classify_failure(e) == _CL.PROPAGATE:
                # a tripped CancelToken (or ANSI-mode error) must
                # unwind, not silently degrade to the strict loop
                raise
            fast = None
        if fast is not None:
            return fast
    mode = str(options.get("mode", "PERMISSIVE")).upper()
    corrupt_col = str(options.get("columnNameOfCorruptRecord",
                                  DEFAULT_CORRUPT_COL))
    fields = schema.fields
    corrupt_idx = next((i for i, f in enumerate(fields)
                        if f.name == corrupt_col), None)
    rows = []
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            raw = line.rstrip("\n")
            if not raw.strip():
                continue
            out = [None] * len(fields)
            try:
                obj = _json.loads(raw)
                bad = not isinstance(obj, dict)
            except ValueError:
                obj, bad = None, True
            if not bad:
                for i, f in enumerate(fields):
                    if i == corrupt_idx:
                        continue
                    out[i] = _convert_json_value(obj.get(f.name),
                                                 f.dataType)
            if bad:
                if mode == "FAILFAST":
                    raise RuntimeError(
                        f"Malformed JSON record (FAILFAST): {raw!r}")
                if mode == "DROPMALFORMED":
                    continue
                if corrupt_idx is not None:
                    out[corrupt_idx] = raw
            rows.append(out)
    return _finish(rows, schema)
