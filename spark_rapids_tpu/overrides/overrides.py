"""TpuOverrides — the plan-rewrite registry (the product's core).

Reference analog: com/nvidia/spark/rapids/GpuOverrides.scala (~4,800 LoC):
a registry mapping every Catalyst expression / exec / scan / partitioning to
a replacement rule with a TypeSig, a tagging hook and a conversion; applied
as a Rule[SparkPlan].  The structure here is the same `expr()` / `exec()`
DSL over our plan nodes, and the apply() entry runs: wrap -> tag (accumulate
willNotWorkOnTpu reasons) -> convert (maximal TPU subtrees + transitions) ->
TpuTransitionOverrides (coalesce insertion + whole-stage fusion).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Type

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import (
    BATCH_SIZE_BYTES,
    ENABLE_CAST_STRING_TO_TIMESTAMP,
    MAX_READER_BATCH_SIZE_ROWS,
    TpuConf,
)
from spark_rapids_tpu.expr import arithmetic as A
from spark_rapids_tpu.expr import base as E
from spark_rapids_tpu.expr import cast as C
from spark_rapids_tpu.expr import collections as CL
from spark_rapids_tpu.expr import conditional as CO
from spark_rapids_tpu.expr import datetime as DT
from spark_rapids_tpu.expr import hashexprs as H
from spark_rapids_tpu.expr import complextypes as CT
from spark_rapids_tpu.expr import hof as HOF
from spark_rapids_tpu.expr import jsonexprs as J
from spark_rapids_tpu.expr import avroexprs as AV
from spark_rapids_tpu.expr import xmlexprs as XM
from spark_rapids_tpu.expr import xpath as XP
from spark_rapids_tpu.expr import mathfuncs as M
from spark_rapids_tpu.expr import misc as MI
from spark_rapids_tpu.expr import predicates as P
from spark_rapids_tpu.expr import strings as S
from spark_rapids_tpu.expr import udf as U
from spark_rapids_tpu.overrides.meta import ExprMeta, SparkPlanMeta
from spark_rapids_tpu.plan import nodes as PN

# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExprRule:
    type_sig: T.TypeSig
    extra_check: Optional[Callable[[ExprMeta], None]] = None
    desc: str = ""
    # array<string> (3-D char tensor) flows only through rules that opt in
    allow_string_arrays: bool = False
    # array<struct<flat|string...>> (the entries layout) opt-in
    allow_struct_entries: bool = False


@dataclasses.dataclass
class ExecRule:
    type_sig: T.TypeSig
    convert: Callable = None
    tag_exprs: Optional[Callable] = None
    extra_check: Optional[Callable[[SparkPlanMeta], None]] = None
    desc: str = ""
    allow_string_arrays: bool = False


_COMMON = (T.BOOLEAN_SIG + T.numeric + T.STRING_SIG + T.DATETIME_SIG
           + T.NULL_SIG)
_COMMON128 = _COMMON + T.DECIMAL_128_SIG.with_max_decimal(18)
# full 38-digit decimals (two-limb device columns, expr/decimal128.py)
_DEC128_FULL = _COMMON + T.DECIMAL_128_SIG
_NUM = T.numeric + T.NULL_SIG
_NUM128 = _NUM + T.DECIMAL_128_SIG
# arrays of primitive elements (padded list columns; element support is
# checked recursively by TypeSig.supports)
_ARRAY_SIG = T.TypeSig(frozenset({T.ArrayType}), 18)
_WITH_ARRAYS = _DEC128_FULL + _ARRAY_SIG


def _check_array_insert(meta: ExprMeta):
    e = meta.expr
    if e.pos_literal is None or int(e.pos_literal) == 0:
        meta.will_not_work_on_tpu(
            "array_insert position must be a non-zero literal on TPU "
            "(the output width bucket is a static shape)")


def _check_flatten(meta: ExprMeta):
    e = meta.expr
    if not (e._absorbed
            and all(isinstance(m.dataType, T.ArrayType)
                    for m in e.children)):
        meta.will_not_work_on_tpu(
            "flatten supports array(a1, a2, ...) of array columns on TPU "
            "(no general array<array> device layout)")


def _check_str_to_map(meta: ExprMeta):
    from spark_rapids_tpu.expr.base import Literal

    for d in meta.expr.children[1:]:
        if not isinstance(d, Literal):
            meta.will_not_work_on_tpu(
                "str_to_map delimiters must be string literals")
            break


def _check_schema_of_json(meta: ExprMeta):
    try:
        meta.expr._folded()
    except Exception as ex:  # non-literal / bad json: CPU raises instead
        meta.will_not_work_on_tpu(f"schema_of_json: {ex}")


def _check_hive_hash(meta: ExprMeta):
    for c in meta.expr.children:
        if isinstance(c.dataType, (T.DecimalType, T.TimestampType,
                                   T.ArrayType, T.MapType, T.StructType)):
            meta.will_not_work_on_tpu(
                f"hive_hash of {c.dataType.simpleString} is not supported "
                f"on TPU")
            break


def _check_xpath(meta: ExprMeta):
    from spark_rapids_tpu.expr.base import Literal

    p = meta.expr.children[1]
    if not (isinstance(p, Literal) and p.value is not None):
        meta.will_not_work_on_tpu("xpath path must be a string literal")


def _check_decimal_div(meta: ExprMeta):
    """Decimal divide computes numerator = l * 10^(s - ls + rs) in int64;
    operands whose numerator can exceed 18 digits fall back (reference:
    decimal_utils.cu 128-bit division; silent-null was a round-4 bug)."""
    e = meta.expr
    dt = e.dataType
    if not isinstance(dt, T.DecimalType):
        return
    lt = e.left.dataType
    rt = e.right.dataType
    shift = dt.scale - lt.scale + rt.scale
    if lt.precision + max(shift, 0) > 18 or rt.precision + max(-shift, 0) > 18:
        meta.will_not_work_on_tpu(
            "decimal divide intermediate exceeds 18 digits "
            "(128-bit division is not implemented on TPU)")


def _check_decimal_mult(meta: ExprMeta):
    """128x128 multiply needs 256-bit intermediates (reference caps at
    DECIMAL128 via decimal_utils.cu); operands above 18 digits fall back."""
    e = meta.expr
    for side in (e.left, e.right):
        dt = side._dataType
        if isinstance(dt, T.DecimalType) and dt.precision > 18:
            meta.will_not_work_on_tpu(
                "decimal multiply with an operand above 18 digits is not "
                "supported on TPU (needs 256-bit intermediates)")


def _check_decimal_addsub(meta: ExprMeta):
    """Reject results that Spark would rescale with precision loss (we only
    implement the exact <=38-digit path)."""
    e = meta.expr
    lt, rt = e.left._dataType, e.right._dataType
    if isinstance(lt, T.DecimalType) and isinstance(rt, T.DecimalType):
        s = max(lt.scale, rt.scale)
        p = max(lt.precision - lt.scale, rt.precision - rt.scale) + s + 1
        if p > 38:
            meta.will_not_work_on_tpu(
                "decimal add/subtract result exceeds 38 digits "
                "(precision-loss rescale not implemented on TPU)")


def _is_dec128(dt) -> bool:
    return isinstance(dt, T.DecimalType) and dt.precision > 18


def _check_cast(meta: ExprMeta):
    e: C.Cast = meta.expr
    src = e.child._dataType
    if src is None:
        return
    if not C.cast_supported(src, e.to):
        meta.will_not_work_on_tpu(
            f"cast from {src.simpleString} to {e.to.simpleString} is not "
            f"supported on TPU")
    if _is_dec128(src) or _is_dec128(e.to):
        # decimal128 limb paths implemented: dec<->dec, int->dec, dec->int,
        # dec->fp.  Everything else (string/fp->dec128, dec128->string)
        # falls back (reference: CastStrings 128-bit kernels, cast_string.cu)
        def kindof(t):
            if isinstance(t, T.DecimalType):
                return "dec"
            if isinstance(t, (T.ByteType, T.ShortType, T.IntegerType,
                              T.LongType)):
                return "int"
            if isinstance(t, (T.FloatType, T.DoubleType)):
                return "fp"
            return "other"

        pair = (kindof(src), kindof(e.to))
        if pair not in {("dec", "dec"), ("int", "dec"), ("dec", "int"),
                        ("dec", "fp")}:
            meta.will_not_work_on_tpu(
                f"cast {src.simpleString} -> {e.to.simpleString} above 18 "
                f"decimal digits is not supported on TPU")
    if isinstance(src, T.StringType) and isinstance(e.to, T.TimestampType):
        if not meta.conf.get(ENABLE_CAST_STRING_TO_TIMESTAMP):
            meta.will_not_work_on_tpu(
                "string->timestamp cast is disabled "
                "(spark.rapids.sql.castStringToTimestamp.enabled)")


def _check_like(meta: ExprMeta):
    e: S.Like = meta.expr
    pat = e.right
    if not isinstance(pat, E.Literal):
        meta.will_not_work_on_tpu("LIKE pattern must be a literal")
        return
    ok, compiled = S.try_compile_like(pat.value)
    if not ok:
        meta.will_not_work_on_tpu(
            f"LIKE pattern {pat.value!r} is not supported on TPU "
            f"(transpiler-reject path; see RegexParser analog)")
    elif compiled is not None:
        e._dfa = compiled  # reuse the tag-time compilation at eval


def _check_literal_pattern(meta: ExprMeta):
    if not isinstance(meta.expr.children[1], E.Literal):
        meta.will_not_work_on_tpu("pattern must be a literal")


def _check_rlike(meta: ExprMeta):
    """Transpile at tag time; reject -> CPU fallback (the reference's
    CudfRegexTranspiler-reject path, RegexParser.scala)."""
    from spark_rapids_tpu.regex import RegexUnsupported, compile_regex

    pat = meta.expr.children[1]
    if not isinstance(pat, E.Literal) or pat.value is None:
        meta.will_not_work_on_tpu("RLIKE pattern must be a non-null literal")
        return
    try:
        meta.expr._dfa = compile_regex(pat.value)
    except RegexUnsupported as ex:
        meta.will_not_work_on_tpu(str(ex))


def _check_literal_children(*ordinals, names="argument"):
    def check(meta: ExprMeta):
        for o in ordinals:
            ch = meta.expr.children[o]
            if not isinstance(ch, E.Literal) or ch.value is None:
                meta.will_not_work_on_tpu(
                    f"{names} (child {o}) must be a non-null literal on TPU")
    return check


def _check_time_format(meta: ExprMeta):
    """from_unixtime/date_format: literal pattern from the supported token
    subset (the transpiler-reject pattern applied to time formats)."""
    from spark_rapids_tpu.expr.datetime import parse_format

    fmt = meta.expr.children[1]
    if not isinstance(fmt, E.Literal) or fmt.value is None:
        meta.will_not_work_on_tpu("time format must be a non-null literal")
        return
    if parse_format(str(fmt.value)) is None:
        meta.will_not_work_on_tpu(
            f"time format {fmt.value!r} contains unsupported pattern "
            f"letters (supported: yyyy MM dd HH mm ss + separators)")


def _check_create_array(meta: ExprMeta):
    kids = meta.expr.children
    if not kids:
        meta.will_not_work_on_tpu("empty array() literal is not supported")
        return
    et = kids[0]._dataType
    if isinstance(et, (T.StringType, T.ArrayType, T.MapType, T.StructType)):
        meta.will_not_work_on_tpu(
            "array() of non-primitive elements is not supported on TPU")
        return
    for c in kids[1:]:
        if c._dataType != et:
            meta.will_not_work_on_tpu("array() elements must share one type")
            return


def _check_regexp_extract_all(meta: ExprMeta):
    """regexp_extract_all: span-safe literal pattern with bounded non-empty
    match length (static padded element matrix), idx 0 only."""
    from spark_rapids_tpu.regex import RegexUnsupported
    from spark_rapids_tpu.regex.spans import (compile_for_spans,
                                              match_length_bounds)

    e = meta.expr
    pat = e.children[1]
    if not isinstance(pat, E.Literal) or pat.value is None:
        meta.will_not_work_on_tpu("regexp pattern must be a non-null literal")
        return
    try:
        e._dfa = compile_for_spans(str(pat.value))
        lo, hi = match_length_bounds(str(pat.value))
    except RegexUnsupported as ex:
        meta.will_not_work_on_tpu(str(ex))
        return
    if lo < 1:
        meta.will_not_work_on_tpu(
            "regexp_extract_all: pattern can match the empty string")
    if hi is None or hi > e.MAX_MATCH_LEN:
        meta.will_not_work_on_tpu(
            f"regexp_extract_all: match length must be bounded by "
            f"{e.MAX_MATCH_LEN}")
    idx = e.children[2]
    if not isinstance(idx, E.Literal) or idx.value is None \
            or int(idx.value) != 0:
        meta.will_not_work_on_tpu(
            "regexp_extract_all with capture-group index needs a "
            "backtracking engine")


def _check_bround(meta: ExprMeta):
    ct = meta.expr.children[0]._dataType
    if isinstance(ct, T.DecimalType):
        meta.will_not_work_on_tpu(
            "bround over decimals (HALF_EVEN rescale) is not supported "
            "on TPU")


def _check_literal_fmt(meta: ExprMeta):
    if not isinstance(meta.expr.children[0], E.Literal) \
            or meta.expr.children[0].value is None:
        meta.will_not_work_on_tpu("format must be a non-null literal")


def _check_convert_timezone(meta: ExprMeta):
    from spark_rapids_tpu.tzdb import zone_tables

    e = meta.expr
    for tz in (e.source_tz, e.target_tz):
        try:
            zone_tables(tz)
        except Exception:
            meta.will_not_work_on_tpu(f"unknown timezone {tz!r}")


def _check_mask(meta: ExprMeta):
    for c in meta.expr.children[1:]:
        if not isinstance(c, E.Literal):
            meta.will_not_work_on_tpu(
                "mask replacement chars must be literals")
        elif c.value is not None and len(str(c.value)) != 1:
            meta.will_not_work_on_tpu(
                "mask replacements must be single characters")


def _check_regexp_span(meta: ExprMeta):
    from spark_rapids_tpu.regex import RegexUnsupported
    from spark_rapids_tpu.regex.spans import compile_for_spans

    e = meta.expr
    pat = e.children[1]
    if not isinstance(pat, E.Literal) or pat.value is None:
        meta.will_not_work_on_tpu("regexp pattern must be a non-null literal")
        return
    try:
        e._dfa = compile_for_spans(str(pat.value))
    except RegexUnsupported as ex:
        meta.will_not_work_on_tpu(str(ex))


def _check_split_part(meta: ExprMeta):
    d = meta.expr.children[1]
    if not isinstance(d, E.Literal) or not d.value:
        meta.will_not_work_on_tpu(
            "split_part delimiter must be a non-empty literal")
        return
    s = str(d.value)
    for k in range(1, len(s)):
        if s[k:] == s[:-k]:
            meta.will_not_work_on_tpu(
                "self-overlapping split_part delimiters are not supported "
                "on TPU (left-to-right scan ambiguity)")
            return


def _check_ilike(meta: ExprMeta):
    e = meta.expr
    pat = e.right
    if not isinstance(pat, E.Literal) or pat.value is None:
        meta.will_not_work_on_tpu(
            "ILIKE pattern must be a non-null literal")
        return
    ok, compiled = S.try_compile_like(str(pat.value).lower())
    if not ok:
        meta.will_not_work_on_tpu(
            "ILIKE pattern shape is not supported on TPU")
    else:
        e._compiled = compiled


def _check_regexp_spans(meta: ExprMeta):
    """regexp_replace/extract: literal pattern from the span-safe subset
    (regex/spans.py), literal replacement without $group refs / backslash,
    extract index 0 only (capture groups need backtracking)."""
    from spark_rapids_tpu.regex import RegexUnsupported
    from spark_rapids_tpu.regex.spans import compile_for_spans

    e = meta.expr
    pat = e.children[1]
    if not isinstance(pat, E.Literal) or pat.value is None:
        meta.will_not_work_on_tpu("regexp pattern must be a non-null literal")
        return
    try:
        e._dfa = compile_for_spans(str(pat.value))
    except RegexUnsupported as ex:
        meta.will_not_work_on_tpu(str(ex))
        return
    third = e.children[2]
    if not isinstance(third, E.Literal) or third.value is None:
        meta.will_not_work_on_tpu(
            "replacement/index must be a non-null literal")
        return
    if type(e).__name__ == "RegExpReplace":
        r = str(third.value)
        if "$" in r or "\\" in r:
            meta.will_not_work_on_tpu(
                "replacement with $group references or escapes is not "
                "supported on TPU")
    else:
        if int(third.value) != 0:
            meta.will_not_work_on_tpu(
                "regexp_extract group index != 0 needs capture groups "
                "(backtracking engine); falls back to CPU")


def _check_udf(meta: ExprMeta):
    """RapidsUDF/arrow-eval ladder: columnar UDFs fuse into the stage;
    plain python functions stay in the TPU plan via the arrow-eval host
    path (GpuArrowEvalPythonExec analog) unless disabled, in which case
    the stage falls back with the reference's explain wording."""
    from spark_rapids_tpu.expr.udf import supports_columnar

    if not supports_columnar(meta.expr.fn):
        from spark_rapids_tpu.config import ARROW_EVAL_ENABLED

        if not meta.conf.get(ARROW_EVAL_ENABLED):
            meta.will_not_work_on_tpu(
                f"UDF {meta.expr.name} does not implement "
                f"evaluate_columnar (TpuUDF); it will run row-based on "
                f"CPU")


def _check_substring_index(meta: ExprMeta):
    """Delimiter must be a literal without a self-overlap border (so left
    and right non-overlapping scans agree with Spark's byte scans)."""
    d = meta.expr.children[1]
    if not isinstance(d, E.Literal) or d.value is None:
        meta.will_not_work_on_tpu("substring_index delimiter must be a "
                                  "non-null literal")
        return
    s = str(d.value)
    for k in range(1, len(s)):
        if s[:k] == s[-k:]:
            meta.will_not_work_on_tpu(
                f"substring_index delimiter {s!r} is self-overlapping "
                f"(border of length {k}); occurrence counting may diverge")
            return


def _check_pad(meta: ExprMeta):
    _check_literal_children(1, 2, names="pad length/pad string")(meta)
    pad = meta.expr.children[2]
    if isinstance(pad, E.Literal) and pad.value == "":
        meta.will_not_work_on_tpu("empty pad string is not supported on TPU")


# structs of primitives/strings (device struct columns, columnar/column.py)
_STRUCT_SIG = (T.TypeSig(frozenset({T.StructType})) + T.BOOLEAN_SIG
               + T.INTEGRAL_SIG + T.FP_SIG + T.STRING_SIG
               + T.DATETIME_SIG + T.NULL_SIG)

_PRIM_ELEM = (T.BooleanType, T.ByteType, T.ShortType, T.IntegerType,
              T.LongType, T.FloatType, T.DoubleType, T.DateType,
              T.TimestampType)


def unsupported_nested_reason(dt, allow_string_elems=False,
                              allow_struct_entries=False) -> Optional[str]:
    """Why a nested type cannot live in device columns yet, or None.

    Array elements and map keys/values must be flat primitives (the padded
    list layout stores one numeric matrix); struct fields may additionally
    be strings.  TypeSig.supports recurses with the FULL kind set, which
    would wrongly admit array<string>, so every rule whose sig includes
    nested kinds routes through this check.  ``allow_struct_entries``
    admits array<struct<flat-or-string...>> — the entries layout
    (per-field array-column children) used by map_entries/arrays_zip."""
    if isinstance(dt, T.ArrayType):
        et = dt.elementType
        if allow_string_elems and isinstance(et, T.StringType):
            return None
        if isinstance(et, T.StructType):
            # the ENTRIES layout (per-field array-column children) is a
            # first-class representation: gather/compact/concat/host
            # conversions all handle it, so array<struct<flat|string>>
            # flows through any exec
            for f in et.fields:
                fd = f.dataType
                ok = isinstance(fd, (T.StringType,) + _PRIM_ELEM) or (
                    isinstance(fd, T.DecimalType) and not fd.is_128)
                if not ok:
                    return (f"{dt.simpleString}: entries-struct fields "
                            f"must be flat or string on TPU")
            return None
        if isinstance(et, T.DecimalType):
            return None if not et.is_128 else \
                f"{dt.simpleString}: decimal128 array elements"
        if not isinstance(et, _PRIM_ELEM):
            return (f"{dt.simpleString}: array elements must be flat "
                    f"primitives on TPU (array<string> needs a rule that "
                    f"opts in)")
        return None
    if isinstance(dt, T.MapType):
        for part, name in ((dt.keyType, "key"), (dt.valueType, "value")):
            if isinstance(part, T.DecimalType):
                if part.is_128:
                    return f"{dt.simpleString}: decimal128 map {name}s"
            elif not isinstance(part, _PRIM_ELEM):
                return (f"{dt.simpleString}: map {name}s must be flat "
                        f"primitives on TPU")
        return None
    if isinstance(dt, T.StructType):
        for f in dt.fields:
            if isinstance(f.dataType, (T.ArrayType, T.MapType,
                                       T.StructType)):
                return (f"{dt.simpleString}: nested field "
                        f"{f.name} inside a struct")
        return None
    return None


# maps with primitive keys/values (keys/values array-column pair)
_WITH_MAPS = (T.TypeSig(frozenset({T.MapType, T.ArrayType}))
              + T.BOOLEAN_SIG + T.INTEGRAL_SIG + T.FP_SIG
              + T.DATETIME_SIG + T.NULL_SIG).with_note(
    T.MapType, "primitive keys/values only (no strings yet)")


def _check_hof(meta: ExprMeta):
    """Tag the lambda body's expressions too (it is not a regular child)."""
    body_meta = wrap_expr(meta.expr.body, meta.conf)
    body_meta.tag_for_tpu()
    if not body_meta.can_run_with_children:
        for r in body_meta.all_reasons():
            meta.will_not_work_on_tpu(f"lambda body: {r}")


def _check_hof_agg(meta: ExprMeta):
    e = meta.expr
    merge_meta = wrap_expr(e.merge, meta.conf)
    merge_meta.tag_for_tpu()
    if not merge_meta.can_run_with_children:
        for r in merge_meta.all_reasons():
            meta.will_not_work_on_tpu(f"merge lambda: {r}")
    if e.finish is not None:
        fin_meta = wrap_expr(e.finish, meta.conf)
        fin_meta.tag_for_tpu()
        if not fin_meta.can_run_with_children:
            for r in fin_meta.all_reasons():
                meta.will_not_work_on_tpu(f"finish lambda: {r}")
    if e.merge.resolved and e.children[1].resolved \
            and type(e.merge.dataType) is not type(e.children[1].dataType):
        meta.will_not_work_on_tpu(
            "aggregate: merge result type must match the zero value type")
    if e.children[1].resolved and isinstance(
            e.children[1].dataType,
            (T.StringType, T.ArrayType, T.MapType, T.StructType)):
        meta.will_not_work_on_tpu(
            "aggregate: accumulator must be a flat primitive on TPU")


_SUPPORTED_CHARSETS = {"utf-8", "utf8", "us-ascii", "ascii", "iso-8859-1",
                       "utf-16", "utf-16be", "utf-16le"}


def _check_timezone(meta: ExprMeta):
    from spark_rapids_tpu.tzdb import is_known_zone

    tz = meta.expr.children[1]
    if not isinstance(tz, E.Literal):
        meta.will_not_work_on_tpu(
            "from/to_utc_timestamp: timezone must be a literal")
        return
    if not is_known_zone(tz.value):
        meta.will_not_work_on_tpu(
            f"unknown or unsupported timezone {tz.value!r}")


def _check_charset(meta: ExprMeta):
    cs = meta.expr.children[1]
    if not isinstance(cs, E.Literal):
        meta.will_not_work_on_tpu(
            "encode/decode: charset must be a literal")
        return
    if cs.value is None or str(cs.value).lower() not in _SUPPORTED_CHARSETS:
        meta.will_not_work_on_tpu(
            f"encode/decode: charset {cs.value!r} is not supported")


def _check_json_path(meta: ExprMeta):
    """Literal, non-wildcard JSON path (the reference's GpuGetJsonObject
    likewise falls back for non-literal paths)."""
    from spark_rapids_tpu.jsonpath import UnsupportedJsonPath, parse_json_path

    p = meta.expr.children[1]
    if not isinstance(p, E.Literal):
        meta.will_not_work_on_tpu(
            "get_json_object: only literal JSON paths are supported")
        return
    if p.value is None:
        return
    try:
        parse_json_path(p.value)
    except UnsupportedJsonPath as ex:
        meta.will_not_work_on_tpu(f"get_json_object: {ex} is not supported")


def _check_json_tuple(meta: ExprMeta):
    for k in meta.expr.children[1:]:
        if not isinstance(k, E.Literal):
            meta.will_not_work_on_tpu(
                "json_tuple: only literal field names are supported")
            return


_FLAT_STRUCT_OK = (T.StringType, T.BooleanType, T.ByteType, T.ShortType,
                   T.IntegerType, T.LongType, T.FloatType, T.DoubleType)


def _check_flat_struct(meta: ExprMeta, st, what: str):
    if not isinstance(st, T.StructType):
        meta.will_not_work_on_tpu(f"{what}: requires a struct schema")
        return
    for f in st.fields:
        if not isinstance(f.dataType, _FLAT_STRUCT_OK):
            meta.will_not_work_on_tpu(
                f"{what}: field {f.name} of type "
                f"{f.dataType.simpleString} is not supported (flat "
                "primitive/string structs only)")


def _check_from_json(meta: ExprMeta):
    _check_flat_struct(meta, meta.expr.schema, "from_json")


def _check_to_json(meta: ExprMeta):
    _check_flat_struct(meta, meta.expr.children[0]._dataType, "to_json")


def _check_to_binary(meta: ExprMeta):
    if meta.expr._fmt not in ("utf-8", "utf8", "hex", "base64"):
        meta.will_not_work_on_tpu(
            f"to_binary format '{meta.expr._fmt}' is not supported "
            "(utf-8/hex/base64; format must be a literal)")


def _check_sentences(meta: ExprMeta):
    meta.will_not_work_on_tpu(
        "sentences returns array<array<string>>, which has no padded "
        "device layout; always runs on CPU (the reference has no "
        "GpuSentences rule either)")


def _check_from_avro(meta: ExprMeta):
    e = meta.expr
    if e._avro_schema is None:
        meta.will_not_work_on_tpu(
            "from_avro: schema must be a literal json string")
        return
    _check_flat_struct(meta, e._dataType, "from_avro")


def _check_to_avro(meta: ExprMeta):
    _check_flat_struct(meta, meta.expr.children[0]._dataType, "to_avro")


def all_avro_sig():
    return (T.STRING_SIG + T.BINARY_SIG + T.numeric + T.BOOLEAN_SIG
            + T.NULL_SIG + T.TypeSig(frozenset({T.StructType})))


def _check_map_from_entries(meta: ExprMeta):
    at = meta.expr.children[0]._dataType
    if not (isinstance(at, T.ArrayType)
            and isinstance(at.elementType, T.StructType)
            and len(at.elementType.fields) == 2):
        meta.will_not_work_on_tpu(
            "map_from_entries requires array<struct<key,value>> input")
        return
    kt = at.elementType.fields[0].dataType
    if isinstance(kt, (T.ArrayType, T.MapType, T.StructType)):
        meta.will_not_work_on_tpu(
            "map_from_entries: nested key types are not supported on TPU")


def _check_map_sort(meta: ExprMeta):
    mt = meta.expr.children[0]._dataType
    if not isinstance(mt, T.MapType):
        meta.will_not_work_on_tpu("map_sort requires a map input")
        return
    if isinstance(mt.keyType, (T.StringType, T.ArrayType, T.MapType,
                               T.StructType, T.FloatType, T.DoubleType)):
        meta.will_not_work_on_tpu(
            "map_sort supports integral/date map keys on TPU")


def _check_shuffle(meta: ExprMeta):
    at = meta.expr.children[0]._dataType
    if isinstance(at, T.ArrayType) and isinstance(
            at.elementType, (T.ArrayType, T.MapType, T.StructType,
                             T.StringType)):
        meta.will_not_work_on_tpu(
            "shuffle supports flat-element arrays on TPU")


def _check_parse_to_datetime(meta: ExprMeta):
    fmt = meta.expr.fmt_literal
    if fmt is None:
        return
    ok = ("yyyy-MM-dd", "yyyy-MM-dd HH:mm:ss")
    if fmt is False or fmt not in ok:
        meta.will_not_work_on_tpu(
            f"to_date/to_timestamp format {fmt!r} is outside the "
            f"default-grammar subset {ok} supported on TPU")


def _check_number_format(meta: ExprMeta):
    if meta.expr._spec is None:
        meta.will_not_work_on_tpu(
            "to_number/to_char format must be a literal over the "
            "0/9/,/./$/S/MI subset")


def _check_from_xml(meta: ExprMeta):
    _check_flat_struct(meta, meta.expr.schema, "from_xml")


def _check_to_xml(meta: ExprMeta):
    _check_flat_struct(meta, meta.expr.children[0]._dataType, "to_xml")


def _check_extract(meta: ExprMeta):
    if getattr(meta.expr, "_delegate", None) is None:
        meta.will_not_work_on_tpu(
            "extract: field must be a literal among "
            + "/".join(sorted(DT._EXTRACT_FIELDS)))


EXPRESSIONS: Dict[Type, ExprRule] = {
    E.Literal: ExprRule(_WITH_ARRAYS, desc="constant literal", allow_string_arrays=True),
    E.BoundReference: ExprRule(_WITH_ARRAYS + _WITH_MAPS,
                               desc="column reference",
                               allow_string_arrays=True),
    E.AttributeReference: ExprRule(_WITH_ARRAYS + _WITH_MAPS,
                                   desc="column reference",
                                   allow_string_arrays=True),
    E.Alias: ExprRule(_WITH_ARRAYS + _WITH_MAPS
                      + T.TypeSig(frozenset({T.StructType})),
                      desc="alias", allow_string_arrays=True),
    A.Add: ExprRule(_NUM128, extra_check=_check_decimal_addsub),
    A.Subtract: ExprRule(_NUM128, extra_check=_check_decimal_addsub),
    A.Multiply: ExprRule(_NUM128, extra_check=_check_decimal_mult),
    A.Divide: ExprRule(_NUM, extra_check=_check_decimal_div),
    A.TryAdd: ExprRule(_NUM128, extra_check=_check_decimal_addsub,
                       desc="ANSI op, errors become null"),
    A.TrySubtract: ExprRule(_NUM128, extra_check=_check_decimal_addsub,
                            desc="ANSI op, errors become null"),
    A.TryMultiply: ExprRule(_NUM128, extra_check=_check_decimal_mult,
                            desc="ANSI op, errors become null"),
    A.TryDivide: ExprRule(_NUM, extra_check=_check_decimal_div,
                          desc="ANSI op, errors become null"),
    A.IntegralDivide: ExprRule(_NUM), A.Remainder: ExprRule(_NUM),
    A.Pmod: ExprRule(_NUM), A.UnaryMinus: ExprRule(_NUM),
    A.Abs: ExprRule(_NUM),
    P.EqualTo: ExprRule(_DEC128_FULL), P.LessThan: ExprRule(_DEC128_FULL),
    P.LessThanOrEqual: ExprRule(_DEC128_FULL),
    P.GreaterThan: ExprRule(_DEC128_FULL),
    P.GreaterThanOrEqual: ExprRule(_DEC128_FULL),
    P.EqualNullSafe: ExprRule(_DEC128_FULL),
    P.And: ExprRule(T.BOOLEAN_SIG + T.NULL_SIG),
    P.Or: ExprRule(T.BOOLEAN_SIG + T.NULL_SIG),
    P.Not: ExprRule(T.BOOLEAN_SIG + T.NULL_SIG),
    P.IsNull: ExprRule(_WITH_ARRAYS, allow_string_arrays=True),
    P.IsNotNull: ExprRule(_WITH_ARRAYS, allow_string_arrays=True),
    P.IsNaN: ExprRule(T.FP_SIG + T.BOOLEAN_SIG),
    P.In: ExprRule(_DEC128_FULL),
    CO.If: ExprRule(_COMMON128), CO.CaseWhen: ExprRule(_COMMON128),
    CO.Coalesce: ExprRule(_COMMON128), CO.Nvl: ExprRule(_COMMON128),
    CO.NaNvl: ExprRule(T.FP_SIG),
    CO.Greatest: ExprRule(_NUM + T.STRING_SIG),
    CO.Least: ExprRule(_NUM + T.STRING_SIG),
    C.Cast: ExprRule(_DEC128_FULL, extra_check=_check_cast),
    M.Sqrt: ExprRule(_NUM), M.Exp: ExprRule(_NUM), M.Log: ExprRule(_NUM),
    M.Log10: ExprRule(_NUM), M.Sin: ExprRule(_NUM), M.Cos: ExprRule(_NUM),
    M.Tan: ExprRule(_NUM), M.Asin: ExprRule(_NUM), M.Acos: ExprRule(_NUM),
    M.Atan: ExprRule(_NUM), M.Signum: ExprRule(_NUM), M.Pow: ExprRule(_NUM),
    M.Floor: ExprRule(_NUM), M.Ceil: ExprRule(_NUM), M.Round: ExprRule(_NUM),
    M.Sinh: ExprRule(_NUM), M.Cosh: ExprRule(_NUM), M.Tanh: ExprRule(_NUM),
    M.Asinh: ExprRule(_NUM), M.Acosh: ExprRule(_NUM),
    M.Atanh: ExprRule(_NUM), M.Cbrt: ExprRule(_NUM),
    M.Log2: ExprRule(_NUM), M.Log1p: ExprRule(_NUM),
    M.Expm1: ExprRule(_NUM), M.Rint: ExprRule(_NUM), M.Cot: ExprRule(_NUM),
    M.Csc: ExprRule(_NUM), M.Sec: ExprRule(_NUM),
    M.ToDegrees: ExprRule(_NUM), M.ToRadians: ExprRule(_NUM),
    M.Atan2: ExprRule(_NUM), M.Hypot: ExprRule(_NUM),
    M.Logarithm: ExprRule(_NUM),
    A.BitwiseAnd: ExprRule(T.INTEGRAL_SIG + T.NULL_SIG),
    A.BitwiseOr: ExprRule(T.INTEGRAL_SIG + T.NULL_SIG),
    A.BitwiseXor: ExprRule(T.INTEGRAL_SIG + T.NULL_SIG),
    A.BitwiseNot: ExprRule(T.INTEGRAL_SIG + T.NULL_SIG),
    A.ShiftLeft: ExprRule(T.INTEGRAL_SIG + T.NULL_SIG),
    A.ShiftRight: ExprRule(T.INTEGRAL_SIG + T.NULL_SIG),
    A.ShiftRightUnsigned: ExprRule(T.INTEGRAL_SIG + T.NULL_SIG),
    S.Length: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.Upper: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "ASCII-only case conversion")),
    S.Lower: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "ASCII-only case conversion")),
    S.Substring: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.Concat: ExprRule(T.STRING_SIG),
    S.StartsWith: ExprRule(T.STRING_SIG + T.BOOLEAN_SIG),
    S.EndsWith: ExprRule(T.STRING_SIG + T.BOOLEAN_SIG),
    S.Contains: ExprRule(T.STRING_SIG + T.BOOLEAN_SIG),
    S.StringTrim: ExprRule(T.STRING_SIG),
    S.Reverse: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "byte-reverse; ASCII-only")),
    S.InitCap: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "ASCII-only case conversion")),
    S.Ascii: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.Chr: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.StringReplace: ExprRule(
        T.STRING_SIG, extra_check=_check_literal_children(
            1, 2, names="search/replace")),
    S.StringTranslate: ExprRule(
        T.STRING_SIG, extra_check=_check_literal_children(
            1, 2, names="from/to")),
    S.StringInstr: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.StringLocate: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.StringLPad: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                           extra_check=_check_pad),
    S.StringRPad: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                           extra_check=_check_pad),
    S.StringRepeat: ExprRule(
        T.STRING_SIG + T.INTEGRAL_SIG,
        extra_check=_check_literal_children(1, names="repeat count")),
    S.ConcatWs: ExprRule(
        T.STRING_SIG, extra_check=_check_literal_children(
            0, names="separator")),
    S.OctetLength: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.BitLength: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.StringLeft: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "byte-based; ASCII-exact") + T.INTEGRAL_SIG),
    S.StringRight: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "byte-based; ASCII-exact") + T.INTEGRAL_SIG),
    S.SubstringIndex: ExprRule(
        T.STRING_SIG.with_note(T.StringType, "byte-based; ASCII-exact")
        + T.INTEGRAL_SIG,
        extra_check=_check_substring_index),
    S.StringSplit: ExprRule(
        _WITH_ARRAYS, allow_string_arrays=True,
        extra_check=_check_literal_children(1, names="split pattern"),
        desc="split into array<string> (host kernel + java-regex rules)"),
    S.ArrayJoin: ExprRule(_WITH_ARRAYS, allow_string_arrays=True),
    S.RegExpReplace: ExprRule(T.STRING_SIG,
                              extra_check=_check_regexp_spans),
    S.RegExpExtract: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                              extra_check=_check_regexp_spans),
    S.RegExpExtractAll: ExprRule(
        T.STRING_SIG + T.INTEGRAL_SIG + _ARRAY_SIG.with_note(
            T.ArrayType,
            f"bounded patterns; at most "
            f"{S.RegExpExtractAll.MAX_MATCHES} matches per row"),
        allow_string_arrays=True,
        extra_check=_check_regexp_extract_all),
    S.Overlay: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.FindInSet: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.Elt: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    S.StringSpace: ExprRule(
        T.STRING_SIG.with_note(
            T.StringType,
            f"length capped at {S.StringSpace.MAX_LEN}")
        + T.INTEGRAL_SIG),
    S.StringTrimLeft: ExprRule(T.STRING_SIG),
    S.StringTrimRight: ExprRule(T.STRING_SIG),
    M.BRound: ExprRule(_NUM, extra_check=_check_bround),
    M.WidthBucket: ExprRule(_NUM),
    M.Factorial: ExprRule(T.INTEGRAL_SIG),
    M.BitwiseCount: ExprRule(T.INTEGRAL_SIG + T.BOOLEAN_SIG),
    CO.Nvl2: ExprRule(_COMMON128),
    CO.NullIf: ExprRule(_COMMON128),
    S.Like: ExprRule(T.STRING_SIG + T.BOOLEAN_SIG, extra_check=_check_like),
    S.RLike: ExprRule(T.STRING_SIG + T.BOOLEAN_SIG,
                      extra_check=_check_rlike),
    DT.Year: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.Month: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.DayOfMonth: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.DayOfWeek: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.DayOfYear: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.Quarter: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.LastDay: ExprRule(T.DATETIME_SIG),
    DT.Hour: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.Minute: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.Second: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.DateAdd: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.DateSub: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.DateDiff: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.UnixTimestamp: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.WeekOfYear: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.AddMonths: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.MonthsBetween: ExprRule(T.DATETIME_SIG + T.FP_SIG),
    DT.TruncDate: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG,
        extra_check=_check_literal_children(1, names="trunc format")),
    DT.NextDay: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG,
        extra_check=_check_literal_children(1, names="day of week")),
    DT.FromUTCTimestamp: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG, extra_check=_check_timezone,
        desc="tz offset via device transition tables (tzdb.py)"),
    DT.ToUTCTimestamp: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG, extra_check=_check_timezone,
        desc="java.time gap/overlap resolution"),
    DT.FromUnixTime: ExprRule(
        T.DATETIME_SIG + T.INTEGRAL_SIG + T.STRING_SIG.with_note(
            T.StringType,
            "UTC session timezone; years 0001-9999 render correctly"),
        extra_check=_check_time_format),
    DT.DateFormat: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG.with_note(
            T.StringType,
            "UTC session timezone; years 0001-9999 render correctly"),
        extra_check=_check_time_format),
    DT.ToUnixTimestamp: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.ToDate: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG.with_note(
            T.StringType,
            "Spark stringToTimestamp subset; named timezones parse "
            "as null")),
    DT.ToTimestamp: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG.with_note(
            T.StringType,
            "Spark stringToTimestamp subset; named timezones parse "
            "as null")),
    DT.WeekDay: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.MakeDate: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.MakeTimestamp: ExprRule(
        T.DATETIME_SIG + T.INTEGRAL_SIG + T.FP_SIG + T.DECIMAL_64_SIG),
    DT.CurrentDate: ExprRule(
        T.DATETIME_SIG.with_note(
            T.DateType, "captured once per query (UTC session timezone)")),
    DT.CurrentTimestamp: ExprRule(
        T.DATETIME_SIG.with_note(
            T.TimestampType,
            "captured once per query (UTC session timezone)")),
    DT.TimestampSeconds: ExprRule(
        T.DATETIME_SIG + T.INTEGRAL_SIG + T.FP_SIG),
    DT.TimestampMillis: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.TimestampMicros: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.UnixSeconds: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.UnixMillis: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.UnixMicros: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.UnixDate: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.DateFromUnixDate: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.TruncTimestamp: ExprRule(
        T.DATETIME_SIG + T.STRING_SIG,
        extra_check=_check_literal_fmt),
    DT.TimestampAdd: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.TimestampDiff: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    DT.ConvertTimezone: ExprRule(
        T.DATETIME_SIG, extra_check=_check_convert_timezone),
    DT.MonthName: ExprRule(T.DATETIME_SIG + T.STRING_SIG),
    DT.DayName: ExprRule(T.DATETIME_SIG + T.STRING_SIG),
    DT.LocalTimestamp: ExprRule(
        T.DATETIME_SIG.with_note(
            T.TimestampType,
            "captured once per query (UTC session timezone)")),
    DT.DatePart: ExprRule(T.DATETIME_SIG + T.INTEGRAL_SIG),
    MI.BitGet: ExprRule(T.INTEGRAL_SIG),
    MI.AssertTrue: ExprRule(T.BOOLEAN_SIG + T.NULL_SIG),
    MI.TypeOf: ExprRule(_WITH_ARRAYS + _WITH_MAPS,
                        allow_string_arrays=True,
                        desc="plan-time constant"),
    MI.UrlEncode: ExprRule(T.STRING_SIG, desc="host kernel"),
    MI.UrlDecode: ExprRule(T.STRING_SIG, desc="host kernel"),
    MI.JsonArrayLength: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                                 desc="host kernel"),
    MI.JsonObjectKeys: ExprRule(
        T.STRING_SIG + _ARRAY_SIG.with_note(
            T.ArrayType,
            f"first {MI.JsonObjectKeys.MAX_KEYS} keys, width "
            f"{MI.JsonObjectKeys.KEY_WIDTH}"),
        allow_string_arrays=True, desc="host kernel"),
    MI.FormatString: ExprRule(
        T.STRING_SIG + T.INTEGRAL_SIG + T.FP_SIG,
        extra_check=_check_literal_fmt, desc="host kernel"),
    MI.Uuid: ExprRule(
        T.STRING_SIG.with_note(
            T.StringType,
            "deterministic splitmix stream (reference marks uuid "
            "nondeterministic-incompat the same way)")),
    MI.Pi: ExprRule(T.FP_SIG),
    MI.EulerNumber: ExprRule(T.FP_SIG),
    MI.ToBinary: ExprRule(T.STRING_SIG, extra_check=_check_to_binary,
                          desc="host kernel (hex/base64); utf-8 on device"),
    MI.TryToBinary: ExprRule(T.STRING_SIG, extra_check=_check_to_binary,
                             desc="null instead of error on malformed"),
    MI.BitmapBitPosition: ExprRule(T.INTEGRAL_SIG),
    MI.BitmapBucketNumber: ExprRule(T.INTEGRAL_SIG),
    MI.BitmapCount: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG + T.BINARY_SIG,
                             desc="popcount over the binary blob"),
    MI.Randn: ExprRule(
        T.FP_SIG.with_note(
            T.DoubleType,
            "splitmix Box-Muller stream, not Spark's XORShiftRandom "
            "(reference marks rand nondeterministic-incompat the same "
            "way)")),
    MI.Sentences: ExprRule(
        T.STRING_SIG + _ARRAY_SIG, extra_check=_check_sentences,
        desc="always falls back (nested array<array<string>> layout)"),
    AV.AvroDataToCatalyst: ExprRule(
        all_avro_sig(), extra_check=_check_from_avro,
        desc="host-kernel row codec (from_avro); flat primitive records"),
    AV.CatalystDataToAvro: ExprRule(
        all_avro_sig(), extra_check=_check_to_avro,
        desc="host-kernel row codec (to_avro); flat primitive records"),
    S.Mask: ExprRule(T.STRING_SIG, extra_check=_check_mask),
    S.ILike: ExprRule(T.STRING_SIG + T.BOOLEAN_SIG,
                      extra_check=_check_ilike),
    S.RegExpCount: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                            extra_check=_check_regexp_span),
    S.RegExpInStr: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                            extra_check=_check_regexp_span),
    S.RegExpSubStr: ExprRule(T.STRING_SIG,
                             extra_check=_check_regexp_span),
    S.SplitPart: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                          extra_check=_check_split_part),
    CL.Get: ExprRule(_WITH_ARRAYS, allow_string_arrays=True),
    CL.ArraySize: ExprRule(_WITH_ARRAYS, allow_string_arrays=True),
    H.Murmur3Hash: ExprRule(_COMMON128, desc="Spark murmur3 hash"),
    H.XxHash64: ExprRule(_COMMON128, desc="Spark xxhash64"),
    H.HiveHash: ExprRule(_COMMON, extra_check=_check_hive_hash,
                         desc="Hive hash (31*h + colHash)"),
    H.BloomFilterMightContain: ExprRule(
        _COMMON128 + _ARRAY_SIG.with_note(
            T.ArrayType,
            "filter layout is the TPU word array, not Spark's sketch "
            "bytes"),
        desc="bloom filter probe (runtime-filter pushdown)"),
    CL.Size: ExprRule(_WITH_ARRAYS, allow_string_arrays=True),
    CL.Cardinality: ExprRule(_WITH_ARRAYS + _WITH_MAPS,
                             allow_string_arrays=True),
    CL.GetArrayItem: ExprRule(_WITH_ARRAYS, allow_string_arrays=True),
    CL.ElementAt: ExprRule(_WITH_ARRAYS + _WITH_MAPS,
                           allow_string_arrays=True),
    CL.TryElementAt: ExprRule(_WITH_ARRAYS + _WITH_MAPS,
                              allow_string_arrays=True),
    CL.MapFromEntries: ExprRule(_WITH_MAPS + _WITH_ARRAYS,
                                extra_check=_check_map_from_entries),
    CL.MapSort: ExprRule(_WITH_MAPS,
                         extra_check=_check_map_sort),
    CL.Shuffle: ExprRule(
        _WITH_ARRAYS.with_note(
            T.ArrayType,
            "splitmix permutation stream, not Spark's random sequence"),
        extra_check=_check_shuffle),
    DT.ParseToDate: ExprRule(T.DATETIME_SIG + T.STRING_SIG,
                             extra_check=_check_parse_to_datetime),
    DT.ParseToTimestamp: ExprRule(T.DATETIME_SIG + T.STRING_SIG,
                                  extra_check=_check_parse_to_datetime),
    DT.Extract: ExprRule(T.DATETIME_SIG + T.STRING_SIG + T.INTEGRAL_SIG,
                         extra_check=_check_extract),
    S.Luhn: ExprRule(T.STRING_SIG + T.BOOLEAN_SIG),
    S.Empty2Null: ExprRule(T.STRING_SIG),
    A.UnaryPositive: ExprRule(_NUM128),
    DT.TryToTimestamp: ExprRule(T.DATETIME_SIG + T.STRING_SIG,
                                extra_check=_check_parse_to_datetime),
    MI.ToNumber: ExprRule(
        T.STRING_SIG + T.DECIMAL_128_SIG,
        extra_check=_check_number_format,
        desc="host kernel; 0/9/,/./$/S/MI format subset"),
    MI.TryToNumber: ExprRule(
        T.STRING_SIG + T.DECIMAL_128_SIG,
        extra_check=_check_number_format,
        desc="null instead of error on mismatch"),
    MI.ToCharacter: ExprRule(
        T.STRING_SIG + _NUM128, extra_check=_check_number_format,
        desc="host kernel; 0/9/,/./$/S/MI format subset"),
    MI.InputFileName: ExprRule(
        T.STRING_SIG, desc="file path stamped by the scan execs"),
    XM.XmlToStructs: ExprRule(
        all_avro_sig(), extra_check=_check_from_xml,
        desc="host-kernel row codec (from_xml); flat structs"),
    XM.StructsToXml: ExprRule(
        all_avro_sig(), extra_check=_check_to_xml,
        desc="host-kernel row codec (to_xml); flat structs"),
    CL.ArrayContains: ExprRule(_WITH_ARRAYS),
    CL.CreateArray: ExprRule(_WITH_ARRAYS, extra_check=_check_create_array),
    CL.ArrayMin: ExprRule(_WITH_ARRAYS),
    CL.ArrayMax: ExprRule(_WITH_ARRAYS),
    CL.ArrayPosition: ExprRule(_WITH_ARRAYS),
    CL.ArrayRemove: ExprRule(_WITH_ARRAYS),
    CL.ArrayDistinct: ExprRule(_WITH_ARRAYS),
    CL.ArraysOverlap: ExprRule(_WITH_ARRAYS),
    CL.ArrayUnion: ExprRule(_WITH_ARRAYS),
    CL.ArrayIntersect: ExprRule(_WITH_ARRAYS),
    CL.ArrayExcept: ExprRule(_WITH_ARRAYS),
    CL.ArrayInsert: ExprRule(_WITH_ARRAYS,
                             extra_check=_check_array_insert,
                             allow_string_arrays=True),
    CL.Flatten: ExprRule(_WITH_ARRAYS, extra_check=_check_flatten,
                         allow_string_arrays=True),
    CL.StrToMap: ExprRule(T.STRING_SIG + T.NULL_SIG + T.TypeSig(
        frozenset({T.MapType, T.ArrayType})),
                          extra_check=_check_str_to_map,
                          desc="host kernel (split family)"),
    CL.MapEntries: ExprRule(
        _WITH_MAPS + T.TypeSig(frozenset({T.StructType})),
        allow_struct_entries=True, desc="entries layout"),
    CL.ArraysZip: ExprRule(
        _WITH_ARRAYS + T.TypeSig(frozenset({T.StructType})),
        allow_struct_entries=True, allow_string_arrays=True,
        desc="entries layout"),
    CL.Slice: ExprRule(_WITH_ARRAYS),
    CL.SortArray: ExprRule(
        _WITH_ARRAYS + T.BOOLEAN_SIG,
        extra_check=_check_literal_children(1, names="ascending flag")),
    CL.ArrayRepeat: ExprRule(
        _WITH_ARRAYS.with_note(
            T.ArrayType,
            f"element count capped at {CL.ArrayRepeat.MAX_ELEMENTS}")),
    CL.Sequence: ExprRule(
        _WITH_ARRAYS.with_note(
            T.ArrayType,
            f"sequence length capped at {CL.Sequence.MAX_ELEMENTS}")),
    HOF.ArrayTransform: ExprRule(_WITH_ARRAYS, extra_check=_check_hof),
    HOF.MapZipWith: ExprRule(_WITH_MAPS + T.STRING_SIG),
    HOF.ArrayFilter: ExprRule(_WITH_ARRAYS, extra_check=_check_hof),
    HOF.ArrayExists: ExprRule(
        _WITH_ARRAYS + T.BOOLEAN_SIG, extra_check=_check_hof),
    HOF.ArrayForAll: ExprRule(
        _WITH_ARRAYS + T.BOOLEAN_SIG, extra_check=_check_hof),
    HOF.ArrayAggregate: ExprRule(_WITH_ARRAYS, extra_check=_check_hof_agg),
    CL.CreateMap: ExprRule(_WITH_MAPS),
    CL.MapKeys: ExprRule(_WITH_MAPS),
    CL.MapValues: ExprRule(_WITH_MAPS),
    CL.GetMapValue: ExprRule(_WITH_MAPS),
    CL.MapFromArrays: ExprRule(_WITH_MAPS),
    CL.MapConcat: ExprRule(_WITH_MAPS),
    CL.MapContainsKey: ExprRule(_WITH_MAPS),
    CL.ArrayCompact: ExprRule(_WITH_ARRAYS),
    CL.ArrayAppend: ExprRule(_WITH_ARRAYS),
    CL.ArrayPrepend: ExprRule(_WITH_ARRAYS),
    HOF.TransformKeys: ExprRule(_WITH_MAPS, extra_check=_check_hof),
    HOF.TransformValues: ExprRule(_WITH_MAPS, extra_check=_check_hof),
    HOF.MapFilter: ExprRule(_WITH_MAPS + T.BOOLEAN_SIG,
                            extra_check=_check_hof),
    HOF.ZipWith: ExprRule(_WITH_ARRAYS, extra_check=_check_hof),
    U.UserDefinedExpression: ExprRule(
        _DEC128_FULL, extra_check=_check_udf,
        desc="TpuUDF (RapidsUDF analog): columnar jax kernel"),
    J.SchemaOfJson: ExprRule(T.STRING_SIG,
                            extra_check=_check_schema_of_json,
                            desc="plan-time constant fold"),
    XP.XPathList: ExprRule(T.STRING_SIG + T.NULL_SIG,
                           extra_check=_check_xpath,
                           allow_string_arrays=True,
                           desc="host kernel"),
    XP.XPathString: ExprRule(T.STRING_SIG + T.NULL_SIG,
                             extra_check=_check_xpath, desc="host kernel"),
    XP.XPathBoolean: ExprRule(T.STRING_SIG + T.NULL_SIG,
                              extra_check=_check_xpath,
                              desc="host kernel"),
    XP.XPathShort: ExprRule(T.STRING_SIG + T.NULL_SIG,
                            extra_check=_check_xpath, desc="host kernel"),
    XP.XPathInt: ExprRule(T.STRING_SIG + T.NULL_SIG,
                          extra_check=_check_xpath, desc="host kernel"),
    XP.XPathLong: ExprRule(T.STRING_SIG + T.NULL_SIG,
                           extra_check=_check_xpath, desc="host kernel"),
    XP.XPathFloat: ExprRule(T.STRING_SIG + T.NULL_SIG,
                            extra_check=_check_xpath, desc="host kernel"),
    XP.XPathDouble: ExprRule(T.STRING_SIG + T.NULL_SIG,
                             extra_check=_check_xpath, desc="host kernel"),
    J.GetJsonObject: ExprRule(
        T.STRING_SIG.with_note(
            T.StringType,
            "nested results are whitespace-compacted, not re-serialized"),
        extra_check=_check_json_path,
        desc="JSON path extraction (native host kernel)"),
    J.JsonTuple: ExprRule(
        T.STRING_SIG + _STRUCT_SIG,
        extra_check=_check_json_tuple,
        desc="json_tuple as a struct of string fields"),
    J.JsonToStructs: ExprRule(
        T.STRING_SIG + _STRUCT_SIG,
        extra_check=_check_from_json,
        desc="from_json (PERMISSIVE) into a flat struct"),
    J.StructsToJson: ExprRule(
        T.STRING_SIG + _STRUCT_SIG.with_note(
            T.StructType, "float fields may format differently than Spark"),
        extra_check=_check_to_json,
        desc="to_json of a flat struct"),
    CT.GetStructField: ExprRule(_STRUCT_SIG + _DEC128_FULL),
    CT.CreateNamedStruct: ExprRule(_STRUCT_SIG + _DEC128_FULL),
    MI.Md5: ExprRule(T.STRING_SIG, desc="md5 hex digest (host kernel)"),
    MI.Sha1: ExprRule(T.STRING_SIG),
    MI.Sha2: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                      extra_check=_check_literal_children(
                          1, names="bit length")),
    MI.Crc32: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    MI.Base64: ExprRule(T.STRING_SIG),
    MI.UnBase64: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "binary output surfaces as the string column kind")),
    MI.Encode: ExprRule(T.STRING_SIG,
                        extra_check=_check_charset),
    MI.Decode: ExprRule(T.STRING_SIG, extra_check=_check_charset),
    MI.Hex: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    MI.Unhex: ExprRule(T.STRING_SIG),
    MI.Bin: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG),
    MI.Conv: ExprRule(T.STRING_SIG + T.INTEGRAL_SIG,
                      extra_check=_check_literal_children(
                          1, 2, names="bases")),
    MI.FormatNumber: ExprRule(
        _NUM + T.STRING_SIG.with_note(
            T.StringType, "HALF_EVEN rounding, US grouping")),
    MI.ParseUrl: ExprRule(T.STRING_SIG,
                          extra_check=_check_literal_children(
                              1, names="url part")),
    MI.Soundex: ExprRule(T.STRING_SIG.with_note(
        T.StringType, "ASCII letters only")),
    MI.Levenshtein: ExprRule(
        T.STRING_SIG.with_note(T.StringType, "byte-based; ASCII-exact")
        + T.INTEGRAL_SIG),
    MI.MonotonicallyIncreasingID: ExprRule(T.INTEGRAL_SIG),
    MI.SparkPartitionID: ExprRule(T.INTEGRAL_SIG),
    MI.Rand: ExprRule(T.FP_SIG.with_note(
        T.DoubleType,
        "deterministic threefry/splitmix stream, not Spark's "
        "XORShiftRandom sequence")),
    MI.RaiseError: ExprRule(T.STRING_SIG + T.NULL_SIG),
}


def wrap_expr(e: E.Expression, conf: TpuConf) -> ExprMeta:
    rule = EXPRESSIONS.get(type(e))
    return ExprMeta(e, conf, rule)


# ---------------------------------------------------------------------------
# Exec rules
# ---------------------------------------------------------------------------

_AGG_FUNCS_SUPPORTED = {"sum", "count", "count_star", "min", "max", "avg",
                        "first", "last", "var_pop", "var_samp", "stddev_pop",
                        "stddev_samp", "collect_list", "collect_set",
                        "count_if", "skewness", "kurtosis", "corr",
                        "covar_pop", "covar_samp", "percentile",
                        "approx_percentile", "approx_count_distinct",
                        "bloom_filter_agg",
                        # round 4: bool/bit/any_value/median + regr family
                        "bool_and", "bool_or", "bit_and", "bit_or",
                        "bit_xor", "any_value", "median",
                        "regr_count", "regr_avgx", "regr_avgy", "regr_sxx",
                        "regr_syy", "regr_sxy", "regr_slope",
                        "regr_intercept", "regr_r2"}

_NUMERIC_AGG_INPUT = (T.ByteType, T.ShortType, T.IntegerType, T.LongType,
                      T.FloatType, T.DoubleType, T.DecimalType)


def _agg_extra_checks(meta: SparkPlanMeta, a) -> None:
    """Per-function input gates for the breadth aggregates."""
    ct = a.child._dataType if a.child is not None else None
    if a.func == "count_if" and not isinstance(ct, T.BooleanType):
        meta.will_not_work_on_tpu("count_if requires a boolean input")
    if a.func in ("skewness", "kurtosis", "percentile",
                  "approx_percentile") \
            and not isinstance(ct, _NUMERIC_AGG_INPUT):
        meta.will_not_work_on_tpu(
            f"{a.func} requires a numeric input")
    if a.func in PN.COVARIANCE_FUNCS or a.func in PN.REGR_FUNCS:
        c2 = a.child2._dataType if a.child2 is not None else None
        for part in (ct, c2):
            if not isinstance(part, _NUMERIC_AGG_INPUT):
                meta.will_not_work_on_tpu(
                    f"{a.func} requires numeric inputs")
                break
    if a.func in ("bool_and", "bool_or") \
            and not isinstance(ct, T.BooleanType):
        meta.will_not_work_on_tpu(f"{a.func} requires a boolean input")
    if a.func in ("bit_and", "bit_or", "bit_xor") \
            and not (ct is not None and ct.is_integral):
        meta.will_not_work_on_tpu(f"{a.func} requires an integral input")
    if a.func == "median" and not isinstance(ct, _NUMERIC_AGG_INPUT):
        meta.will_not_work_on_tpu("median requires a numeric input")
    if a.func == "median" and isinstance(ct, T.DecimalType) and ct.is_128:
        meta.will_not_work_on_tpu(
            "median over decimal128 is not supported on TPU")
    if a.func in ("percentile", "approx_percentile"):
        if not a.args or not (0.0 <= float(a.args[0]) <= 1.0):
            meta.will_not_work_on_tpu(
                f"{a.func}: percentage must be a literal in [0, 1]")
        if isinstance(ct, T.DecimalType) and ct.is_128:
            meta.will_not_work_on_tpu(
                f"{a.func} over decimal128 is not supported on TPU")
    if a.func in ("approx_count_distinct", "bloom_filter_agg"):
        if isinstance(ct, T.DecimalType) and ct.precision > 18:
            meta.will_not_work_on_tpu(
                f"{a.func}: decimal128 inputs are not supported (xxhash64 "
                "big-integer path missing)")
        if isinstance(ct, (T.ArrayType, T.MapType, T.StructType)):
            meta.will_not_work_on_tpu(
                f"{a.func} over nested inputs is not supported on TPU")
    if a.func == "bloom_filter_agg":
        if len(a.args) != 2 or int(a.args[1]) % 64 != 0 \
                or not (64 <= int(a.args[1]) <= (1 << 22)):
            meta.will_not_work_on_tpu(
                "bloom_filter_agg: num_bits must be a multiple of 64 in "
                "[64, 4194304]")
_WINDOW_FUNCS_SUPPORTED = {"row_number", "rank", "dense_rank", "sum", "count",
                           "min", "max", "avg", "lead", "lag", "ntile",
                           "percent_rank", "cume_dist", "first_value",
                           "last_value", "var_pop", "var_samp", "stddev_pop",
                           "stddev_samp"}
# frame-independent ranking/navigation functions
_WINDOW_RANK_FUNCS = {"row_number", "rank", "dense_rank", "ntile",
                      "percent_rank", "cume_dist", "lead", "lag"}
# bounded ROWS frames unroll shifted combines; cap the static window width
_MAX_BOUNDED_WINDOW = 256
_JOIN_TYPES_SUPPORTED = {PN.JoinType.INNER, PN.JoinType.LEFT_OUTER,
                         PN.JoinType.RIGHT_OUTER, PN.JoinType.FULL_OUTER,
                         PN.JoinType.LEFT_SEMI, PN.JoinType.LEFT_ANTI,
                         PN.JoinType.CROSS}


def _agg_check(meta: SparkPlanMeta):
    plan: PN.HashAggregate = meta.plan
    # the array-capable sig exists for collect_* OUTPUT columns only; array
    # grouping keys / array inputs to other aggregates have no TPU kernels
    for g in plan.grouping:
        if isinstance(g._dataType, T.ArrayType):
            meta.will_not_work_on_tpu(
                "grouping by an array column is not supported on TPU")
    for a in plan.aggregates:
        if (a.func not in ("collect_list", "collect_set")
                and a.child is not None
                and isinstance(a.child._dataType, T.ArrayType)):
            meta.will_not_work_on_tpu(
                f"{a.func} over an array column is not supported on TPU")
        if a.func not in _AGG_FUNCS_SUPPORTED:
            meta.will_not_work_on_tpu(
                f"aggregate function {a.func} is not supported on TPU")
        else:
            _agg_extra_checks(meta, a)
        if a.distinct:
            meta.will_not_work_on_tpu(
                "distinct aggregates are not supported on TPU yet")
        if a.func in ("collect_list", "collect_set") \
                and a.child is not None:
            et = a.child._dataType
            if isinstance(et, (T.StringType, T.ArrayType, T.MapType,
                               T.StructType)) or _is_dec128(et):
                meta.will_not_work_on_tpu(
                    f"{a.func} of {et.simpleString} elements is not "
                    f"supported on TPU (primitive elements only)")
        if (a.func in ("avg", "var_pop", "var_samp", "stddev_pop",
                       "stddev_samp")
                and a.child is not None and _is_dec128(a.child._dataType)):
            meta.will_not_work_on_tpu(
                f"{a.func} over decimals above 18 digits needs 128-bit "
                f"division; not supported on TPU yet")


def _join_check(meta: SparkPlanMeta):
    plan = meta.plan
    if plan.join_type not in _JOIN_TYPES_SUPPORTED:
        meta.will_not_work_on_tpu(
            f"join type {plan.join_type.value} is not supported on TPU")
    if plan.condition is not None and plan.join_type != PN.JoinType.INNER:
        meta.will_not_work_on_tpu(
            "non-inner join with residual condition is not supported on TPU")
    if not plan.left_keys and plan.join_type != PN.JoinType.CROSS:
        meta.will_not_work_on_tpu("equi-join keys required")
    for k in list(plan.left_keys) + list(plan.right_keys):
        if isinstance(k._dataType, (T.ArrayType, T.MapType, T.StructType)):
            meta.will_not_work_on_tpu(
                "join keys of nested types are not supported on TPU")


def _window_check(meta: SparkPlanMeta):
    """Tag-or-fallback for every (function, frame, type) combination the
    exec supports (GpuWindowExec tagging analog).  Anything rejected here is
    unreachable in exec/window.py — the RapidsMeta contract is that no
    NotImplementedError fires after conversion."""
    plan: PN.Window = meta.plan
    frame = plan.frame
    bounded = isinstance(frame, tuple)
    for f in plan.functions:
        if f.func not in _WINDOW_FUNCS_SUPPORTED:
            meta.will_not_work_on_tpu(
                f"window function {f.func} is not supported on TPU")
            continue
        if f.func in _WINDOW_RANK_FUNCS:
            continue
        ct = f.child._dataType if f.child is not None else None
        if ct is not None and isinstance(ct, (T.ArrayType, T.MapType,
                                              T.StructType)):
            meta.will_not_work_on_tpu(
                f"{f.func} over nested-typed window inputs is not "
                f"supported on TPU")
        if ct is not None and isinstance(ct, T.DecimalType) and ct.is_128 \
                and f.func != "count":
            meta.will_not_work_on_tpu(
                f"{f.func} over decimals above 18 digits in a window is "
                f"not supported on TPU")
        if ct is not None and isinstance(ct, T.DecimalType) \
                and (f.func == "avg" or f.func.startswith(("var", "stddev"))):
            meta.will_not_work_on_tpu(
                f"window {f.func} over decimals yields a decimal result "
                f"(needs decimal division); not supported on TPU")
        if isinstance(ct, T.StringType):
            if f.func in ("sum", "avg") or f.func.startswith(("var", "stddev")):
                meta.will_not_work_on_tpu(
                    f"{f.func} over strings is not valid")
            elif f.func in ("min", "max") and bounded:
                meta.will_not_work_on_tpu(
                    "string min/max over bounded window frames is not "
                    "supported on TPU (running/range/unbounded frames only)")
    if bounded:
        kind, a, b = frame
        if a < 0 or b < 0:
            meta.will_not_work_on_tpu(
                "bounded window frame offsets must be non-negative")
        elif kind == "rows" and a + b + 1 > _MAX_BOUNDED_WINDOW:
            meta.will_not_work_on_tpu(
                f"bounded window width {a + b + 1} exceeds the TPU unroll "
                f"cap ({_MAX_BOUNDED_WINDOW})")
        if kind == "range":
            if len(plan.order_by) != 1:
                meta.will_not_work_on_tpu(
                    "RANGE window frames require exactly one ORDER BY key")
            else:
                ot = plan.order_by[0][0]._dataType
                ok = (ot.is_integral
                      or isinstance(ot, (T.FloatType, T.DoubleType,
                                         T.DateType, T.TimestampType)))
                if not ok:
                    meta.will_not_work_on_tpu(
                        f"RANGE window frames over {ot.simpleString} order "
                        f"keys are not supported on TPU")
    if frame in ("range_running",) or (bounded and frame[0] == "range"):
        if not plan.order_by:
            meta.will_not_work_on_tpu(
                "RANGE window frames require an ORDER BY")


def _scan_check(meta: SparkPlanMeta):
    plan: PN.FileSourceScan = meta.plan
    fmt = plan.fmt
    key = {"parquet": "spark.rapids.sql.format.parquet.read.enabled",
           "csv": "spark.rapids.sql.format.csv.read.enabled",
           "json": "spark.rapids.sql.format.json.read.enabled",
           "orc": "spark.rapids.sql.format.orc.read.enabled",
           "avro": "spark.rapids.sql.format.avro.read.enabled"}.get(fmt)
    if key is None:
        meta.will_not_work_on_tpu(f"format {fmt} is not supported on TPU")
        return
    if str(meta.conf.settings.get(key, "true")).lower() == "false":
        meta.will_not_work_on_tpu(f"{fmt} reads disabled by {key}=false")


def _write_check(meta: SparkPlanMeta):
    """dataWriteCmds tagging (GpuOverrides.dataWriteCmds analog)."""
    plan = meta.plan
    if plan.fmt not in ("parquet", "orc", "csv", "json"):
        meta.will_not_work_on_tpu(
            f"write format {plan.fmt} is not supported on TPU")
        return
    key = f"spark.rapids.sql.format.{plan.fmt}.write.enabled"
    if str(meta.conf.settings.get(key, "true")).lower() == "false":
        meta.will_not_work_on_tpu(
            f"{plan.fmt} writes disabled by {key}=false")


def _exprs_of(plan) -> List[E.Expression]:
    if isinstance(plan, PN.Project):
        return list(plan.exprs)
    if isinstance(plan, PN.Filter):
        return [plan.condition]
    if isinstance(plan, PN.HashAggregate):
        out = list(plan.grouping)
        out += [a.child for a in plan.aggregates if a.child is not None]
        out += [a.child2 for a in plan.aggregates if a.child2 is not None]
        return out
    if isinstance(plan, PN._BaseJoin):
        out = list(plan.left_keys) + list(plan.right_keys)
        if plan.condition is not None:
            out.append(plan.condition)
        return out
    if isinstance(plan, PN.Sort):
        return [e for e, _ in plan.orders]
    if isinstance(plan, PN.Window):
        out = list(plan.partition_by) + [e for e, _ in plan.order_by]
        out += [f.child for f in plan.functions if f.child is not None]
        return out
    if isinstance(plan, PN.Exchange) and isinstance(
            plan.partitioning, PN.HashPartitioning):
        return list(plan.partitioning.keys)
    if isinstance(plan, PN.Generate):
        return [plan.gen_expr]
    if isinstance(plan, PN.Expand):
        return [e for ps in plan.projections for e in ps]
    return []


EXECS: Dict[Type, ExecRule] = {}


def _exec(cls, sig=_DEC128_FULL, tag_exprs=_exprs_of, extra=None, desc="",
          allow_string_arrays=False):
    EXECS[cls] = ExecRule(sig, tag_exprs=tag_exprs, extra_check=extra,
                          desc=desc,
                          allow_string_arrays=allow_string_arrays)


def _generate_check(meta: SparkPlanMeta):
    plan: PN.Generate = meta.plan
    dt = plan.gen_expr._dataType
    if not isinstance(dt, T.ArrayType):
        meta.will_not_work_on_tpu("explode input must be an array column")
    elif isinstance(dt.elementType, (T.ArrayType, T.MapType, T.StructType)):
        meta.will_not_work_on_tpu(
            "explode of nested array elements is not supported on TPU yet")


_BNLJ_TYPES = {PN.JoinType.INNER, PN.JoinType.CROSS, PN.JoinType.LEFT_OUTER,
               PN.JoinType.LEFT_SEMI, PN.JoinType.LEFT_ANTI}


def _bnlj_check(meta: SparkPlanMeta):
    plan: PN.BroadcastNestedLoopJoin = meta.plan
    if plan.join_type not in _BNLJ_TYPES:
        meta.will_not_work_on_tpu(
            f"nested-loop join type {plan.join_type.value} is not supported "
            f"on TPU (use an equi-join)")


def _exchange_check(meta: SparkPlanMeta):
    plan: PN.Exchange = meta.plan
    if isinstance(plan.partitioning, PN.HashPartitioning):
        for k in plan.partitioning.keys:
            if _is_dec128(k._dataType):
                meta.will_not_work_on_tpu(
                    "hash partitioning on decimals above 18 digits is not "
                    "supported on TPU (murmur3 big-integer path missing)")


_WITH_NESTED = _WITH_ARRAYS + T.TypeSig(
    frozenset({T.StructType, T.MapType}))

_exec(PN.LocalTableScan, sig=_WITH_NESTED, allow_string_arrays=True)
_exec(PN.CachedRelation, desc="GpuInMemoryTableScanExec analog")
_exec(PN.FileSourceScan, extra=_scan_check)
_exec(PN.InsertIntoHadoopFsRelation, extra=_write_check,
      desc="GpuDataWritingCommandExec analog")
_exec(PN.RangeNode)
_exec(PN.Sample, sig=_WITH_NESTED, allow_string_arrays=True,
      desc="deterministic splitmix sampler "
      "(GpuSampleExec analog; not Spark's XORShift sequence)")
_exec(PN.Project, sig=_WITH_NESTED, allow_string_arrays=True)
_exec(PN.Filter, sig=_WITH_NESTED, allow_string_arrays=True)
_exec(PN.HashAggregate, sig=_WITH_ARRAYS, extra=_agg_check)
_exec(PN.SortMergeJoin, sig=_WITH_ARRAYS, extra=_join_check,
      desc="converted to shuffled sorted join (GpuSortMergeJoinMeta analog)")
_exec(PN.ShuffledHashJoin, sig=_WITH_ARRAYS, extra=_join_check)
_exec(PN.BroadcastHashJoin, sig=_WITH_ARRAYS, extra=_join_check)
_exec(PN.Sort)
_exec(PN.Window, sig=_COMMON128, extra=_window_check)
_exec(PN.Generate, sig=_WITH_ARRAYS, extra=_generate_check,
      allow_string_arrays=True)
_exec(PN.Expand, sig=_WITH_ARRAYS)
_exec(PN.BroadcastNestedLoopJoin, extra=_bnlj_check)
_exec(PN.Exchange, extra=_exchange_check)
_exec(PN.BroadcastExchange)
_exec(PN.GlobalLimit, sig=_WITH_ARRAYS)
_exec(PN.LocalLimit, sig=_WITH_ARRAYS)
_exec(PN.Union, sig=_WITH_ARRAYS)


def wrap_plan(plan: PN.SparkPlan, conf: TpuConf) -> SparkPlanMeta:
    rule = EXECS.get(type(plan))
    return SparkPlanMeta(plan, conf, rule)


def wrap_plan_children(plan: PN.SparkPlan, conf: TpuConf):
    return [wrap_plan(c, conf) for c in plan.children]


# ---------------------------------------------------------------------------
# Conversion
# ---------------------------------------------------------------------------

def _convert_node(meta: SparkPlanMeta, tpu_children, ansi: bool):
    """Build the TpuExec for one convertible node."""
    from spark_rapids_tpu import exec as X
    from spark_rapids_tpu.exec.exchange import TpuBroadcastExchangeExec
    from spark_rapids_tpu.exec.join import TpuCartesianProductExec
    from spark_rapids_tpu.io.scan import TpuFileSourceScanExec

    plan = meta.plan
    if isinstance(plan, PN.LocalTableScan):
        from spark_rapids_tpu.config import TPU_SCAN_CACHE

        rows_cap = meta.conf.get(MAX_READER_BATCH_SIZE_ROWS)
        return X.TpuLocalTableScanExec(
            plan.host_columns, plan.output,
            target_batch_rows=rows_cap if rows_cap < 2147483647 else None,
            cache_device=meta.conf.get(TPU_SCAN_CACHE),
            cache_slot=plan.origin, ordinals=plan.ordinals)
    if isinstance(plan, PN.FileSourceScan):
        return TpuFileSourceScanExec(plan, meta.conf)
    if isinstance(plan, PN.RangeNode):
        return X.TpuRangeExec(plan.start, plan.end, plan.step)
    if isinstance(plan, PN.CachedRelation):
        return X.TpuInMemoryTableScanExec(tpu_children[0], plan.cache_slot)
    if isinstance(plan, PN.Project):
        return X.TpuProjectExec(plan.exprs, tpu_children[0], ansi)
    if isinstance(plan, PN.Filter):
        return X.TpuFilterExec(plan.condition, tpu_children[0], ansi)
    if isinstance(plan, PN.HashAggregate):
        return X.TpuHashAggregateExec(
            plan.grouping, plan.aggregates, plan.mode, tpu_children[0],
            plan.child.output, plan.output, ansi)
    if isinstance(plan, (PN.SortMergeJoin, PN.ShuffledHashJoin)):
        if plan.join_type == PN.JoinType.CROSS:
            return TpuCartesianProductExec(tpu_children[0], tpu_children[1],
                                           plan.output, plan.condition, ansi,
                                           emit=plan.emit)
        shuffled = X.TpuShuffledSymmetricHashJoinExec(
            tpu_children[0], tpu_children[1], plan.left_keys, plan.right_keys,
            plan.join_type, plan.condition, plan.output, ansi,
            sub_partition_bytes=meta.conf.get(BATCH_SIZE_BYTES),
            emit=plan.emit)
        # AQE: runtime join-strategy switch when both sides are planned
        # exchanges (spark.sql.adaptive.enabled, default on like Spark)
        from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
        from spark_rapids_tpu.exec.join import TpuAdaptiveJoinExec

        from spark_rapids_tpu.config import ADAPTIVE_ENABLED

        adaptive = meta.conf.get(ADAPTIVE_ENABLED)
        if adaptive and all(isinstance(c, TpuShuffleExchangeExec)
                            for c in shuffled.children):
            from spark_rapids_tpu.config import (
                AUTO_BROADCAST_JOIN_THRESHOLD,
            )

            return TpuAdaptiveJoinExec(
                shuffled, meta.conf.get(AUTO_BROADCAST_JOIN_THRESHOLD))
        return shuffled
    if isinstance(plan, PN.BroadcastHashJoin):
        return X.TpuBroadcastHashJoinExec(
            tpu_children[0], tpu_children[1], plan.left_keys, plan.right_keys,
            plan.join_type, plan.condition, plan.output, ansi,
            sub_partition_bytes=meta.conf.get(BATCH_SIZE_BYTES),
            emit=plan.emit)
    if isinstance(plan, PN.Sample):
        from spark_rapids_tpu.exec.limit import TpuSampleExec

        return TpuSampleExec(plan.fraction, plan.seed, tpu_children[0])
    if isinstance(plan, PN.Sort):
        return X.TpuSortExec(plan.orders, plan.is_global, tpu_children[0],
                             ansi, ooc_bytes=meta.conf.get(BATCH_SIZE_BYTES))
    if isinstance(plan, PN.Window):
        return X.TpuWindowExec(plan.functions, plan.partition_by,
                               plan.order_by, tpu_children[0], plan.output,
                               plan.frame, ansi)
    if isinstance(plan, PN.Generate):
        from spark_rapids_tpu.exec.generate import TpuGenerateExec

        return TpuGenerateExec(plan.gen_expr, tpu_children[0],
                               plan.position, plan.outer, plan.output, ansi)
    if isinstance(plan, PN.Expand):
        from spark_rapids_tpu.exec.generate import TpuExpandExec

        return TpuExpandExec(plan.projections, tpu_children[0], plan.output,
                             ansi)
    if isinstance(plan, PN.BroadcastNestedLoopJoin):
        from spark_rapids_tpu.exec.generate import (
            TpuBroadcastNestedLoopJoinExec,
        )

        return TpuBroadcastNestedLoopJoinExec(
            tpu_children[0], tpu_children[1], plan.join_type,
            plan.condition, plan.output, ansi)
    if isinstance(plan, PN.Exchange):
        return X.TpuShuffleExchangeExec(plan.partitioning, tpu_children[0],
                                        ansi, conf=meta.conf)
    if isinstance(plan, PN.BroadcastExchange):
        return TpuBroadcastExchangeExec(tpu_children[0])
    if isinstance(plan, PN.GlobalLimit):
        return X.TpuGlobalLimitExec(plan.n, tpu_children[0])
    if isinstance(plan, PN.LocalLimit):
        return X.TpuLocalLimitExec(plan.n, tpu_children[0])
    if isinstance(plan, PN.Union):
        return X.TpuUnionExec(tpu_children)
    if isinstance(plan, PN.InsertIntoHadoopFsRelation):
        from spark_rapids_tpu.io.writer import TpuDataWritingCommandExec

        return TpuDataWritingCommandExec(
            plan.fmt, plan.path, plan.partition_cols, tpu_children[0],
            meta.conf, plan.mode)
    raise NotImplementedError(f"convert {meta.name}")


class CpuSubtree:
    """Marker: this subtree stays on CPU (executed by the oracle)."""

    def __init__(self, plan: PN.SparkPlan):
        self.plan = plan


def _rebuild_cpu_plan(meta: SparkPlanMeta, converted_children):
    """Child results may be TpuExec (need materialization node) or CPU plans."""
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.overrides.transitions import TpuMaterializedScan

    new_children = []
    for cc in converted_children:
        if isinstance(cc, TpuExec):
            new_children.append(TpuMaterializedScan(cc))
        else:
            new_children.append(cc)
    return meta.plan.with_new_children(new_children)


def _walk_plan(plan: PN.SparkPlan):
    yield plan
    for c in plan.children:
        yield from _walk_plan(c)


class TpuOverrides:
    """The Rule[SparkPlan] entry point."""

    @staticmethod
    def apply(plan: PN.SparkPlan, conf: TpuConf):
        """Returns (root, meta): root is a TpuExec (possibly with embedded
        CPU subtrees) or a CPU plan (possibly with embedded TPU subtrees)."""
        from spark_rapids_tpu.exec.base import TpuExec
        from spark_rapids_tpu.exec.transitions import TpuRowToColumnarExec
        from spark_rapids_tpu.overrides.transitions import (
            TpuTransitionOverrides,
        )

        TpuOverrides._compile_udfs(plan, conf)
        meta = wrap_plan(plan, conf)
        meta.tag_for_tpu()
        TpuOverrides._apply_cost_optimizer(meta, conf)
        explain = conf.explain.upper()
        if explain in ("NOT_ON_GPU", "ALL"):
            txt = meta.explain(only_fallback=(explain == "NOT_ON_GPU"))
            if txt:
                print(txt)
        ansi = conf.ansi_enabled
        root = TpuOverrides._convert(meta, ansi)
        meta.stage_decisions = []
        if isinstance(root, TpuExec):
            from spark_rapids_tpu.overrides.transitions import (
                stage_decisions,
            )

            root = TpuTransitionOverrides.apply(root, conf)
            # transition-stage explain parity: the
            # collective/fused stages report install/fallback like execs
            meta.stage_decisions = stage_decisions()
            if explain in ("NOT_ON_GPU", "ALL"):
                for name, installed, reason in meta.stage_decisions:
                    if installed and explain == "ALL":
                        print(f"  *stage* {name} will install")
                    elif not installed:
                        print(f"  !stage! {name} cannot install because "
                              f"{reason}")
        return root, meta

    @staticmethod
    def _apply_cost_optimizer(meta: SparkPlanMeta, conf: TpuConf):
        """CostBasedOptimizer analog (SURVEY.md §2.2, default OFF like the
        reference): keeps a plan on CPU when the device round-trip cannot
        pay for itself — the transition cost (2 transfers + compile) of a
        tiny input exceeds any kernel win."""
        from spark_rapids_tpu.config import (
            OPTIMIZER_ENABLED,
            OPTIMIZER_SMALL_PLAN_BYTES,
        )

        if not conf.get(OPTIMIZER_ENABLED) or not meta.can_this_run:
            return
        from spark_rapids_tpu.session import _estimated_plan_bytes

        threshold = conf.get(OPTIMIZER_SMALL_PLAN_BYTES)
        size = _estimated_plan_bytes(meta.plan)
        if size is not None and size < threshold:
            meta.will_not_work_on_tpu(
                f"not worth accelerating (cost-based optimizer: input "
                f"~{size}B below spark.rapids.sql.optimizer."
                f"smallPlanBytes={threshold})")

    @staticmethod
    def _compile_udfs(plan: PN.SparkPlan, conf: TpuConf):
        """udf-compiler pass (the reference's logical-rule analog): trace
        plain-python UDFs in Project/Filter into expression trees so they
        fuse into the compiled stage; untranslatable UDFs keep arrow-eval.

        Runs pre-tagging; differential tests still compare against the
        oracle executing the ORIGINAL python function."""
        from spark_rapids_tpu.expr.cast import Cast
        from spark_rapids_tpu.expr.udf import (
            UserDefinedExpression,
            supports_columnar,
        )
        from spark_rapids_tpu.udf_compiler import try_compile

        from spark_rapids_tpu.config import UDF_COMPILER_ENABLED

        if not conf.get(UDF_COMPILER_ENABLED):
            return

        def make_sub(schema):
            def sub(e):
                if isinstance(e, UserDefinedExpression) \
                        and not supports_columnar(e.fn):
                    compiled = try_compile(e.fn, e.children)
                    if compiled is not None:
                        try:
                            out = Cast(compiled, e.dataType)
                            out.resolve(schema)
                            return out
                        except Exception:
                            return e
                return e

            return sub

        import copy

        def has_plain_udf(x):
            return bool(x.collect(
                lambda y: isinstance(y, UserDefinedExpression)
                and not supports_columnar(y.fn)))

        for node in _walk_plan(plan):
            # substitution works on DEEP COPIES: the logical plan is the
            # user's object and re-plans with the compiler (or the rewrite)
            # disabled must still see the original python UDF
            if isinstance(node, PN.Project):
                sub = make_sub(node.child.output)
                node.exprs = [
                    copy.deepcopy(x).transform_up(sub)
                    if has_plain_udf(x) else x for x in node.exprs]
            elif isinstance(node, PN.Filter):
                if has_plain_udf(node.condition):
                    sub = make_sub(node.child.output)
                    node.condition = copy.deepcopy(
                        node.condition).transform_up(sub)

    @staticmethod
    def _convert(meta: SparkPlanMeta, ansi: bool):
        from spark_rapids_tpu.exec.base import TpuExec
        from spark_rapids_tpu.exec.transitions import TpuRowToColumnarExec

        converted = [TpuOverrides._convert(m, ansi) for m in meta.child_metas]
        if meta.can_this_run:
            tpu_children = []
            for cc, cm in zip(converted, meta.child_metas):
                if isinstance(cc, TpuExec):
                    tpu_children.append(cc)
                else:
                    # CPU child under a TPU parent: row->columnar transition
                    tpu_children.append(TpuRowToColumnarExec(cc, ansi))
            node = _convert_node(meta, tpu_children, ansi)
            # the fault domain's runtime CPU fallback + circuit-breaker
            # keying map an exec back to its plan-node twin
            node._origin_plan = meta.plan
            return node
        # node stays on CPU; TPU children materialize through transitions
        return _rebuild_cpu_plan(meta, converted)
