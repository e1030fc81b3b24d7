"""Literal-expectation tests pinning Spark-documented semantics.

The differential harness proves TPU == oracle; since BOTH are written here,
a shared misunderstanding of Spark would be invisible to it.  This file
pins ~50 hand-derived expectations from Spark's
documented behavior (ANSI errors, HALF_UP decimal rounding, NaN/-0.0
ordering, Java integer wrap, date/time edges) and checks BOTH backends
against the literal values — oracle bugs cannot silently define truth.

Reference analog: the ScalaTest suites that assert exact values
(CastOpSuite etc., SURVEY.md §4) rather than GPU==CPU.
"""
import datetime
import math
from decimal import Decimal

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.cast import Cast
from spark_rapids_tpu.session import TpuSession, col, lit, sum_, avg_


def _both(build, expected_rows):
    """Run on the TPU path and the oracle; both must equal the pinned rows."""
    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled})
        got = build(s).collect()
        assert got == expected_rows, (
            f"{'TPU' if enabled else 'CPU'} diverges from pinned Spark "
            f"semantics: {got} != {expected_rows}")


def _df1(s, values, dtype, name="a"):
    return s.create_dataframe(
        {name: values}, T.StructType([T.StructField(name, dtype)]))


# -- integral arithmetic: Java two's-complement wrap -------------------------

def test_int_add_wraps():
    _both(lambda s: _df1(s, [2147483647], T.INT).select(
        (col("a") + lit(1)).alias("r")), [(-2147483648,)])


def test_long_multiply_wraps():
    _both(lambda s: _df1(s, [2 ** 62], T.LONG).select(
        (col("a") * lit(4)).alias("r")), [(0,)])


def test_long_sum_wraps():
    _both(lambda s: _df1(s, [2 ** 62, 2 ** 62, 2 ** 62, 2 ** 62],
                         T.LONG).agg(sum_("a", "s")), [(0,)])


def test_byte_cast_truncates():
    _both(lambda s: _df1(s, [300], T.INT).select(
        Cast(col("a"), T.BYTE).alias("r")), [(44,)])


def test_integral_divide_semantics():
    from spark_rapids_tpu.expr.arithmetic import IntegralDivide

    _both(lambda s: _df1(s, [-7], T.INT).select(
        IntegralDivide(col("a"), lit(2)).alias("r")), [(-3,)])


def test_remainder_sign_follows_dividend():
    _both(lambda s: _df1(s, [-7], T.INT).select(
        (col("a") % lit(3)).alias("r")), [(-1,)])


def test_pmod_always_non_negative():
    from spark_rapids_tpu.expr.arithmetic import Pmod

    _both(lambda s: _df1(s, [-7], T.INT).select(
        Pmod(col("a"), lit(3)).alias("r")), [(2,)])


def test_divide_by_zero_null_legacy():
    _both(lambda s: _df1(s, [10], T.INT).select(
        (col("a") / lit(0)).alias("r")), [(None,)])


# -- decimal: DecimalPrecision + HALF_UP -------------------------------------

def test_decimal_multiply_result_type_and_value():
    def build(s):
        df = s.create_dataframe(
            {"a": [Decimal("1.10")], "b": [Decimal("2.50")]},
            T.StructType([T.StructField("a", T.DecimalType(12, 2)),
                          T.StructField("b", T.DecimalType(12, 2))]))
        return df.select((col("a") * col("b")).alias("r"))

    # decimal(12,2)*decimal(12,2) -> decimal(25,4)
    _both(build, [(Decimal("2.7500"),)])


def test_decimal_rescale_half_up():
    _both(lambda s: _df1(s, [Decimal("2.345")], T.DecimalType(10, 3)).select(
        Cast(col("a"), T.DecimalType(10, 2)).alias("r")),
        [(Decimal("2.35"),)])


def test_decimal_rescale_half_up_negative():
    _both(lambda s: _df1(s, [Decimal("-2.345")], T.DecimalType(10, 3)).select(
        Cast(col("a"), T.DecimalType(10, 2)).alias("r")),
        [(Decimal("-2.35"),)])


def test_decimal_rescale_half_up_exact_half():
    _both(lambda s: _df1(s, [Decimal("0.125")], T.DecimalType(10, 3)).select(
        Cast(col("a"), T.DecimalType(10, 2)).alias("r")),
        [(Decimal("0.13"),)])  # HALF_UP, not banker's


def test_decimal_overflow_null_legacy():
    _both(lambda s: _df1(s, [Decimal("99.9")], T.DecimalType(3, 1)).select(
        Cast(col("a"), T.DecimalType(2, 1)).alias("r")), [(None,)])


def test_decimal_sum_type_widens_by_10():
    def build(s):
        df = _df1(s, [Decimal("1.5"), Decimal("2.5")], T.DecimalType(5, 1))
        return df.agg(sum_("a", "s"))

    _both(build, [(Decimal("4.0"),)])


def test_decimal_avg_scale_plus_4_half_up():
    def build(s):
        df = _df1(s, [Decimal("1"), Decimal("2")], T.DecimalType(5, 0))
        return df.agg(avg_("a", "r"))

    _both(build, [(Decimal("1.5000"),)])


def test_decimal128_sum_exact():
    big = Decimal(10 ** 20)
    def build(s):
        df = _df1(s, [big, big, big], T.DecimalType(25, 0))
        return df.agg(sum_("a", "s"))

    _both(build, [(Decimal(3 * 10 ** 20),)])


# -- floats: NaN / -0.0 / round ---------------------------------------------

def test_neg_zero_equals_zero():
    _both(lambda s: _df1(s, [-0.0], T.DOUBLE).select(
        col("a").eq(lit(0.0)).alias("r")), [(True,)])


def test_neg_zero_groups_with_zero():
    def build(s):
        df = _df1(s, [-0.0, 0.0], T.DOUBLE)
        return df.group_by("a").agg(("count_star", None, "c"))

    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled})
        got = build(s).collect()
        assert len(got) == 1 and got[0][1] == 2, got


def test_nan_sorts_greatest():
    def build(s):
        df = _df1(s, [1.0, float("nan"), float("inf"), -1.0], T.DOUBLE)
        return df.order_by("a")

    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled})
        got = [r[0] for r in build(s).collect()]
        assert got[0] == -1.0 and got[1] == 1.0 and got[2] == float("inf")
        assert got[3] != got[3]  # NaN last


def test_nan_equals_nan_in_groupby():
    def build(s):
        df = _df1(s, [float("nan"), float("nan")], T.DOUBLE)
        return df.group_by("a").agg(("count_star", None, "c"))

    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled})
        got = build(s).collect()
        assert len(got) == 1 and got[0][1] == 2, got


def test_max_prefers_nan():
    _both(lambda s: _df1(s, [1.0, float("nan")], T.DOUBLE).agg(
        ("max", col("a"), "m")), [(pytest.approx(float("nan"), nan_ok=True),)])


def test_round_half_up_not_bankers():
    from spark_rapids_tpu.expr.mathfuncs import Round

    _both(lambda s: _df1(s, [2.5], T.DOUBLE).select(
        Round(col("a"), lit(0)).alias("r")), [(3.0,)])


def test_rint_is_bankers():
    from spark_rapids_tpu.expr.mathfuncs import Rint

    _both(lambda s: _df1(s, [2.5], T.DOUBLE).select(
        Rint(col("a")).alias("r")), [(2.0,)])


def test_log_nonpositive_null():
    from spark_rapids_tpu.expr.mathfuncs import Log

    _both(lambda s: _df1(s, [0.0], T.DOUBLE).select(
        Log(col("a")).alias("r")), [(None,)])


def test_double_cast_to_long_truncates():
    _both(lambda s: _df1(s, [-3.99], T.DOUBLE).select(
        Cast(col("a"), T.LONG).alias("r")), [(-3,)])


def test_float_cast_nan_to_int_zero():
    _both(lambda s: _df1(s, [float("nan")], T.DOUBLE).select(
        Cast(col("a"), T.INT).alias("r")), [(0,)])


def test_double_to_long_saturates():
    _both(lambda s: _df1(s, [1e300], T.DOUBLE).select(
        Cast(col("a"), T.LONG).alias("r")), [(9223372036854775807,)])


# -- ANSI mode ---------------------------------------------------------------

def test_ansi_int_overflow_raises():
    from spark_rapids_tpu.expr.base import SparkArithmeticException

    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled,
                        "spark.sql.ansi.enabled": True})
        df = _df1(s, [2147483647], T.INT).select((col("a") + lit(1)).alias("r"))
        with pytest.raises(SparkArithmeticException):
            df.collect()


def test_ansi_divide_by_zero_raises():
    from spark_rapids_tpu.expr.base import SparkArithmeticException

    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled,
                        "spark.sql.ansi.enabled": True})
        df = _df1(s, [1], T.INT).select((col("a") / lit(0)).alias("r"))
        with pytest.raises(SparkArithmeticException):
            df.collect()


def test_ansi_decimal_overflow_raises():
    from spark_rapids_tpu.expr.base import SparkArithmeticException

    for enabled in (True, False):
        s = TpuSession({"spark.rapids.sql.enabled": enabled,
                        "spark.sql.ansi.enabled": True})
        df = _df1(s, [Decimal("99.9")], T.DecimalType(3, 1)).select(
            Cast(col("a"), T.DecimalType(2, 1)).alias("r"))
        with pytest.raises(SparkArithmeticException):
            df.collect()


# -- strings -----------------------------------------------------------------

def test_substring_negative_start():
    from spark_rapids_tpu.expr.strings import Substring

    _both(lambda s: _df1(s, ["hello"], T.STRING).select(
        Substring(col("a"), lit(-3), lit(2)).alias("r")), [("ll",)])


def test_substring_pos_zero_behaves_like_one():
    from spark_rapids_tpu.expr.strings import Substring

    _both(lambda s: _df1(s, ["hello"], T.STRING).select(
        Substring(col("a"), lit(0), lit(3)).alias("r")), [("hel",)])


def test_concat_null_propagates():
    from spark_rapids_tpu.expr.strings import Concat

    _both(lambda s: s.create_dataframe(
        {"a": ["x"], "b": [None]},
        T.StructType([T.StructField("a", T.STRING),
                      T.StructField("b", T.STRING)])).select(
        Concat([col("a"), col("b")]).alias("r")), [(None,)])


def test_concat_ws_skips_nulls():
    from spark_rapids_tpu.expr.strings import ConcatWs

    _both(lambda s: s.create_dataframe(
        {"a": ["x"], "b": [None], "c": ["y"]},
        T.StructType([T.StructField("a", T.STRING),
                      T.StructField("b", T.STRING),
                      T.StructField("c", T.STRING)])).select(
        ConcatWs([lit("-"), col("a"), col("b"), col("c")]).alias("r")),
        [("x-y",)])


def test_substring_index_examples():
    from spark_rapids_tpu.expr.strings import SubstringIndex

    # the canonical docs examples
    _both(lambda s: _df1(s, ["www.apache.org"], T.STRING).select(
        SubstringIndex(col("a"), lit("."), lit(2)).alias("r")),
        [("www.apache",)])
    _both(lambda s: _df1(s, ["www.apache.org"], T.STRING).select(
        SubstringIndex(col("a"), lit("."), lit(-2)).alias("r")),
        [("apache.org",)])


def test_instr_not_found_zero():
    from spark_rapids_tpu.expr.strings import StringInstr

    _both(lambda s: _df1(s, ["hello"], T.STRING).select(
        StringInstr(col("a"), lit("zz")).alias("r")), [(0,)])


def test_like_escape_semantics():
    from spark_rapids_tpu.expr.strings import Like

    _both(lambda s: _df1(s, ["50%"], T.STRING).select(
        Like(col("a"), lit("50\\%")).alias("r")), [(True,)])


def test_upper_lower_ascii():
    from spark_rapids_tpu.expr.strings import Lower, Upper

    _both(lambda s: _df1(s, ["MiXeD123"], T.STRING).select(
        Upper(col("a")).alias("u"), Lower(col("a")).alias("l")),
        [("MIXED123", "mixed123")])


# -- null semantics ----------------------------------------------------------

def test_three_valued_and_or():
    def build(s):
        df = s.create_dataframe(
            {"a": [None]}, T.StructType([T.StructField("a", T.BOOLEAN)]))
        return df.select((col("a") & lit(False)).alias("and_f"),
                         (col("a") | lit(True)).alias("or_t"),
                         (col("a") & lit(True)).alias("and_t"))

    _both(build, [(False, True, None)])


def test_null_safe_equal():
    def build(s):
        df = s.create_dataframe(
            {"a": [None], "b": [None]},
            T.StructType([T.StructField("a", T.INT),
                          T.StructField("b", T.INT)]))
        from spark_rapids_tpu.expr.predicates import EqualNullSafe

        return df.select(EqualNullSafe(col("a"), col("b")).alias("r"),
                         col("a").eq(col("b")).alias("eq"))

    _both(build, [(True, None)])


def test_in_with_null_candidate():
    def build(s):
        df = _df1(s, [5], T.INT)
        return df.select(col("a").isin(1, 2, None).alias("r"))

    _both(build, [(None,)])  # no match + null candidate -> NULL


def test_count_ignores_nulls_sum_null_on_empty():
    def build(s):
        df = _df1(s, [None, None], T.INT)
        return df.agg(("count", col("a"), "c"), sum_("a", "s"))

    _both(build, [(0, None)])


def test_nulls_first_asc_default():
    def build(s):
        return _df1(s, [3, None, 1], T.INT).order_by("a")

    _both(build, [(None,), (1,), (3,)])


# -- dates -------------------------------------------------------------------

def test_add_months_clamps_to_month_end():
    from spark_rapids_tpu.expr.datetime import AddMonths

    _both(lambda s: _df1(s, [datetime.date(2024, 1, 31)], T.DATE).select(
        AddMonths(col("a"), lit(1)).alias("r")),
        [(datetime.date(2024, 2, 29),)])


def test_months_between_day_equality_ignores_time():
    from spark_rapids_tpu.expr.datetime import MonthsBetween

    def build(s):
        df = s.create_dataframe(
            {"a": [datetime.datetime(2020, 2, 15, 12, 0, 0)],
             "b": [datetime.datetime(2020, 1, 15, 0, 0, 0)]},
            T.StructType([T.StructField("a", T.TIMESTAMP),
                          T.StructField("b", T.TIMESTAMP)]))
        return df.select(MonthsBetween(col("a"), col("b")).alias("r"))

    _both(build, [(1.0,)])


def test_last_day_leap_february():
    from spark_rapids_tpu.expr.datetime import LastDay

    _both(lambda s: _df1(s, [datetime.date(2024, 2, 3)], T.DATE).select(
        LastDay(col("a")).alias("r")), [(datetime.date(2024, 2, 29),)])


def test_day_of_week_sunday_is_one():
    from spark_rapids_tpu.expr.datetime import DayOfWeek

    # 2024-01-07 was a Sunday
    _both(lambda s: _df1(s, [datetime.date(2024, 1, 7)], T.DATE).select(
        DayOfWeek(col("a")).alias("r")), [(1,)])


def test_datediff_sign():
    from spark_rapids_tpu.expr.datetime import DateDiff

    def build(s):
        df = s.create_dataframe(
            {"a": [datetime.date(2024, 1, 1)],
             "b": [datetime.date(2024, 1, 11)]},
            T.StructType([T.StructField("a", T.DATE),
                          T.StructField("b", T.DATE)]))
        return df.select(DateDiff(col("a"), col("b")).alias("r"))

    _both(build, [(-10,)])


def test_next_day_strictly_later():
    from spark_rapids_tpu.expr.datetime import NextDay

    # 2024-01-01 was a Monday; next_day(..., 'Mon') is the FOLLOWING Monday
    _both(lambda s: _df1(s, [datetime.date(2024, 1, 1)], T.DATE).select(
        NextDay(col("a"), lit("Mon")).alias("r")),
        [(datetime.date(2024, 1, 8),)])


def test_from_unixtime_epoch():
    from spark_rapids_tpu.expr.datetime import FromUnixTime

    _both(lambda s: _df1(s, [0], T.LONG).select(
        FromUnixTime(col("a"), lit("yyyy-MM-dd HH:mm:ss")).alias("r")),
        [("1970-01-01 00:00:00",)])


# -- casts -------------------------------------------------------------------

def test_string_to_int_invalid_null():
    _both(lambda s: _df1(s, ["12abc"], T.STRING).select(
        Cast(col("a"), T.INT).alias("r")), [(None,)])


def test_string_to_int_trims_whitespace():
    _both(lambda s: _df1(s, ["  42  "], T.STRING).select(
        Cast(col("a"), T.INT).alias("r")), [(42,)])


def test_bool_to_string():
    _both(lambda s: _df1(s, [True], T.BOOLEAN).select(
        Cast(col("a"), T.STRING).alias("r")), [("true",)])


def test_decimal_to_string_keeps_scale():
    _both(lambda s: _df1(s, [Decimal("1.50")], T.DecimalType(5, 2)).select(
        Cast(col("a"), T.STRING).alias("r")), [("1.50",)])


def test_date_to_string_iso():
    _both(lambda s: _df1(s, [datetime.date(2024, 3, 7)], T.DATE).select(
        Cast(col("a"), T.STRING).alias("r")), [("2024-03-07",)])


# -- JSON: Spark-documented get_json_object / from_json behavior -------------

def test_get_json_object_null_terminal():
    from spark_rapids_tpu.expr.jsonexprs import GetJsonObject
    _both(lambda s: _df1(s, ['{"a":null}'], T.STRING).select(
        GetJsonObject(col("a"), lit("$.a")).alias("r")), [(None,)])


def test_get_json_object_nested_compacts():
    from spark_rapids_tpu.expr.jsonexprs import GetJsonObject
    _both(lambda s: _df1(s, ['{"a": {"b": 1, "c": [1, 2]}}'],
                         T.STRING).select(
        GetJsonObject(col("a"), lit("$.a")).alias("r")),
        [('{"b":1,"c":[1,2]}',)])


def test_get_json_object_invalid_json_is_null():
    from spark_rapids_tpu.expr.jsonexprs import GetJsonObject
    _both(lambda s: _df1(s, ['{"a": }'], T.STRING).select(
        GetJsonObject(col("a"), lit("$.a")).alias("r")), [(None,)])


def test_get_json_object_string_unescapes():
    from spark_rapids_tpu.expr.jsonexprs import GetJsonObject
    _both(lambda s: _df1(s, ['{"a":"x\\n\\"y\\u0041"}'], T.STRING).select(
        GetJsonObject(col("a"), lit("$.a")).alias("r")), [('x\n"yA',)])


def test_from_json_permissive_nulls_whole_record():
    """An int field holding a float nulls EVERY field of the row."""
    from spark_rapids_tpu.expr.complextypes import GetStructField
    from spark_rapids_tpu.expr.jsonexprs import JsonToStructs
    schema = T.StructType([T.StructField("a", T.INT),
                           T.StructField("b", T.STRING)])

    def build(s):
        st = JsonToStructs(col("a"), schema)
        return _df1(s, ['{"a":1.5,"b":"keep"}'], T.STRING).select(
            GetStructField(st, "a").alias("x"),
            GetStructField(st, "b").alias("y"))

    _both(build, [(None, None)])


def test_from_json_missing_field_is_null_only_there():
    from spark_rapids_tpu.expr.complextypes import GetStructField
    from spark_rapids_tpu.expr.jsonexprs import JsonToStructs
    schema = T.StructType([T.StructField("a", T.INT),
                           T.StructField("b", T.STRING)])

    def build(s):
        st = JsonToStructs(col("a"), schema)
        return _df1(s, ['{"b":"only"}'], T.STRING).select(
            GetStructField(st, "a").alias("x"),
            GetStructField(st, "b").alias("y"))

    _both(build, [(None, "only")])


def test_to_json_omits_null_fields():
    from spark_rapids_tpu.expr.complextypes import CreateNamedStruct
    from spark_rapids_tpu.expr.jsonexprs import StructsToJson

    def build(s):
        st = CreateNamedStruct(["p", "q"], [col("a"), lit(None).cast(T.INT)])
        return _df1(s, [7], T.INT).select(StructsToJson(st).alias("r"))

    _both(build, [('{"p":7}',)])


def test_float_sum_inf_cancellation_pinned():
    """Spark sum over [+inf, -inf] is NaN (IEEE): the oracle's scalar adds
    hit this path with a RuntimeWarning — pin the semantics so the NaN
    behavior is deliberate, not incidental."""
    import warnings

    from spark_rapids_tpu.session import TpuSession, sum_, avg_

    inf = float("inf")
    data = {"v": [inf, -inf, 1.0, None], "w": [inf, inf, 1.0, 2.0]}
    schema = T.StructType([T.StructField("v", T.DOUBLE, True),
                           T.StructField("w", T.DOUBLE, True)])

    def run(enabled):
        s = TpuSession({"spark.rapids.sql.enabled": enabled})
        df = s.create_dataframe(data, schema)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            try:
                return df.agg(sum_("v", "sv"), sum_("w", "sw"),
                              avg_("v", "av")).collect()
            except RuntimeWarning:
                # the oracle's scalar-add path may warn; semantics pinned
                # below are what matter — rerun without -Werror
                pass
        s2 = TpuSession({"spark.rapids.sql.enabled": enabled})
        df2 = s2.create_dataframe(data, schema)
        return df2.agg(sum_("v", "sv"), sum_("w", "sw"),
                       avg_("v", "av")).collect()

    for enabled in (False, True):
        ((sv, sw, av),) = run(enabled)
        assert math.isnan(sv), f"sum(+inf,-inf,...) must be NaN ({enabled})"
        assert sw == inf
        assert math.isnan(av)
