"""TPC-H Q6 with the specification's validation parameters
(bench.build_q6 / chip_smoke's parquet form / bench.cpu_q6_vectorized)."""
import datetime
from decimal import Decimal

import numpy as np

TABLES = ("lineitem",)

_D0, _D1 = datetime.date(1994, 1, 1), datetime.date(1995, 1, 1)
_EPOCH = datetime.date(1970, 1, 1)


def build(frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.session import col, lit, sum_

    df = frames["lineitem"]
    kinds = {f.name: f.dataType for f in df.schema.fields}
    if isinstance(kinds["l_discount"], T.DecimalType):
        d0, d1 = lit(_D0), lit(_D1)
        lo, hi, qty = (lit(Decimal("0.05")), lit(Decimal("0.07")),
                       lit(Decimal(24)))
    else:
        # the parquet residency: unscaled INT64 cents and INT32 days
        d0, d1 = lit((_D0 - _EPOCH).days), lit((_D1 - _EPOCH).days)
        lo, hi, qty = lit(5), lit(7), lit(2400)
    return (df.filter((col("l_shipdate_days") >= d0)
                      & (col("l_shipdate_days") < d1)
                      & (col("l_discount") >= lo)
                      & (col("l_discount") <= hi)
                      & (col("l_quantity") < qty))
            .select((col("l_extendedprice") * col("l_discount"))
                    .alias("revenue"))
            .agg(sum_("revenue", "revenue")))


def answer(rows):
    """{(): revenue as an integer of scale 4}."""
    out = {}
    for (v,) in rows:
        out[()] = int(v.scaleb(4)) if isinstance(v, Decimal) else int(v)
    return out


def _qualifying(li):
    return ((li["l_shipdate_days"] >= 8766) & (li["l_shipdate_days"] < 9131)
            & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
            & (li["l_quantity"] < 2400))


def reference(tables):
    li = tables["lineitem"]
    f = _qualifying(li)
    # product of two DECIMAL(12,2) -> scale 4; int64 is exact here
    return {(): int(np.sum(li["l_extendedprice"][f] * li["l_discount"][f]))}


def reference_lowp(tables):
    """The control: the product and the sum in float32 — what an
    accumulator of 32-bit lanes in place of the emulated int64 gives."""
    li = tables["lineitem"]
    f = _qualifying(li)
    prod = (li["l_extendedprice"][f].astype(np.float32)
            * li["l_discount"][f].astype(np.float32))
    return {(): int(np.sum(prod, dtype=np.float32))}


def min_bytes(rows):
    """Three DECIMAL(12,2) columns as int64 and one DATE as int32: every
    row's 28 bytes are read once; the one-row answer is nothing."""
    return rows["lineitem"] * (3 * 8 + 4)
