"""store_sales LEFT OUTER JOIN store_returns ON (ticket, item), net sales
per store (bench.build_qb / bench.cpu_qb_vectorized)."""
from decimal import Decimal

import numpy as np

from benchmark.datagen.store_sales import N_ITEMS, N_STORES

TABLES = ("store_sales", "store_returns")


def build(frames):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.base import Literal
    from spark_rapids_tpu.expr.conditional import Coalesce
    from spark_rapids_tpu.session import col, sum_

    joined = frames["store_sales"].join(
        frames["store_returns"], on=["ticket", "item_sk"], how="left")
    net = (col("ext_sales")
           - Coalesce([col("return_amt"),
                       Literal(Decimal("0.00"), T.DecimalType(7, 2))]))
    return (joined.select(col("store_sk"), net.alias("net"))
            .group_by("store_sk").agg(sum_("net", "net_sales")))


def answer(rows):
    """{(store,): net sales in cents}."""
    return {(int(s),): int(v.scaleb(2)) for s, v in rows}


def _net(ss, sr):
    k = np.int64(2 * N_ITEMS)
    skey = ss["ticket"] * k + ss["item_sk"]
    rkey = sr["ticket"] * k + sr["item_sk"]
    order = np.argsort(rkey)
    rk_sorted = rkey[order]
    ramt_sorted = sr["return_amt"][order]
    pos = np.clip(np.searchsorted(rk_sorted, skey), 0, len(rk_sorted) - 1)
    found = rk_sorted[pos] == skey
    return ss["ext_sales"] - np.where(found, ramt_sorted[pos], 0)


def reference(tables):
    ss, sr = tables["store_sales"], tables["store_returns"]
    net = _net(ss, sr)
    # 2.9 M rows of at most 1e6 cents: far inside float64's 2^53
    sums = np.bincount(ss["store_sk"], weights=net.astype(np.float64),
                       minlength=N_STORES + 1)
    present = np.bincount(ss["store_sk"], minlength=N_STORES + 1) > 0
    return {(int(s),): int(sums[s]) for s in np.nonzero(present)[0]}


def reference_lowp(tables):
    """The control: the per-store sums in float32."""
    ss, sr = tables["store_sales"], tables["store_returns"]
    net = _net(ss, sr).astype(np.float32)
    order = np.argsort(ss["store_sk"], kind="stable")
    stores, starts = np.unique(ss["store_sk"][order], return_index=True)
    totals = np.add.reduceat(net[order], starts, dtype=np.float32)
    return {(int(s),): int(t) for s, t in zip(stores, totals)}


def min_bytes(rows):
    return None     # the join's least bytes are not reckoned yet
