"""store_sales JOIN (date_dim WHERE d_qoy = 1) ON date_sk, sales per
(d_year, store_sk): the one-dimension star join of TPC-DS queries 3, 42,
43, 52 and 55 (bench.build_qa), over keys and a measure that may be null.

SQL's rules, which the reference spells out in plain numpy: a null
``date_sk`` matches no date; a null ``store_sk`` is a group of its own
(key None); a null ``ext_sales`` adds nothing to its group's sum, and the
sum of a group with no other row is NULL (keyed apart, see ``answer``)."""
import numpy as np

TABLES = ("store_sales", "date_dim")

NULL_SUM = "sum is null"


def build(frames):
    from spark_rapids_tpu.expr.predicates import EqualTo
    from spark_rapids_tpu.session import col, lit, sum_

    dates = frames["date_dim"].filter(EqualTo(col("d_qoy"), lit(1)))
    return (frames["store_sales"].join(dates, on="date_sk")
            .group_by("d_year", "store_sk")
            .agg(sum_("ext_sales", "sum_sales")))


def answer(rows):
    """{(year, store or None): sales in cents}.  A NULL sum (a group
    whose every ``ext_sales`` is null; a handful of rows a group at a
    test's size, never at the cell's) has no cents to compare: its key is
    (year, store, NULL_SUM) and its value 0, so that a 0 in a NULL's
    place, or the reverse, is a group off."""
    out = {}
    for year, store, v in rows:
        key = (int(year), None if store is None else int(store))
        if v is None:
            out[key + (NULL_SUM,)] = 0
        else:
            out[key] = int(v.scaleb(2))
    return out


def _groups(tables):
    """The joined rows, from the tables' numpy columns: (year, store,
    store is null, cents, cents is null) of every sale whose date is not
    null and is a date of quarter 1, by a direct index over the
    calendar's ``date_sk`` range."""
    ss, dd = tables["store_sales"], tables["date_dim"]
    sk0 = int(dd["date_sk"].min())
    days = int(dd["date_sk"].max()) - sk0 + 1
    year_of = np.zeros(days, np.int64)
    in_q1 = np.zeros(days, bool)
    q1 = dd["d_qoy"] == 1
    year_of[dd["date_sk"][q1] - sk0] = dd["d_year"][q1]
    in_q1[dd["date_sk"][q1] - sk0] = True
    day = np.ma.getdata(ss["date_sk"]).astype(np.int64) - sk0
    keep = ~np.ma.getmaskarray(ss["date_sk"]) & (day >= 0) & (day < days)
    keep[keep] = in_q1[day[keep]]
    return (year_of[day[keep]],
            np.ma.getdata(ss["store_sk"])[keep].astype(np.int64),
            np.ma.getmaskarray(ss["store_sk"])[keep],
            np.ma.getdata(ss["ext_sales"])[keep],
            np.ma.getmaskarray(ss["ext_sales"])[keep])


def _sums(tables, dtype):
    year, store, no_store, cents, no_cents = _groups(tables)
    if not len(year):
        return {}
    store = np.where(no_store, 0, store)
    order = np.lexsort((store, no_store, year))
    year, store, no_store = year[order], store[order], no_store[order]
    first = np.ones(len(order), bool)
    first[1:] = ((year[1:] != year[:-1]) | (store[1:] != store[:-1])
                 | (no_store[1:] != no_store[:-1]))
    starts = np.nonzero(first)[0]
    totals = np.add.reduceat(
        np.where(no_cents, 0, cents)[order].astype(dtype), starts,
        dtype=dtype)
    counted = np.add.reduceat((~no_cents)[order].astype(np.int64), starts)
    out = {}
    for i, total, n in zip(starts, totals, counted):
        key = (int(year[i]), None if no_store[i] else int(store[i]))
        if n:
            out[key] = int(total)
        else:
            out[key + (NULL_SUM,)] = 0
    return out


def reference(tables):
    return _sums(tables, np.int64)


def reference_lowp(tables):
    """The control: the per-group sums in float32."""
    return _sums(tables, np.float32)


def min_bytes(rows):
    """What the query must read at the engine's widths: of every sale its
    date and store keys (int32) and its DECIMAL(7,2) measure (int64); of
    every date its key, year and quarter (int32).  The ~78-row answer is
    nothing."""
    return rows["store_sales"] * (4 + 4 + 8) + rows["date_dim"] * (4 + 4 + 4)
