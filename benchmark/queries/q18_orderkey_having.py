"""TPC-H Q18's IN-subquery with its qualification value ``:1 = 300``,
projecting the sum beside the key as Q18's outer query reports it:

    SELECT l_orderkey, sum(l_quantity)
    FROM lineitem
    GROUP BY l_orderkey
    HAVING sum(l_quantity) > 300

Every order is a group (15,000,000 at SF10); a few hundred qualify.  The
reference sums each order's run of lines in int64 cents (``l_quantity``
is DECIMAL(12,2) as unscaled cents), independently of the engine; the
CPU tests use the same function as their oracle."""
from decimal import Decimal

import numpy as np

TABLES = ("lineitem",)

THRESHOLD = 300                 # Q18's qualification value of :1
CHIPS = 4                       # the deployment's executors, one a chip


def build(frames):
    from spark_rapids_tpu.session import col, lit, sum_

    _counted_quota()
    return (frames["lineitem"].group_by("l_orderkey")
            .agg(sum_("l_quantity", "sum_qty"))
            .filter(col("sum_qty") > lit(Decimal(THRESHOLD))))


def _counted_quota():
    """The cell measures an all-to-all sized by the groups the partial
    sends, which the engine reports in its counter ``ici_quota_rows``
    (docs/diagnostics.md).  An engine without it reserves the shard's
    capacity for every peer and merges n_dev x the shard on each chip: that
    program does not compile inside the first run's limit at this size nor
    at a quarter of it (PERF.md, section 6), so it is refused here, at
    set-up, before any program compiles."""
    from spark_rapids_tpu import perfcounters

    if "ici_quota_rows" not in perfcounters.COUNTERS:
        raise RuntimeError(
            "q18_orderkey_having: this engine counts no ici_quota_rows: its "
            "mesh aggregate reserves the shard's capacity for every peer")


def answer(rows):
    """{(l_orderkey,): sum of l_quantity in cents}.  An order answered
    twice (two chips each holding a group of it) keeps its second row
    under (l_orderkey, "again"): a group off, even where both rows hold
    the same sum."""
    out = {}
    for k, v in rows:
        key = (int(k),)
        while key in out:
            key = key + ("again",)
        out[key] = int(v.scaleb(2))
    return out


def _runs(keys):
    """(order of the rows by key, start of each key's run in that order):
    the generator's rows are already in key order and keep it."""
    order = (None if bool(np.all(keys[1:] >= keys[:-1]))
             else np.argsort(keys, kind="stable"))
    k = keys if order is None else keys[order]
    return order, np.flatnonzero(np.r_[True, k[1:] != k[:-1]])


def _sums(tables, dtype, scale):
    li = tables["lineitem"]
    keys, qty = li["l_orderkey"], li["l_quantity"]
    if len(keys) == 0:
        return {}
    order, starts = _runs(keys)
    q = (qty if order is None else qty[order]).astype(dtype)
    sums = np.add.reduceat(q * dtype(scale), starts, dtype=dtype)
    k = keys if order is None else keys[order]
    keep = sums > dtype(THRESHOLD * 100 * scale)
    return {(int(key),): int(round(float(s) / scale))
            for key, s in zip(k[starts][keep], sums[keep])}


def reference(tables):
    return _sums(tables, np.int64, 1)


def reference_lowp(tables):
    """The control: each order's sum in bfloat16, the MXU's input
    precision, over the quantities as numbers (1.00 to 50.00).  Sums past
    256 lose their last unit; float32 would not, since seven values up to
    50.00 sum exactly there."""
    from ml_dtypes import bfloat16

    return _sums(tables, bfloat16, 0.01)


def min_bytes(rows):
    """The fullest chip's shard: its ``l_orderkey`` and ``l_quantity``,
    8 bytes each, read once; the answer is a few hundred rows."""
    return -(-rows["lineitem"] // CHIPS) * 16
