"""Set-up of one cell: the chip, the data from the seed, the session, the
frames by residency, the plan shape."""
from __future__ import annotations

import os
import re

import numpy as np


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> None:
    """The measurement path fails without the chips the cell asks for."""
    import jax

    if jax.default_backend() != "tpu":
        raise NoChip(f"the benchmark measures on a TPU; "
                     f"jax.default_backend() is {jax.default_backend()!r}")
    if len(jax.devices()) < n:
        raise NoChip(f"the cell asks for {n} chips, jax sees "
                     f"{len(jax.devices())}")


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes():
    """Peak on the fullest chip; None where the backend reports none."""
    import jax

    peaks = [s.get("peak_bytes_in_use") for s in
             (d.memory_stats() for d in jax.local_devices()) if s]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def make_tables(cell, seed: int) -> dict:
    """The query's tables as numpy columns, from one generator a table
    seeded by (``seed``, the table's place in the configuration).  Row
    counts come from the configuration alone."""
    order = list(cell.config["tables"])
    tables: dict = {}

    def gen(name):
        if name not in tables:
            spec = cell.config["tables"][name]
            parent = gen(spec["from"]) if "from" in spec else None
            rng = np.random.default_rng([seed, order.index(name)])
            cols = cell.generators[name].make(spec["rows"], rng, parent)
            for c, v in cols.items():
                if len(v) != spec["rows"]:
                    raise ValueError(f"{name}.{c}: {len(v)} rows, the "
                                     f"configuration says {spec['rows']}")
            tables[name] = cols
        return tables[name]

    for name in cell.query.TABLES:
        gen(name)
    return {t: tables[t] for t in cell.query.TABLES}


_DECIMAL = re.compile(r"^decimal\((\d+),(\d+)\)$")


def engine_type(spelling: str):
    from spark_rapids_tpu import types as T

    m = _DECIMAL.match(spelling)
    if m:
        return T.DecimalType(int(m.group(1)), int(m.group(2)))
    try:
        return {"int": T.INT, "long": T.LONG, "date": T.DATE}[spelling]
    except KeyError:
        raise ValueError(f"datagen TYPES: unknown type {spelling!r}") from None


def _resident_frame(session, cols: dict, spellings):
    """A DataFrame over host columns (bench._df): with
    spark.rapids.tpu.scan.cacheDeviceBatches the first collect uploads
    them and they stay on the device."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import HostColumn
    from spark_rapids_tpu.plan.nodes import LocalTableScan
    from spark_rapids_tpu.session import DataFrame

    kinds = [engine_type(s) for s in spellings]
    host = [HostColumn.from_numpy(np.ascontiguousarray(v), t)
            for v, t in zip(cols.values(), kinds)]
    schema = T.StructType([T.StructField(name, t, False)
                           for name, t in zip(cols, kinds)])
    return DataFrame(LocalTableScan(host, schema), session)


def _parquet_frame(session, cols: dict, path: str, options: dict):
    import pyarrow as pa
    import pyarrow.parquet as pq

    pq.write_table(pa.table(cols), path, **options)
    return session.read.parquet(path)


def make_frames(cell, session, tables: dict, data_dir: str) -> dict:
    """{table: DataFrame} as the traffic's residency says.  ``data_dir``
    holds the parquet files of the parquet residency."""
    frames = {}
    for name, cols in tables.items():
        if cell.traffic["residency"] == "resident":
            frames[name] = _resident_frame(
                session, cols, cell.generators[name].TYPES)
        else:
            os.makedirs(data_dir, exist_ok=True)
            frames[name] = _parquet_frame(
                session, cols, os.path.join(data_dir, name + ".parquet"),
                cell.traffic.get("parquet", {}))
    return frames


def _walk(root):
    yield root
    for c in getattr(root, "children", []):
        yield from _walk(c)


def check_plan(cell, df) -> None:
    """The executed plan is all-TPU and has the shape the configuration
    and the traffic name (exec classes by name, as chip_smoke.py asserts
    them): a cell must run what its ``why`` says."""
    from spark_rapids_tpu.exec.base import TpuExec

    root, meta = df._planned()
    pretty = root.pretty() if hasattr(root, "pretty") else repr(root)
    if not isinstance(root, TpuExec):
        raise AssertionError(f"{cell.name}: plan root is not a TPU exec")
    not_on_tpu = meta.explain(only_fallback=True) if meta is not None else ""
    if not_on_tpu:
        raise AssertionError(f"{cell.name}: CPU nodes in the plan:\n"
                             f"{not_on_tpu}")
    seen: dict = {}
    for node in _walk(root):
        for klass in type(node).__mro__:
            seen[klass.__name__] = seen.get(klass.__name__, 0) + 1
    plan = cell.plan
    for name in plan["expect"]:
        if not seen.get(name):
            raise AssertionError(f"{cell.name}: no {name} in\n{pretty}")
    for name in plan["forbid"]:
        if seen.get(name):
            raise AssertionError(f"{cell.name}: {name} in\n{pretty}")
    for name, n in plan["count"].items():
        if seen.get(name, 0) != n:
            raise AssertionError(
                f"{cell.name}: {seen.get(name, 0)} {name}, not {n}, in\n{pretty}")
    want = plan.get("join_decision")
    if want is not None:
        decisions = [getattr(n, "decision", None) for n in _walk(root)
                     if type(n).__name__ == "TpuAdaptiveJoinExec"]
        if not decisions or not all((d or "").startswith(want)
                                    for d in decisions):
            raise AssertionError(
                f"{cell.name}: adaptive join decided {decisions}, "
                f"not {want!r}, in\n{pretty}")
