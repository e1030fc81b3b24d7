"""Composable seeded random data generators.

Reference analog: integration_tests/src/main/python/data_gen.py (IntegerGen,
LongGen, DoubleGen w/ special values, StringGen, DecimalGen, DateGen,
TimestampGen, BooleanGen, NullGen; nullable wrappers; seeded determinism).
The generator zoo is the backbone of the differential harness: wide value
coverage (boundaries, NaN/inf, nulls) with reproducible seeds.
"""
from __future__ import annotations

import datetime
import math
import random
import string as _string
from decimal import Decimal
from typing import List, Optional

from spark_rapids_tpu import types as T

DEFAULT_SEED = 20260729


class DataGen:
    def __init__(self, data_type: T.DataType, nullable: bool = True,
                 null_prob: float = 0.08):
        self.data_type = data_type
        self.nullable = nullable
        self.null_prob = null_prob if nullable else 0.0

    def __repr__(self):
        """What defines the generator, never an address: a test id made
        of it is the same in every process and every run."""
        parts = [self.data_type.simpleString]
        if not self.nullable:
            parts.append("not null")
        # bounds or lengths, for the classes a file may hold twice
        if hasattr(self, "lo"):
            parts.append(f"{self.lo}..{self.hi}")
        if hasattr(self, "min_len"):
            parts.append(f"len {self.min_len}..{self.max_len}")
        return f"{type(self).__name__}({', '.join(parts)})"

    def gen_value(self, rng: random.Random):
        raise NotImplementedError

    def gen(self, rng: random.Random):
        if self.nullable and rng.random() < self.null_prob:
            return None
        return self.gen_value(rng)

    def with_nullable(self, nullable: bool) -> "DataGen":
        import copy

        g = copy.copy(self)
        g.nullable = nullable
        g.null_prob = g.null_prob if nullable else 0.0
        return g


class _IntLike(DataGen):
    def __init__(self, data_type, lo, hi, special, nullable=True,
                 null_prob=0.08):
        super().__init__(data_type, nullable, null_prob)
        self.lo, self.hi = lo, hi
        self.special = special

    def gen_value(self, rng):
        if rng.random() < 0.1:
            return rng.choice(self.special)
        return rng.randint(self.lo, self.hi)


def ByteGen(nullable=True):
    return _IntLike(T.BYTE, -128, 127, [-128, -1, 0, 1, 127], nullable)


def ShortGen(nullable=True):
    return _IntLike(T.SHORT, -(2**15), 2**15 - 1,
                    [-(2**15), -1, 0, 1, 2**15 - 1], nullable)


def IntegerGen(nullable=True, min_val=None, max_val=None, null_prob=0.08):
    lo = min_val if min_val is not None else -(2**31)
    hi = max_val if max_val is not None else 2**31 - 1
    special = [v for v in [lo, -1, 0, 1, hi] if lo <= v <= hi]
    return _IntLike(T.INT, lo, hi, special, nullable, null_prob)


def LongGen(nullable=True, min_val=None, max_val=None, null_prob=0.08):
    lo = min_val if min_val is not None else -(2**63)
    hi = max_val if max_val is not None else 2**63 - 1
    special = [v for v in [lo, -1, 0, 1, hi] if lo <= v <= hi]
    return _IntLike(T.LONG, lo, hi, special, nullable, null_prob)


class BooleanGen(DataGen):
    def __init__(self, nullable=True, null_prob=0.08):
        super().__init__(T.BOOLEAN, nullable, null_prob)

    def gen_value(self, rng):
        return rng.random() < 0.5


class DoubleGen(DataGen):
    def __init__(self, nullable=True, no_nans=False, min_exp=-30, max_exp=30,
                 null_prob=0.08):
        super().__init__(T.DOUBLE, nullable, null_prob)
        self.no_nans = no_nans
        self.min_exp, self.max_exp = min_exp, max_exp

    def gen_value(self, rng):
        r = rng.random()
        if r < 0.08:
            choices = [0.0, -0.0, 1.0, -1.0]
            if not self.no_nans:
                choices += [math.nan, math.inf, -math.inf]
            return rng.choice(choices)
        m = rng.uniform(-1.0, 1.0)
        e = rng.randint(self.min_exp, self.max_exp)
        return m * (10.0 ** e)


class FloatGen(DoubleGen):
    def __init__(self, nullable=True, no_nans=False):
        super().__init__(nullable, no_nans, -10, 10)
        self.data_type = T.FLOAT

    def gen_value(self, rng):
        import struct

        v = super().gen_value(rng)
        return struct.unpack("f", struct.pack("f", v))[0]


class DecimalGen(DataGen):
    def __init__(self, precision=10, scale=2, nullable=True,
                 full_range=False):
        super().__init__(T.DecimalType(precision, scale), nullable)
        self.precision, self.scale = precision, scale
        self.full_range = full_range

    def gen_value(self, rng):
        # default: leave headroom for aggregation tests; full_range exercises
        # the whole precision (decimal128 limb paths need >18-digit values)
        digits = self.precision if self.full_range else min(self.precision, 15)
        unscaled = rng.randint(-(10**digits - 1), 10**digits - 1)
        return Decimal(unscaled).scaleb(-self.scale)


class ArrayGen(DataGen):
    """Arrays of primitive elements (device layout: padded list column)."""

    def __init__(self, elem_gen, min_len=0, max_len=6, nullable=True,
                 elem_null_prob=0.1):
        super().__init__(T.ArrayType(elem_gen.data_type), nullable)
        self.elem_gen = elem_gen
        self.min_len, self.max_len = min_len, max_len
        self.elem_null_prob = elem_null_prob

    def gen_value(self, rng):
        ln = rng.randint(self.min_len, self.max_len)
        return [None if rng.random() < self.elem_null_prob
                else self.elem_gen.gen_value(rng) for _ in range(ln)]


class StringGen(DataGen):
    def __init__(self, pattern: Optional[str] = None, nullable=True,
                 min_len=0, max_len=20, charset=None):
        super().__init__(T.STRING, nullable)
        self.min_len, self.max_len = min_len, max_len
        self.charset = charset or (_string.ascii_letters + _string.digits
                                   + " _-.")

    def gen_value(self, rng):
        n = rng.randint(self.min_len, self.max_len)
        return "".join(rng.choice(self.charset) for _ in range(n))


class DateGen(DataGen):
    def __init__(self, nullable=True,
                 start=datetime.date(1940, 1, 1),
                 end=datetime.date(2100, 12, 31)):
        super().__init__(T.DATE, nullable)
        self.start_days = (start - datetime.date(1970, 1, 1)).days
        self.end_days = (end - datetime.date(1970, 1, 1)).days

    def gen_value(self, rng):
        return (datetime.date(1970, 1, 1) + datetime.timedelta(
            days=rng.randint(self.start_days, self.end_days)))


class TimestampGen(DataGen):
    def __init__(self, nullable=True, min_us=None, max_us=None):
        super().__init__(T.TIMESTAMP, nullable)
        self.min_us = (min_us if min_us is not None
                       else -30610224000 * 1_000_000 // 1000)
        self.max_us = max_us if max_us is not None else 4102444800 * 1_000_000

    @staticmethod
    def ns_safe(nullable=True):
        """Range representable as int64 nanoseconds (1677-2262) — what ORC
        and parquet-ns can round-trip."""
        return TimestampGen(nullable, min_us=-9_223_372_036_854_000,
                            max_us=9_223_372_036_854_000)

    def gen_value(self, rng):
        us = rng.randint(self.min_us, self.max_us)
        return (datetime.datetime(1970, 1, 1,
                                  tzinfo=datetime.timezone.utc)
                + datetime.timedelta(microseconds=us))


class NullGen(DataGen):
    def __init__(self):
        super().__init__(T.NULL, True, 1.0)

    def gen_value(self, rng):
        return None


class JsonGen(DataGen):
    """Random JSON documents with nested objects/arrays, escapes, unicode,
    and occasional malformed docs (reference: json_test.py gens)."""

    def __init__(self, nullable=True, max_depth=2, malformed_prob=0.08):
        super().__init__(T.STRING, nullable)
        self.max_depth = max_depth
        self.malformed_prob = malformed_prob

    def _value(self, rng, depth):
        r = rng.random()
        if depth > 0 and r < 0.22:
            return {f"k{i}": self._value(rng, depth - 1)
                    for i in range(rng.randint(0, 3))}
        if depth > 0 and r < 0.38:
            return [self._value(rng, depth - 1)
                    for _ in range(rng.randint(0, 3))]
        r = rng.random()
        if r < 0.25:
            return rng.randint(-10**9, 10**9)
        if r < 0.40:
            return round(rng.uniform(-1000, 1000), 4)
        if r < 0.55:
            return rng.choice([True, False])
        if r < 0.62:
            return None
        n = rng.randint(0, 10)
        chars = 'abXY01 "\\\n\t\ré€語'
        return "".join(rng.choice(chars) for _ in range(n))

    def gen_value(self, rng):
        import json as _json

        if rng.random() < self.malformed_prob:
            return rng.choice(['not json', '{"a":', '', '[1,2', '{"a" 1}',
                               '{"a": }'])
        doc = {}
        for k in ("a", "b", "c")[:rng.randint(0, 3)]:
            doc[k] = self._value(rng, self.max_depth)
        compact = rng.random() < 0.7
        return _json.dumps(
            doc, separators=(",", ":") if compact else (", ", ": "),
            ensure_ascii=False)


class SetValuesGen(DataGen):
    """Draw from a fixed set (for skewed keys etc.)."""

    def __init__(self, data_type, values: List, nullable=True):
        super().__init__(data_type, nullable)
        self.values = values

    def gen_value(self, rng):
        return rng.choice(self.values)


def gen_df(session, gens: List, names: Optional[List[str]] = None,
           length: int = 512, seed: int = DEFAULT_SEED):
    """Build a DataFrame of `length` rows from generator list.

    Reference analog: data_gen.py gen_df(spark, gen_list)."""
    rng = random.Random(seed)
    names = names or [f"c{i}" for i in range(len(gens))]
    data = {}
    for name, g in zip(names, gens):
        data[name] = [g.gen(rng) for _ in range(length)]
    schema = T.StructType([
        T.StructField(n, g.data_type, g.nullable)
        for n, g in zip(names, gens)])
    return session.create_dataframe(data, schema)


# ---------------------------------------------------------------------------
# corrupt-file generators (ISSUE 5): deterministic on-disk damage for the
# I/O fault-domain matrix tests and tools/run_chaos.py --corrupt-inputs
# ---------------------------------------------------------------------------

def write_multifile_dataset(dirpath, fmt: str, n_files: int = 4,
                            rows_per_file: int = 50,
                            seed: int = DEFAULT_SEED) -> List[str]:
    """N standalone files of one scan-able schema (i: long, v: double,
    s: string) -> ordered path list.  Values are globally unique across
    files so surviving-row counts are unambiguous."""
    import os

    import pyarrow as pa

    os.makedirs(str(dirpath), exist_ok=True)
    rng = random.Random(seed)
    paths = []
    for fi in range(n_files):
        base = fi * rows_per_file
        tbl = pa.table({
            "i": list(range(base, base + rows_per_file)),
            "v": [round(rng.uniform(-100, 100), 6)
                  for _ in range(rows_per_file)],
            "s": [f"r{base + j}" for j in range(rows_per_file)],
        })
        path = os.path.join(str(dirpath), f"part-{fi:03d}.{fmt}")
        if fmt == "parquet":
            import pyarrow.parquet as pq

            pq.write_table(tbl, path)
        elif fmt == "orc":
            import pyarrow.orc as paorc

            paorc.write_table(tbl, path)
        elif fmt == "avro":
            from spark_rapids_tpu.io.avro import write_avro_file

            schema = {"type": "record", "name": "row", "fields": [
                {"name": "i", "type": "long"},
                {"name": "v", "type": "double"},
                {"name": "s", "type": "string"}]}
            write_avro_file(path, schema, tbl.to_pylist())
        elif fmt == "csv":
            with open(path, "w") as f:
                f.write("i,v,s\n")
                for r in tbl.to_pylist():
                    f.write(f"{r['i']},{r['v']},{r['s']}\n")
        else:
            raise NotImplementedError(fmt)
        paths.append(path)
    return paths


def corrupt_truncate(path: str, keep_frac: float = 0.6) -> str:
    """Cut the file short (drops the parquet footer / ORC postscript /
    avro sync tail) — the classic mid-upload truncation."""
    with open(path, "rb") as f:
        data = f.read()
    keep = max(int(len(data) * keep_frac), 1)
    with open(path, "wb") as f:
        f.write(data[:keep])
    return path


def corrupt_flip(path: str, offset: Optional[int] = None,
                 nbytes: int = 16) -> str:
    """Flip a byte run.  Default offset targets the metadata tail
    (footer / postscript / sync marker), where single-bit damage is
    reliably fatal to every container format; pyarrow does not verify
    data-page checksums on read, so mid-page flips may decode silently."""
    with open(path, "rb") as f:
        data = bytearray(f.read())
    if offset is None:
        offset = max(len(data) - 24, 0)
    for i in range(offset, min(offset + nbytes, len(data))):
        data[i] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))
    return path


def corrupt_garbage(path: str, offset: int = 0, nbytes: int = 24) -> str:
    """Overwrite a byte run with NUL/0xFF garbage — the text-format
    corruption shape (undecodable bytes; a bit-flipped ASCII row would
    still parse permissively)."""
    with open(path, "rb") as f:
        data = bytearray(f.read())
    junk = (b"\x00\xff" * ((nbytes + 1) // 2))[:nbytes]
    data[offset:offset + len(junk)] = junk
    with open(path, "wb") as f:
        f.write(bytes(data))
    return path


def corrupt_delete(path: str) -> str:
    """The file vanished between planning and read (ignoreMissingFiles
    territory)."""
    import os

    os.remove(path)
    return path


def write_schema_drifted(path: str, fmt: str, rows: int = 10,
                         seed: int = DEFAULT_SEED) -> str:
    """Overwrite ``path`` with a file whose column ``i`` was renamed —
    the per-file SchemaMismatch shape (pyarrow: 'No match for FieldRef'
    / 'Invalid column selected')."""
    import pyarrow as pa

    rng = random.Random(seed)
    tbl = pa.table({
        "i_renamed": list(range(rows)),
        "v": [round(rng.uniform(-100, 100), 6) for _ in range(rows)],
        "s": [f"d{j}" for j in range(rows)],
    })
    if fmt == "parquet":
        import pyarrow.parquet as pq

        pq.write_table(tbl, path)
    elif fmt == "orc":
        import pyarrow.orc as paorc

        paorc.write_table(tbl, path)
    else:
        raise NotImplementedError(fmt)
    return path


# canonical generator sets, as the reference groups them
numeric_gens = [ByteGen(), ShortGen(), IntegerGen(), LongGen(),
                FloatGen(), DoubleGen()]
integral_gens = [ByteGen(), ShortGen(), IntegerGen(), LongGen()]
decimal_gens = [DecimalGen(7, 3), DecimalGen(12, 2), DecimalGen(18, 6)]
string_gens = [StringGen(), StringGen(min_len=1, max_len=5)]
date_gens = [DateGen()]
all_basic_gens = (numeric_gens + [BooleanGen(), StringGen(), DateGen(),
                                  TimestampGen()])
