"""Device-side Parquet column assembly.

Reference analog: SURVEY.md §3.4's device half — the reference hands
host-stitched row-group bytes to cuDF's decode kernels; here the host half
(io/parquet_native.py) parses footers/page headers/run headers and the
Pallas kernels (pallas/decode.py) unpack bits, expand runs, and gather
dictionaries on device.  Unsupported features raise _Unsupported and the
scan silently falls back to the pyarrow host decode (the reference's
hybrid-scan stance).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (
    DEFAULT_ROW_BUCKETS,
    DeviceColumn,
    round_up_bucket,
)
from spark_rapids_tpu.io.parquet_native import (
    CODEC_SNAPPY,
    CODEC_UNCOMPRESSED,
    ENC_PLAIN,
    ENC_PLAIN_DICT,
    ENC_RLE_DICT,
    TYPE_BOOLEAN,
    TYPE_BYTE_ARRAY,
    TYPE_FLOAT,
    TYPE_INT32,
    TYPE_INT64,
    _PLAIN_DTYPES,
    _Unsupported,
    read_column_pages,
    read_footer,
    split_hybrid_runs,
)
from spark_rapids_tpu.pallas.decode import (
    MAX_BIT_WIDTH,
    expand_runs,
    expand_runs_dev,
    expand_runs_host,
    unpack_bitpacked,
    unpack_bitpacked_dev,
)
from spark_rapids_tpu.pallas.decompress import (
    TooFragmented,
    raw_to_device,
    snappy_to_device,
)


class _CompressedUnsupported(Exception):
    """Page/chunk outside the compressed-transfer subset: the caller
    re-decodes the CHUNK through the decoded-transfer device path
    (``chunk_decode_fallbacks``) — correctness is identical, only the
    link bytes differ."""

_OK_TYPES = {
    TYPE_INT32: (T.IntegerType, T.DateType, T.ByteType, T.ShortType,
                 T.DecimalType),
    TYPE_INT64: (T.LongType, T.TimestampType, T.DecimalType),
    TYPE_FLOAT: (T.FloatType,),
    5: (T.DoubleType,),          # TYPE_DOUBLE
    TYPE_BOOLEAN: (T.BooleanType,),
    TYPE_BYTE_ARRAY: (T.StringType,),
}


def _check_field(info, dt: T.DataType):
    ok = _OK_TYPES.get(info.ptype)
    if ok is None or not isinstance(dt, ok):
        raise _Unsupported(
            f"column {info.name}: parquet type {info.ptype} as "
            f"{dt.simpleString}")
    if isinstance(dt, T.DecimalType) and dt.is_128:
        raise _Unsupported("decimal128 device decode")


def expand_defined(page):
    """Definition levels -> (defined bool array, ndef) — host expansion of
    the tiny 1-bit streams (shared by numeric + string pages and the ORC
    reader's PRESENT handling)."""
    from spark_rapids_tpu.perfcounters import count_h2d

    n = page.num_values
    if page.def_runs is not None:
        levels = expand_runs_host(page.def_runs, page.def_buf, n, 1)
        defined_np = levels.astype(np.bool_)
        count_h2d(defined_np.nbytes)
        return jnp.asarray(defined_np), int(defined_np.sum())
    return jnp.ones(n, jnp.bool_), n


def _page_dev_region(page) -> jax.Array:
    """Ship the page's STORED bytes across the link and return the
    decompressed region as a device uint8 array (the compressed-transfer
    entry point).  Raises for codecs outside the device-decompressible
    subset (zstd) or streams whose gather resolution has no transport
    win — the chunk then falls back to the decoded-transfer path."""
    if page.raw_values is None:
        raise _CompressedUnsupported("no stored-page bytes recorded")
    if page.raw_codec == CODEC_UNCOMPRESSED:
        return raw_to_device(page.raw_values)
    if page.raw_codec == CODEC_SNAPPY:
        # what the decoded-transfer path would ship for this page: the
        # value payload plus (when the levels live inside the region)
        # the expanded definition-level bool vector
        decoded_cost = len(page.value_buf) + (
            page.num_values if page.def_off is not None else 0)
        return snappy_to_device(page.raw_values, decoded_cost)
    raise _CompressedUnsupported(
        f"codec {page.raw_codec} has no device decompressor")


def _expand_defined_dev(page, dev_region):
    """Compressed-path twin of :func:`expand_defined`: the 1-bit levels
    expand from the DEVICE-resident decompressed region (v1 pages carry
    them inside it), so no decoded bool vector crosses the link.  The
    defined COUNT comes from the host-parsed runs — the host already
    holds the decompressed structure, so this costs neither a transfer
    nor a device sync."""
    from spark_rapids_tpu.perfcounters import count_h2d

    n = page.num_values
    if page.def_runs is None:
        return jnp.ones(n, jnp.bool_), n
    levels = expand_runs_host(page.def_runs, page.def_buf, n, 1)
    ndef = int(levels.astype(np.bool_).sum())
    if page.def_off is not None:
        lv = expand_runs_dev(page.def_runs, dev_region, page.def_off,
                             n, 1)
        return lv.astype(jnp.bool_), ndef
    # v2: levels sit uncompressed OUTSIDE the values region — host
    # expansion, decoded bool vector on the link (counted)
    defined_np = levels.astype(np.bool_)
    count_h2d(defined_np.nbytes)
    return jnp.asarray(defined_np), ndef


def scatter_present(vals, defined, ndef, n):
    """Compacted present values -> row positions (null rows zero-filled)."""
    if ndef == n:
        return vals
    pos = jnp.cumsum(defined.astype(jnp.int32)) - 1
    safe = jnp.clip(pos, 0, max(ndef - 1, 0))
    return jnp.where(defined, vals[safe],
                     jnp.zeros((), vals.dtype))


def _decode_string_page(page, cp, ndict):
    """Dictionary-encoded BYTE_ARRAY page -> (row dict indices, validity).

    The small dict page parsed on host; the per-ROW index stream expands
    on device and the chars gather happens once per file (TPU-shaped: a
    dense (rows, width) gather from the resident dict matrix)."""
    n = page.num_values
    if page.encoding not in (ENC_PLAIN_DICT, ENC_RLE_DICT):
        raise _Unsupported("PLAIN byte_array data page (host-walk only)")
    defined, ndef = expand_defined(page)
    if page.index_bit_width > MAX_BIT_WIDTH:
        raise _Unsupported(f"dictionary index width {page.index_bit_width}")
    runs = split_hybrid_runs(page.value_buf, page.index_bit_width, ndef)
    idx = expand_runs(runs, page.value_buf, ndef, page.index_bit_width)
    idx = jnp.clip(idx.astype(jnp.int32), 0, max(ndict - 1, 0))
    return scatter_present(idx, defined, ndef, n), defined


def _decode_string_page_compressed(page, cp, ndict):
    """Compressed-transfer twin of :func:`_decode_string_page`: the
    index stream expands from the device-decompressed page region.
    PLAIN byte_array pages stay outside the subset (their interleaved
    lengths force the host walk) — the chunk falls back."""
    n = page.num_values
    if page.encoding not in (ENC_PLAIN_DICT, ENC_RLE_DICT):
        raise _CompressedUnsupported(
            "PLAIN byte_array page (host-walk only)")
    if page.index_bit_width > MAX_BIT_WIDTH:
        raise _Unsupported(f"dictionary index width {page.index_bit_width}")
    dev = _page_dev_region(page)
    defined, ndef = _expand_defined_dev(page, dev)
    runs = split_hybrid_runs(page.value_buf, page.index_bit_width, ndef)
    idx = expand_runs_dev(runs, dev, page.value_off, ndef,
                          page.index_bit_width)
    idx = jnp.clip(idx.astype(jnp.int32), 0, max(ndict - 1, 0))
    return scatter_present(idx, defined, ndef, n), defined


def _decode_plain_string_page(page):
    """PLAIN BYTE_ARRAY page -> (chars matrix, lens, identity indices,
    validity).  The interleaved (len, bytes) layout
    forces a sequential length walk (C kernel, host_kernels.cpp); the
    char gather into the padded matrix is one vectorized numpy pass and
    the matrix uploads once like a page-local dictionary."""
    from spark_rapids_tpu.native import plain_byte_array_lens

    n = page.num_values
    defined, ndef = expand_defined(page)
    lens = plain_byte_array_lens(page.value_buf, ndef)
    buf_np = np.frombuffer(page.value_buf, np.uint8)
    starts = (4 * (np.arange(ndef, dtype=np.int64) + 1)
              + np.concatenate([[0], np.cumsum(lens[:-1], dtype=np.int64)])
              if ndef else np.zeros(0, np.int64))
    w = max(int(lens.max()) if ndef else 1, 1)
    pos = starts[:, None] + np.arange(w, dtype=np.int64)[None, :]
    inside = np.arange(w, dtype=np.int32)[None, :] < lens[:, None]
    chars = np.where(inside,
                     buf_np[np.clip(pos, 0, max(len(buf_np) - 1, 0))],
                     0).astype(np.uint8)
    idx = scatter_present(jnp.arange(max(ndef, 1), dtype=jnp.int32)[:ndef]
                          if ndef else jnp.zeros(0, jnp.int32),
                          defined, ndef, n)
    return chars, lens, idx, defined


def _decode_page(page, info, dt: T.DataType, dictionary):
    """One data page -> (values (n,), validity (n,)) device arrays."""
    from spark_rapids_tpu.perfcounters import count_h2d

    n = page.num_values
    defined, ndef = expand_defined(page)
    sdt = T.storage_dtype(dt)
    if page.encoding in (ENC_PLAIN_DICT, ENC_RLE_DICT):
        if dictionary is None:
            raise _Unsupported("dictionary page missing")
        if page.index_bit_width > MAX_BIT_WIDTH:
            raise _Unsupported(
                f"dictionary index width {page.index_bit_width}")
        runs = split_hybrid_runs(page.value_buf, page.index_bit_width,
                                 ndef)
        idx = expand_runs(runs, page.value_buf, ndef,
                          page.index_bit_width)
        count_h2d(dictionary.nbytes)
        dict_dev = jnp.asarray(dictionary)
        vals = dict_dev[jnp.clip(idx.astype(jnp.int32), 0,
                                 max(len(dictionary) - 1, 0))]
    elif page.encoding == ENC_PLAIN:
        if info.ptype == TYPE_BOOLEAN:
            vals = unpack_bitpacked(
                np.frombuffer(page.value_buf, np.uint8), 1, ndef)
        else:
            np_dt = _PLAIN_DTYPES[info.ptype]
            host_vals = np.frombuffer(page.value_buf, np_dt, count=ndef)
            count_h2d(host_vals.nbytes)
            vals = jnp.asarray(host_vals)
    else:
        raise _Unsupported(f"encoding {page.encoding}")
    vals = vals.astype(sdt)
    return scatter_present(vals, defined, ndef, n), defined


def _decode_page_compressed(page, info, dt: T.DataType, dictionary):
    """Compressed-transfer twin of :func:`_decode_page`: the page's
    STORED bytes cross the link, decompress on device
    (pallas/decompress.py), and the value stream decodes from the
    device-resident region — bit-unpack + run expansion via the Pallas
    kernels, PLAIN numerics via a device bitcast."""
    from spark_rapids_tpu.perfcounters import count_h2d

    n = page.num_values
    dev = _page_dev_region(page)
    defined, ndef = _expand_defined_dev(page, dev)
    sdt = T.storage_dtype(dt)
    if page.encoding in (ENC_PLAIN_DICT, ENC_RLE_DICT):
        if dictionary is None:
            raise _Unsupported("dictionary page missing")
        if page.index_bit_width > MAX_BIT_WIDTH:
            raise _Unsupported(
                f"dictionary index width {page.index_bit_width}")
        runs = split_hybrid_runs(page.value_buf, page.index_bit_width,
                                 ndef)
        idx = expand_runs_dev(runs, dev, page.value_off, ndef,
                              page.index_bit_width)
        count_h2d(dictionary.nbytes)
        dict_dev = jnp.asarray(dictionary)
        vals = dict_dev[jnp.clip(idx.astype(jnp.int32), 0,
                                 max(len(dictionary) - 1, 0))]
    elif page.encoding == ENC_PLAIN:
        if info.ptype == TYPE_BOOLEAN:
            vals = unpack_bitpacked_dev(
                dev[page.value_off:], 1, ndef)
        else:
            np_dt = _PLAIN_DTYPES[info.ptype]
            isz = np.dtype(np_dt).itemsize
            lo = page.value_off
            region = dev[lo:lo + ndef * isz]
            if int(region.shape[0]) < ndef * isz:
                raise _Unsupported("PLAIN value region short")
            vals = jax.lax.bitcast_convert_type(
                region.reshape(ndef, isz) if ndef else
                region.reshape(0, isz), np_dt)
    else:
        raise _Unsupported(f"encoding {page.encoding}")
    vals = vals.astype(sdt)
    return scatter_present(vals, defined, ndef, n), defined


def read_parquet_device(path: str, schema: T.StructType,
                        row_buckets=DEFAULT_ROW_BUCKETS) -> ColumnarBatch:
    """One file -> one padded device batch via the Pallas decode path.
    Escaping errors carry ``file=<path>`` context (io/faults.py) so a
    decoder failure in a multi-file scan is attributable."""
    from spark_rapids_tpu.io.faults import file_context

    with file_context(path, "parquet", "device"):
        return _read_parquet_device(path, schema, row_buckets)


def _decode_string_chunk(f, cp, use_compressed: bool):
    """One string column chunk -> (vals, valids, dicts).

    dict-encoded pages share the row group's dictionary; PLAIN pages
    (incl. parquet's dict-overflow spill) carry page-local char matrices
    — entries appended in row order so the assembly's base offsets line
    up."""
    vals: List = []
    valids: List = []
    dicts: List = []
    pending_dict_rows = 0
    for page in cp.pages:
        if page.encoding in (ENC_PLAIN_DICT, ENC_RLE_DICT):
            if cp.dict_chars is None:
                raise _Unsupported(
                    f"column {cp.info.name}: dictionary page missing")
            ndict = cp.dict_chars.shape[0]
            if use_compressed:
                idx, ok = _decode_string_page_compressed(page, cp, ndict)
            else:
                idx, ok = _decode_string_page(page, cp, ndict)
            pending_dict_rows += page.num_values
        elif page.encoding == ENC_PLAIN:
            if use_compressed:
                raise _CompressedUnsupported(
                    "PLAIN byte_array page (host-walk only)")
            if pending_dict_rows:
                dicts.append((cp.dict_chars, cp.dict_lens,
                              pending_dict_rows))
                pending_dict_rows = 0
            chars, lens2, idx, ok = _decode_plain_string_page(page)
            dicts.append((chars, lens2, page.num_values))
        else:
            raise _Unsupported(f"byte_array encoding {page.encoding}")
        vals.append(idx)
        valids.append(ok)
    if pending_dict_rows:
        dicts.append((cp.dict_chars, cp.dict_lens, pending_dict_rows))
    return vals, valids, dicts


def _decode_numeric_chunk(f, info, cp, use_compressed: bool):
    vals: List = []
    valids: List = []
    for page in cp.pages:
        if use_compressed:
            v, ok = _decode_page_compressed(page, info, f.dataType,
                                            cp.dictionary)
        else:
            v, ok = _decode_page(page, info, f.dataType, cp.dictionary)
        vals.append(v)
        valids.append(ok)
    return vals, valids


def _compressed_transfer_on() -> bool:
    from spark_rapids_tpu.config import (PARQUET_COMPRESSED_TRANSFER,
                                         get_conf)

    return bool(get_conf().get(PARQUET_COMPRESSED_TRANSFER))


def _chunk_compressed_eligible(cp, is_string: bool) -> bool:
    """Metadata pre-pass: every page of the chunk must sit inside the
    compressed-transfer subset BEFORE any bytes ship — a mid-chunk
    unsupported page discovered after uploading its predecessors would
    pay the link twice (once compressed, once decoded on the retry)."""
    for page in cp.pages:
        if page.raw_values is None:
            return False
        if page.raw_codec not in (CODEC_UNCOMPRESSED, CODEC_SNAPPY):
            return False
        if is_string and page.encoding not in (ENC_PLAIN_DICT,
                                               ENC_RLE_DICT):
            return False
    return True


def _read_parquet_device(path: str, schema: T.StructType,
                         row_buckets=DEFAULT_ROW_BUCKETS) -> ColumnarBatch:
    from spark_rapids_tpu import perfcounters as PC

    with open(path, "rb") as f:
        data = f.read()
    groups, names = read_footer(data)
    wanted = schema.field_names()
    for w in wanted:
        if w not in names:
            raise _Unsupported(f"column {w} missing from file")
    total = sum(g.num_rows for g in groups)
    cap = round_up_bucket(max(total, 1), row_buckets)
    compressed = _compressed_transfer_on()
    per_field_vals: List[List] = [[] for _ in wanted]
    per_field_valid: List[List] = [[] for _ in wanted]
    # string columns: dict char matrices per (field, row-group)
    per_field_dicts: List[List] = [[] for _ in wanted]
    for g in groups:
        by_name = {c.name: c for c in g.columns}
        for fi, f in enumerate(schema.fields):
            info = by_name.get(f.name)
            if info is None:
                raise _Unsupported(f"column {f.name} missing in row group")
            _check_field(info, f.dataType)
            cp = read_column_pages(data, info, g.num_rows)
            # compressed transfer first, falling back PER CHUNK to the
            # decoded-transfer path when any page sits outside the
            # device-decompressible subset (zstd, PLAIN byte_array,
            # no-transport-win streams) — same bits, heavier link.
            # Statically-knowable ineligibility (codec/encoding) is
            # decided from the page headers before any bytes ship; the
            # try/except handles the data-dependent cases
            # (no-transport-win snappy streams)
            is_str = isinstance(f.dataType, T.StringType)
            use_comp = compressed and _chunk_compressed_eligible(
                cp, is_str)
            if compressed and not use_comp:
                PC.bump("chunk_decode_fallbacks")
            while True:
                try:
                    if isinstance(f.dataType, T.StringType):
                        vals, valids, dicts = _decode_string_chunk(
                            f, cp, use_comp)
                        per_field_dicts[fi].extend(dicts)
                    else:
                        vals, valids = _decode_numeric_chunk(
                            f, info, cp, use_comp)
                    break
                except (_CompressedUnsupported, TooFragmented):
                    if not use_comp:
                        raise
                    use_comp = False
                    PC.bump("chunk_decode_fallbacks")
            per_field_vals[fi].extend(vals)
            per_field_valid[fi].extend(valids)
    cols = []
    for fi, f in enumerate(schema.fields):
        vals = jnp.concatenate(per_field_vals[fi]) \
            if len(per_field_vals[fi]) > 1 else per_field_vals[fi][0]
        valid = jnp.concatenate(per_field_valid[fi]) \
            if len(per_field_valid[fi]) > 1 else per_field_valid[fi][0]
        valid_arr = jnp.zeros(cap, jnp.bool_).at[:valid.shape[0]].set(valid)
        if isinstance(f.dataType, T.StringType):
            cols.append(_assemble_string_col(
                f.dataType, per_field_dicts[fi], vals, valid_arr, cap))
            continue
        sdt = T.storage_dtype(f.dataType)
        data_arr = jnp.zeros(cap, sdt).at[:vals.shape[0]].set(vals)
        cols.append(DeviceColumn(f.dataType, valid_arr, data=data_arr))
    return ColumnarBatch(cols, total, schema)


def _assemble_string_col(dt, dicts, idx, valid_arr, cap):
    """Row dict-indices + per-row-group dictionaries -> one padded string
    column: stack the dictionaries (offsetting indices per row group) and
    gather the char matrix on device."""
    from spark_rapids_tpu.columnar.column import (DEFAULT_WIDTH_BUCKETS,
                                                  round_up_bucket)

    from spark_rapids_tpu.perfcounters import count_h2d

    w = round_up_bucket(
        max(max(d[0].shape[1] for d in dicts), 1), DEFAULT_WIDTH_BUCKETS)
    parts = []
    lens = []
    base = 0
    bases = []
    for chars, ln, nrows in dicts:
        padded = np.zeros((chars.shape[0], w), np.uint8)
        padded[:, :chars.shape[1]] = chars
        parts.append(padded)
        lens.append(ln)
        bases.append((base, nrows))
        base += chars.shape[0]
    chars_np = np.concatenate(parts, axis=0)
    lens_np = np.concatenate(lens)
    count_h2d(chars_np.nbytes + lens_np.nbytes)
    all_chars = jnp.asarray(chars_np)
    all_lens = jnp.asarray(lens_np)
    # offset each row group's indices into the stacked dictionary
    offs = np.zeros(int(idx.shape[0]), np.int32)
    pos = 0
    for b, nrows in bases:
        offs[pos:pos + nrows] = b
        pos += nrows
    count_h2d(4 * int(idx.shape[0]))
    gidx = idx + jnp.asarray(offs[: int(idx.shape[0])])
    full_idx = jnp.zeros(cap, jnp.int32).at[: gidx.shape[0]].set(gidx)
    chars = all_chars[full_idx]
    lengths = jnp.where(valid_arr, all_lens[full_idx], 0).astype(jnp.int32)
    return DeviceColumn(dt, valid_arr, chars=chars, lengths=lengths)
