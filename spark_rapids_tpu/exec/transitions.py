"""CPU<->TPU transitions.

Reference analog: GpuRowToColumnarExec / GpuColumnarToRowExec /
HostColumnarToGpu (SURVEY.md §2.4 Transitions) — the device boundary of the
plan.  Here the CPU side is the oracle executor; transitions convert between
its CpuCols (host) and device ColumnarBatches.

TpuColumnarToRowExec is what the session's collect() drives; its device->host
copy is the analog of the reference's accelerated columnar-to-row kernel
(the padded layout makes the host-side conversion a memcpy per column).
"""
from __future__ import annotations

from typing import Iterator, List

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import HostColumn
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.perfcounters import span


class TpuRowToColumnarExec(TpuExec):
    """Wraps a CPU plan subtree; materializes it via the oracle and uploads
    batches to the device."""

    def __init__(self, cpu_plan, ansi: bool = False,
                 target_batch_rows: int = 1 << 20):
        super().__init__([])
        self.cpu_plan = cpu_plan
        self.ansi = ansi
        self.target_batch_rows = target_batch_rows

    @property
    def output(self):
        return self.cpu_plan.output

    def describe(self):
        return f"TpuRowToColumnar <- {self.cpu_plan.describe()}"

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.cpu.oracle import execute_cpu_plan

        cols, n = execute_cpu_plan(self.cpu_plan, ansi=self.ansi)
        host = [c.to_host() for c in cols]
        names = self.output.field_names()
        step = self.target_batch_rows

        for start in range(0, max(n, 1), step):
            end = min(start + step, n)
            chunk = [h.slice_rows(start, end) for h in host]
            yield self._count_output(
                ColumnarBatch.from_host_columns(chunk, names))
            if n == 0:
                break


class TpuColumnarToRowExec(TpuExec):
    """Device batches -> host rows (the top of every collected plan)."""

    def __init__(self, child: TpuExec):
        super().__init__([child])

    @property
    def output(self):
        return self.children[0].output

    def describe(self):
        return "TpuColumnarToRow"

    def execute_columnar(self):
        yield from self.children[0].execute_columnar()

    def collect_host(self) -> List[HostColumn]:
        """Materialize all batches to host columns."""
        import numpy as np

        with span("srt.execute"):
            batches = list(self.children[0].execute_columnar())
        if not batches:
            schema = self.output
            return [HostColumn.from_pylist([], f.dataType)
                    for f in schema.fields]
        from spark_rapids_tpu.config import (
            FUSION_COLLECT_SHRINK_MAX_WASTE, get_conf)

        waste_cap = get_conf().get(FUSION_COLLECT_SHRINK_MAX_WASTE)
        per_batch = [b.to_host_columns(max_shrink_waste_bytes=waste_cap)
                     for b in batches]
        out = [_concat_host([pb[ci] for pb in per_batch])
               for ci in range(len(per_batch[0]))]
        return out


def _concat_host(hs: List[HostColumn]) -> HostColumn:
    """Concatenate host columns of one schema slot (all column kinds)."""
    import numpy as np

    dtype = hs[0].dtype
    validity = np.concatenate([h.validity for h in hs])
    if hs[0].is_struct:
        kids = [_concat_host([h.children[k] for h in hs])
                for k in range(len(hs[0].children))]
        lengths = (np.concatenate([h.lengths for h in hs])
                   if hs[0].lengths is not None else None)
        return HostColumn(dtype, validity, lengths=lengths, children=kids)
    if hs[0].is_string_array:
        ew = max(h.chars.shape[1] for h in hs)
        w = max(h.chars.shape[2] for h in hs)
        nrows = len(validity)
        chars = np.zeros((nrows, ew, w), np.uint8)
        elens = np.zeros((nrows, ew), np.int32)
        ev = np.zeros((nrows, ew), np.bool_)
        lengths = np.concatenate([h.lengths for h in hs])
        off = 0
        for h in hs:
            k = len(h.lengths)
            chars[off:off + k, :h.chars.shape[1], :h.chars.shape[2]] = h.chars
            elens[off:off + k, :h.data.shape[1]] = h.data
            ev[off:off + k, :h.elem_valid.shape[1]] = h.elem_valid
            off += k
        return HostColumn(dtype, validity, chars=chars, data=elens,
                          lengths=lengths, elem_valid=ev)
    if hs[0].is_string:
        width = max(h.chars.shape[1] for h in hs)
        chars = np.zeros((len(validity), width), np.uint8)
        lengths = np.concatenate([h.lengths for h in hs])
        off = 0
        for h in hs:
            chars[off: off + len(h.lengths), : h.chars.shape[1]] = h.chars
            off += len(h.lengths)
        return HostColumn(dtype, validity, chars=chars, lengths=lengths)
    if hs[0].is_array:
        ew = max(h.data.shape[1] for h in hs)
        n = len(validity)
        data = np.zeros((n, ew), hs[0].data.dtype)
        ev = np.zeros((n, ew), np.bool_)
        lengths = np.concatenate([h.lengths for h in hs])
        off = 0
        for h in hs:
            k = len(h.lengths)
            data[off: off + k, : h.data.shape[1]] = h.data
            ev[off: off + k, : h.elem_valid.shape[1]] = h.elem_valid
            off += k
        return HostColumn(dtype, validity, data=data, lengths=lengths,
                          elem_valid=ev)
    data = np.concatenate([h.data for h in hs])
    return HostColumn(dtype, validity, data=data)
