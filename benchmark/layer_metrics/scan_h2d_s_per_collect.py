"""Wall of the host-to-device upload of the scan's batches per collect,
in s: the inclusive time of ``srt.scan.h2d`` (pad and ``device_put``; on
the scan's staging thread where the prefetch ring is on).
From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import inclusive


def read(run):
    return inclusive(run, ("srt.scan.h2d",), 1e9)
