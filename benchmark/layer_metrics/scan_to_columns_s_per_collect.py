"""Wall of turning the arrow table into padded host columns per collect,
in s: the inclusive time of ``srt.scan.to_columns`` (on the scan's
staging thread where the prefetch ring is on).
From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import inclusive


def read(run):
    return inclusive(run, ("srt.scan.to_columns",), 1e9)
