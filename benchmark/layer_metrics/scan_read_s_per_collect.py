"""Wall of reading and decoding the files per collect, in s: the
inclusive time of ``srt.scan.read`` (the host reader) and of
``srt.scan.device_decode`` (the decode on the device, where it is taken).
From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import inclusive


def read(run):
    return inclusive(run, ("srt.scan.read", "srt.scan.device_decode"), 1e9)
