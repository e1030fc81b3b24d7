"""Wall inside the calls of the jitted programs per collect, in ms:
the inclusive time of ``srt.launch`` (dispatch; a launch is
asynchronous, so the device's work shows in the sync that waits for it).
From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import inclusive


def read(run):
    return inclusive(run, ("srt.launch",), 1e6)
