"""Wall inside the counted host syncs per collect, in ms: the inclusive
time of ``srt.sync``, the one span around every blocking device-to-host
read (the ``ArrayImpl`` dunder patch and ``sync_get``).  It holds the
device time the host waited for and the copy: less
``device_ms_per_collect`` it bounds the launch and completion latency
plus the copy.  The two are one await in the runtime and are not timed
apart (a ``block_until_ready`` ahead of the read cost 0.3 ms a collect).
From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import inclusive


def read(run):
    return inclusive(run, ("srt.sync",), 1e6)
