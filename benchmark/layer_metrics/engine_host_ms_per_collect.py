"""The engine's own Python per collect, in ms: the self time of
``srt.collect`` and of every span beneath it that is neither a host
sync (``srt.sync``), a scan phase (``srt.scan.*``) nor a launch
(``srt.launch``), nor lies inside one.  That equals the inclusive time
of ``srt.collect`` minus the inclusive time of those spans, so with
``launch_ms_per_collect`` and ``sync_ms_per_collect`` it adds up to the
collect where no scan runs.
From the folded span table (``_spans.py``)."""
from benchmark.layer_metrics._spans import self_time


def _engine(path):
    return "srt.collect" in path and not any(
        p in ("srt.sync", "srt.launch") or p.startswith("srt.scan.")
        for p in path)


def read(run):
    return self_time(run, _engine, 1e6)
