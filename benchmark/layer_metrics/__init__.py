"""One file a per-layer metric: ``read(run) -> number or None``.

``run`` (benchmark/run.py ``Run``) holds the traced run's ``window``
(harness/stats.py Window), ``counters`` (perfcounters delta over the
window), ``setup_counters`` (over set-up), ``trace`` (harness/trace.py
summary, or None), ``memory_peak_bytes``, ``min_bytes`` (the query's, or
None) and ``peaks`` (harness/peaks.py entry of the device).  A reader that
finds nothing to read returns None and the metric is left out of the
line; it never returns 0 for a share of a roofline or of a peak."""
