"""What the readers of the folded span table share (no metric of its own:
no entry of BENCHMARK.json has this name).

The table is ``perfcounters.span``'s, in ``run.counters``:
``span_n|<path>``, ``span_ns|<path>`` (inclusive) and
``span_self_ns|<path>``, ``<path>`` being the names of the open spans
from the thread's outermost joined by ``/``.  Each function returns None
for a program without spans (no ``span_n|`` key at all), and 0 where the
spans asked for never opened."""


def _paths(run, kind):
    c = run.counters
    if not any(k.startswith("span_n|") for k in c):
        return None
    return [(k.split("|", 1)[1].split("/"), v) for k, v in c.items()
            if k.startswith(kind + "|")]


def inclusive(run, names, per):
    """Inclusive time of the spans ``names`` (summed over the paths that
    end in one of them) per collect, in units of ``per`` ns."""
    paths = _paths(run, "span_ns")
    if paths is None:
        return None
    ns = sum(v for path, v in paths if path[-1] in names)
    return ns / run.window.collects / per


def self_time(run, keep, per):
    """Self time of the paths that ``keep(path)`` holds for (``path`` a
    list of span names) per collect, in units of ``per`` ns."""
    paths = _paths(run, "span_self_ns")
    if paths is None:
        return None
    ns = sum(v for path, v in paths if keep(path))
    return ns / run.window.collects / per
