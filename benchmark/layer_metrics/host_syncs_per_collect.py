"""Blocking device-to-host reads per collect (perfcounters host_syncs)."""


def read(run):
    return run.counters["host_syncs"] / run.window.collects
