"""XLA programs launched per collect (perfcounters programs_launched)."""


def read(run):
    return run.counters["programs_launched"] / run.window.collects
