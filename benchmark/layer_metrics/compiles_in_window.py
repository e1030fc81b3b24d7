"""XLA compiles inside the measured window, inline and background (AOT
pool) together.  Must read 0: harness/check.py holds the run to it."""


def read(run):
    return run.counters["compiles"] + run.counters["aot_compiles"]
