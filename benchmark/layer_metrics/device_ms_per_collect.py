"""Device time per collect: the union of the device-op intervals in the
traced window over the collects in it."""


def read(run):
    if run.trace is None:
        return None
    return run.trace["busy_s"] / run.trace["spans"] * 1e3
