"""The least time the chip could take to read the query's bytes
(queries/<query>.py min_bytes over the published HBM bandwidth) as a
share of the device time per collect.  Memory-bound by construction: the
queries here do a handful of integer operations a row."""


def read(run):
    if run.trace is None or run.min_bytes is None:
        return None
    device_s = run.trace["busy_s"] / run.trace["spans"]
    if device_s <= 0:
        return None
    least_s = run.min_bytes / (run.peaks["hbm_gb_per_s"] * 1e9)
    return 100.0 * least_s / device_s
