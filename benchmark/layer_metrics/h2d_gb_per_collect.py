"""Bytes uploaded host-to-device per collect, in GB (perfcounters
bytes_h2d): the file's decoded columns in the parquet residency, 0 once
the tables are resident."""


def read(run):
    return run.counters["bytes_h2d"] / run.window.collects / 1e9
