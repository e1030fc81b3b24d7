"""Units the host reader handed on per collect (counter ``scan_units``:
a parquet file read by runs of whole row groups gives one unit a run,
any other file one unit; ``io/scan.py`` ``_host_tables``).  Beside it
``scan_files_streamed`` counts the files cut into more than one unit.
None for a program that does not count units."""


def read(run):
    units = run.counters.get("scan_units")
    if units is None:
        return None
    return units / run.window.collects
