"""Peak device memory of the process after the window, in GB
(memory_stats()["peak_bytes_in_use"] of the fullest chip)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
