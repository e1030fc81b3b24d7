"""The yardstick: manifest loader, data and frames, the closed loop, the
statistics, the comparison that decides ``correct``, the trace reduction
and the table of peaks.  Nothing here names a workload."""
