"""Compile wall during set-up, inline and background: seconds to minutes
in a checkout's first run, cache loads afterwards."""


def read(run):
    c = run.setup_counters
    return (c["compile_wall_ns"] + c["aot_compile_wall_ns"]) / 1e9
