"""Count jit launches / compiles / host syncs per bench query (CPU backend).

These counts are identical on every backend; only the per-event
latency differs.  Run: python tools/count_launches.py
Uses the framework's own perfcounters (spark_rapids_tpu/perfcounters.py).
"""
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu import perfcounters as PC

import bench

_T0 = [time.perf_counter()]


def snap(name):
    c = PC.snapshot()
    dt = time.perf_counter() - _T0[0]
    print(f"{name}: {dt:6.2f}s launches={c['programs_launched']} "
          f"compiles={c['compiles']} syncs={c['host_syncs']} "
          f"d2h={c['bytes_d2h'] / 1e6:.2f}MB "
          f"h2d={c['bytes_h2d'] / 1e6:.2f}MB "
          f"launch_wall={c['launch_wall_ns'] / 1e9:.2f}s", flush=True)
    PC.reset()
    _T0[0] = time.perf_counter()


def main():
    n = int(os.environ.get("ROWS", 100_000))
    li = bench.make_lineitem(n)
    ss = bench.make_store_sales(n)
    dd = bench.make_date_dim()
    sr = bench.make_store_returns(ss, n // 10)

    for name, build, args in [
        ("q6", bench.build_q6, (li,)),
        ("qa", bench.build_qa, (ss, dd)),
        ("qb", bench.build_qb, (ss, sr)),
        ("qc", bench.build_qc, (ss,)),
    ]:
        df = build(bench._session(True, True), *args)
        PC.reset()
        _T0[0] = time.perf_counter()
        df.collect()
        snap(f"{name} first")
        df.collect()
        snap(f"{name} repeat")


if __name__ == "__main__":
    main()
