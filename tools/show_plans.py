"""Print the planned exec trees of the bench queries."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def show(name, df):
    root, _ = df._planned()
    print(f"===== {name} =====")
    print(root.pretty())
    print()


def main():
    n = 1000
    li = bench.make_lineitem(n)
    ss = bench.make_store_sales(n)
    dd = bench.make_date_dim()
    sr = bench.make_store_returns(ss, n // 10)

    show("q6", bench.build_q6(bench._session(True, True), li))
    show("qa", bench.build_qa(bench._session(True, True), ss, dd))
    show("qb", bench.build_qb(bench._session(True, True), ss, sr))
    show("qc", bench.build_qc(bench._session(True, True), ss))


if __name__ == "__main__":
    main()
