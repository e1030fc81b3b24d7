"""Real-TPU test rung (SURVEY.md §4 premerge analog).

Runs a tagged subset of the differential suite on the real chip
(SRT_TEST_ON_TPU=1): the Pallas parquet decode kernel (multiple bit
widths via the codec/dict matrix), decimal128 limb arithmetic, a string-
kernel slice, and a window slice.  Float64-heavy tests stay off the rung
(v5e f64 emulation breaks exact differential compares — conftest note).

The chip belongs to ONE process: this parent starts the pytest child
that uses it and must itself never import jax or spark_rapids_tpu (a
parent that touched jax would hold the chip and the child would fail or
hang).  Writes chiprun_out/TPU_TESTS.json.
"""
import json
import os
import subprocess
import sys
import time

SUBSET = [
    # round-3 core: pallas parquet decode matrix, decimal128, strings,
    # window, groupby
    "tests/test_parquet_device.py",
    "tests/test_decimal128.py",
    "tests/test_string.py::test_length_upper_lower_trim",
    "tests/test_string.py::test_substring",
    "tests/test_string.py::test_concat",
    "tests/test_string.py::test_starts_ends_contains",
    "tests/test_window.py::test_row_number_rank_dense_rank",
    "tests/test_hash_aggregate.py::test_groupby_sum_count",
    # round-5 surfaces: fused join->agg (+ the
    # bounded groups-cap ladder and MXU small-table gathers), scan-form
    # window/segment ops, device parquet ENCODE, join repeat-collect
    "tests/test_fusion_perf.py::test_join_agg_fused_matches_oracle",
    "tests/test_fusion_perf.py::test_join_agg_fused_dup_build_keys",
    "tests/test_fusion_perf.py::test_window_chain_fused_matches_oracle",
    "tests/test_agg_bounded.py",
    "tests/test_join.py::test_adaptive_shuffled_join_repeat_collect",
    "tests/test_window.py::test_range_running_default_frame",
    "tests/test_window.py::test_bounded_range_frames",
    "tests/test_parquet_encode.py::test_plain_and_dict_int_roundtrip",
    "tests/test_parquet_encode.py::test_nullable_columns_def_levels",
    "tests/test_orc_device.py",
]


def main():
    env = dict(os.environ)
    env["SRT_TEST_ON_TPU"] = "1"
    env.pop("JAX_PLATFORMS", None)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "--no-header", *SUBSET],
        capture_output=True, text=True, env=env,
        timeout=int(os.environ.get("TPU_TESTS_TIMEOUT", 5400)))
    tail = proc.stdout.strip().splitlines()[-15:]
    out = {
        "subset": SUBSET,
        "returncode": proc.returncode,
        "green": proc.returncode == 0,
        "wall_seconds": round(time.time() - t0, 1),
        "summary": tail[-1] if tail else "",
        "tail": tail,
        "platform_note": ("SRT_TEST_ON_TPU=1: differential tests executed "
                          "on the attached chip; float64-heavy files "
                          "excluded per v5e f64-emulation caveat"),
    }
    os.makedirs("chiprun_out", exist_ok=True)
    path = os.path.join("chiprun_out", "TPU_TESTS.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrote": path, "green": out["green"],
                      "summary": out["summary"]}))


if __name__ == "__main__":
    main()
