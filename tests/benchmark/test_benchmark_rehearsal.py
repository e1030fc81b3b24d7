"""CPU rehearsal of a benchmark run: every cell at a tiny row count
through benchmark/run.py's own ``measure`` — data from the seed, the
query planned, the plan shape asserted, a loop of collects, the numpy
reference, the last line.  The look for a chip is skipped HERE
(``need_chip=False``); the command has no option that waives it.  And
the same with the timed path broken underneath: ``correct`` comes out
false."""
import json
import time
from decimal import Decimal

import pytest

from benchmark import run as bench_run
from benchmark.harness import cell as C
from benchmark.harness.manifest import Manifest

from test_benchmark_harness import CELLS, tiny

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _measure(name, *, trace=False, seed=2**31 + 11, seconds=0.3, cell=None):
    m = Manifest()
    cell = cell or tiny(m.cell(name))
    return cell, bench_run.measure(m, cell, seed, seconds, trace,
                                   t0=time.time(), need_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_rehearses_on_the_cpu(name, capfd):
    cell, res = _measure(name)
    assert list(res)[:5] == RESULT_KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == res["collects"] >= 1
    assert set(res["metrics"]) == {e["name"] for e in cell.end_to_end}
    for m in res["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert res["metrics"]["rows_per_s"]["value"] == pytest.approx(
        cell.fact_rows * res["collects"] / res["window_s"])
    assert all(c["value"] == 0 == c["limit"]
               for c in res["compared"].values())
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)
    assert "executed plan" in capfd.readouterr().err

    # the traced run reports the per-layer metrics its readers can read:
    # the CPU backend has no device plane, so those of the trace are left
    # out, never written as 0
    _, traced = _measure(name, trace=True)
    assert traced["correct"] is True
    from_trace = {"device_ms_per_collect", "query_hbm_roofline",
                  "device_idle_pct", "hbm_peak_gb"}
    assert set(traced["metrics"]) == \
        {p["name"] for p in cell.per_layer} - from_trace
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" not in traced["device"] and "breakdown" not in traced
    resident = cell.traffic["residency"] == "resident"
    assert (traced["metrics"]["h2d_gb_per_collect"]["value"] == 0) == resident


def test_two_clients_rehearse_on_the_cpu():
    """A traffic file may ask for more clients (the q6_resident_closed2
    row of PERF.md's open questions): each gets its own DataFrame and
    thread, and every answer of every client is compared."""
    cell = tiny(Manifest().cell("q6_resident"))
    cell.traffic["clients"] = 2
    cell.traffic["warmup_collects"] = 2
    _, res = _measure("q6_resident", cell=cell, seconds=0.2)
    assert res["correct"] is True and res["collects"] >= 4
    assert res["compared"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_run_py_fails_and_prints_no_result_without_a_chip(capsys):
    with pytest.raises(C.NoChip, match="measures on a TPU"):
        bench_run.main(["--workload", "q6_resident", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert capsys.readouterr().out == ""


def test_a_plan_of_another_shape_is_refused():
    """Without the traffic's pin the join of the cut tables is broadcast:
    the cell named for a shuffled join must not run it."""
    m = Manifest()
    cell = tiny(m.cell("ds_shuffled_join"))
    del cell.traffic["conf"]["spark.sql.autoBroadcastJoinThreshold"]
    with pytest.raises(AssertionError, match="TpuBroadcast"):
        _measure("ds_shuffled_join", cell=cell)


def _alter_an_answer(monkeypatch, cell):
    """The window's first collect is off by one unscaled unit in its
    first value."""
    from spark_rapids_tpu.session import DataFrame

    real, calls = DataFrame.collect, []

    def collect(self):
        rows = real(self)
        calls.append(1)
        if len(calls) == cell.traffic["warmup_collects"] + 1:
            *keys, v = rows[0]
            step = (Decimal(1).scaleb(v.as_tuple().exponent)
                    if isinstance(v, Decimal) else 1)
            rows = [(*keys, v + step)] + list(rows[1:])
        return rows

    monkeypatch.setattr(DataFrame, "collect", collect)


def _leave_out_half_the_rows(monkeypatch, cell):
    """The engine sees the first half of every table, the reference all."""
    real = C.make_frames

    def make_frames(cell, session, tables, data_dir):
        half = {t: {c: v[:len(v) // 2] for c, v in cols.items()}
                for t, cols in tables.items()}
        return real(cell, session, half, data_dir)

    monkeypatch.setattr(C, "make_frames", make_frames)


def _drop_a_group(monkeypatch, cell):
    from spark_rapids_tpu.session import DataFrame

    real = DataFrame.collect
    monkeypatch.setattr(DataFrame, "collect", lambda self: real(self)[:-1])


def _fall_back(monkeypatch, cell):
    """A stage answered by the CPU path moves a fallback counter."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.session import DataFrame

    real = DataFrame.collect

    def collect(self):
        PC.bump("runtime_fallbacks")
        return real(self)

    monkeypatch.setattr(DataFrame, "collect", collect)


@pytest.mark.parametrize("name,fault,fails", [
    ("q6_resident", _alter_an_answer, "max_abs_err"),
    ("q6_resident", _leave_out_half_the_rows, "max_abs_err"),
    ("q6_resident", _fall_back, "fallbacks"),
    ("ds_shuffled_join", _alter_an_answer, "max_abs_err"),
    ("ds_shuffled_join", _leave_out_half_the_rows, "max_abs_err"),
    ("ds_shuffled_join", _drop_a_group, "groups_off"),
    ("q6_parquet_scan", _alter_an_answer, "max_abs_err"),
    ("q6_parquet_scan", _leave_out_half_the_rows, "max_abs_err"),
])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, name, fault,
                                                   fails):
    cell = tiny(Manifest().cell(name))
    fault(monkeypatch, cell)
    _, res = _measure(name, cell=cell)
    assert res["correct"] is False
    c = res["compared"][fails]
    assert c["value"] > c["limit"]
    if fault is _alter_an_answer:
        # one collect of the window was wrong, and it is the one counted
        assert res["compared"]["wrong_answers"]["value"] == 1
        assert res["compared"]["max_abs_err"]["value"] == 1
