"""The benchmark's own arithmetic, on the CPU and without the engine:
manifest loader, data from the seed, statistics, the comparison and its
control, the trace reduction, the peaks table."""
import json
import os
import shutil

import numpy as np
import pytest

from benchmark import control
from benchmark.harness import check, stats, trace
from benchmark.harness.cell import make_tables
from benchmark.harness.manifest import ROOT, Manifest, ManifestError
from benchmark.harness.peaks import peaks_of

CELLS = ["q6_resident", "ds_shuffled_join", "q6_parquet_scan"]
CONTRACT_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}


def tiny(cell, divisor=4000):
    """The cell with its tables cut to a size a test run can hold."""
    for name, spec in cell.config["tables"].items():
        if name != "date_dim":
            spec["rows"] = max(spec["rows"] // divisor, 100)
    return cell


def test_manifest_has_the_contracts_keys_and_every_file_resolves():
    m = Manifest()
    assert set(m.data) == CONTRACT_KEYS
    assert m.workload_names() == CELLS
    for c in m.data["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and cfg["assumed"]
        assert set(cfg["reduced_why"]) == set(c["reduced"])
    for name in CELLS:
        cell = m.cell(name)
        assert cell.chips == 1 and len(cell.why) <= 200
        assert set(cell.query.TABLES) <= set(cell.generators)
        e2e = {e["name"] for e in cell.end_to_end}
        assert e2e == {"setup_s", "rows_per_s"} == set(stats.END_TO_END)
        assert cell.per_layer and set(cell.readers) == {
            p["name"] for p in cell.per_layer}
        for reader in cell.readers.values():
            assert callable(reader.read)
        for p in cell.per_layer:
            assert p["moves"] in e2e


@pytest.mark.parametrize("field,value", [
    (("workloads", 0, "name"), "has space"),
    (("end_to_end", 0, "unit"), "rows per s"),
    (("per_layer", 0, "moves"), "no_such_metric"),
    (("per_layer", 0, "workloads"), ["no_such_cell"]),
    (("workloads", 1, "chips"), 2),
])
def test_manifest_refuses_what_the_contract_refuses(tmp_path, field, value):
    data = Manifest().data
    group, i, key = field
    data[group][i][key] = value
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    with pytest.raises(ManifestError):
        Manifest(str(tmp_path))


@pytest.mark.parametrize("name", CELLS)
def test_two_seeds_give_the_same_shapes_and_other_values(name):
    cell = tiny(Manifest().cell(name))
    a, b, a2 = (make_tables(cell, s) for s in (3, 2**31 + 7, 3))
    assert a.keys() == b.keys() == set(cell.query.TABLES)
    differs = False
    for t in a:
        assert len(cell.generators[t].TYPES) == len(a[t])
        for c in a[t]:
            assert a[t][c].shape == b[t][c].shape == (cell.table_rows[t],)
            assert a[t][c].dtype == b[t][c].dtype
            assert np.array_equal(a[t][c], a2[t][c])
            differs |= not np.array_equal(a[t][c], b[t][c])
    assert differs


def test_a_later_pr_adds_a_cell_with_new_files_alone(tmp_path):
    """A throw-away configuration, traffic mix, query, table and per-layer
    metric go into a copy as NEW files and entries; no file that was
    there is edited, and the loader resolves the new cell."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    b = root / "benchmark"
    (b / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "source": "none", "tables": {
            "t": {"generator": "toy_table", "rows": 64}},
        "fact_table": "t", "query": "toy_query", "conf": {},
        "reduced": [], "assumed": [], "guarantees": {}}))
    (b / "traffic" / "toy_closed1.json").write_text(json.dumps({
        "loop": "closed", "clients": 1, "residency": "resident",
        "warmup_collects": 1}))
    (b / "datagen" / "toy_table.py").write_text(
        "TYPES = ['long']\n"
        "def make(rows, rng, parent=None):\n"
        "    return {'v': rng.integers(0, 9, rows)}\n")
    (b / "queries" / "toy_query.py").write_text(
        "TABLES = ('t',)\n"
        "def reference(tables):\n"
        "    return {(): int(tables['t']['v'].sum())}\n"
        "def min_bytes(rows):\n"
        "    return rows['t'] * 8\n")
    (b / "layer_metrics" / "toy_metric.py").write_text(
        "def read(run):\n    return 1.0\n")
    data = Manifest().data
    data["configs"].append({"name": "toy", "source": "none",
                            "file": "benchmark/configs/toy.json",
                            "reduced": [], "why": "a test"})
    data["workloads"].append({"name": "toy_cell", "config": "toy",
                              "traffic": "toy_closed1", "chips": 1,
                              "why": "a test"})
    data["per_layer"].append({
        "name": "toy_metric", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "entry and plan, host runtime",
        "moves": "rows_per_s", "workloads": ["toy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    m = Manifest(str(root))
    cell = m.cell("toy_cell")
    assert cell.fact_rows == 64 and cell.readers["toy_metric"].read(None) == 1.0
    assert [p["name"] for p in cell.per_layer] == ["toy_metric"]
    assert {e["name"] for e in cell.end_to_end} == {"rows_per_s", "setup_s"}
    tables = make_tables(cell, 5)
    assert cell.query.reference(tables) == {(): int(tables["t"]["v"].sum())}
    # the cells that were there do not report the new metric
    assert "toy_metric" not in m.cell("q6_resident").readers
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


def _window(lat):
    lat = np.asarray(lat, dtype=float)
    return stats.Window(latencies_s=lat, elapsed_s=float(lat.sum()),
                        attempted=len(lat), failed=0, rows_per_collect=1000)


def test_rate_and_tail_both_show_a_stall():
    steady = _window([0.010] * 400)
    stalled = _window([0.010] * 370 + [0.060] * 30)   # a 7.5 % stall
    assert stats.rows_per_s(steady) == pytest.approx(1000 / 0.010)
    # all the rows over all the time: 400,000 rows in 5.5 s, not 100,000/s
    assert stats.rows_per_s(stalled) == pytest.approx(400_000 / 5.5)
    assert stats.percentile_ms(steady, 95) == pytest.approx(10.0)
    assert stats.percentile_ms(stalled, 95) == pytest.approx(60.0)
    assert stats.percentile_ms(stalled, 50) == pytest.approx(10.0)
    assert stats.END_TO_END["rows_per_s"](stalled, 12.5) == \
        stats.rows_per_s(stalled)
    assert stats.END_TO_END["setup_s"](stalled, 12.5) == 12.5
    # the tail's per-layer reader reads the same window
    tail = Manifest().cell("q6_resident").readers["collect_p95_ms"]
    assert tail.read(type("Run", (), {"window": stalled})) == \
        pytest.approx(60.0)


def _verdict(answers, want, **kw):
    kw = {"failed": 0, "fallbacks": 0, "compiles_in_window": 0, **kw}
    compared = check.compare(answers, want, **kw)
    return check.is_correct(compared), {k: v["value"]
                                        for k, v in compared.items()}


def test_the_comparison_is_exact_and_counts_every_collect():
    want = {(1,): 100, (2,): 250}
    assert _verdict([dict(want)] * 3, want)[0]
    ok, v = _verdict([dict(want), {(1,): 100, (2,): 251}], want)
    assert not ok and v["wrong_answers"] == 1 and v["max_abs_err"] == 1
    ok, v = _verdict([{(1,): 100}], want)
    assert not ok and v["groups_off"] == 1
    ok, v = _verdict([{**want, (3,): 0}], want)
    assert not ok and v["groups_off"] == 1
    for broken in ({"failed": 1}, {"fallbacks": 2},
                   {"compiles_in_window": 1}):
        assert not _verdict([dict(want)], want, **broken)[0]
    # no answer at all is no proof
    assert not _verdict([], want)[0]
    assert all(c["limit"] == 0 for c in
               check.compare([want], want, failed=0, fallbacks=0,
                             compiles_in_window=0).values())


@pytest.mark.parametrize("name", ["q6_resident", "ds_shuffled_join"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float32_control_comes_out_not_correct(name, seed):
    cell = tiny(Manifest().cell(name), divisor=100)
    compared = control.control(cell, seed)
    assert not check.is_correct(compared)
    assert compared["max_abs_err"]["value"] > 0
    assert compared["groups_off"]["value"] == 0


def test_q6_min_bytes_and_the_peaks_table():
    cell = Manifest().cell("q6_resident")
    assert cell.query.min_bytes({"lineitem": 59_986_052}) == 59_986_052 * 28
    assert Manifest().cell("ds_shuffled_join").query.min_bytes(
        {"store_sales": 1, "store_returns": 1}) is None
    v5e = peaks_of("TPU v5 lite")
    assert v5e["hbm_gb_per_s"] == 819.0 and v5e["bf16_tflop_per_s"] == 197.0
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_of("TPU v99")


def test_the_trace_reduction_on_hand_made_events():
    ms = 1e6
    events = {
        "host": [("collect", 0 * ms, 10 * ms), ("collect", 10 * ms, 10 * ms),
                 ("np.asarray(jax.Array)", 7 * ms, 2.5 * ms),
                 ("before the window", -50 * ms, 5 * ms)],
        "device": {"/device:TPU:0": [
            ("fusion.1", 1 * ms, 3 * ms), ("fusion.2", 3 * ms, 3 * ms),
            ("fusion.1", 11 * ms, 3 * ms), ("copy", 14 * ms + 5_000, 1 * ms),
            ("outside", 30 * ms, 5 * ms)]},
    }
    s = trace.reduce_events(events, "collect")
    assert s["spans"] == 2 and s["window_s"] == pytest.approx(0.020)
    # union: [1,6] + [11,14] + [14.005,15.005] = 9 ms; overlap counted once
    assert s["busy_s"] == pytest.approx(0.009)
    assert dict(map(tuple, s["device_ops"])) == pytest.approx(
        {"fusion.1": 0.006, "fusion.2": 0.003, "copy": 0.001})
    gaps = dict(map(tuple, s["idle_gaps"]))
    # the gap [6,11] is split: np.asarray was open (innermost) for
    # [7,9.5], the rest of it and [0,1], [15.005,20] had collect alone;
    # 5 us lie between two ops
    assert gaps == pytest.approx({
        "collect": 0.001 + 0.004995 + 0.0025, "np.asarray_jax.Array_": 0.0025,
        trace._short(trace.SHORT_GAP): 0.000005})
    assert s["busy_s"] + sum(gaps.values()) == pytest.approx(s["window_s"])
    assert trace.reduce_events({"host": [], "device": events["device"]},
                               "collect") is None
    assert trace.reduce_events({"host": events["host"], "device": {}},
                               "collect") is None


def test_the_trace_reduction_on_a_trace_recorded_on_the_chip():
    """benchmark/testdata/q6_1m_rows_21_collects.xplane.pb: q6_resident
    cut to 1 M rows, 21 collects in a 0.05 s window on a TPU v5 lite
    (PR 25, chip call 1).  One XLA program a collect: 16 op events each."""
    path = os.path.join(ROOT, "benchmark", "testdata",
                        "q6_1m_rows_21_collects.xplane.pb")
    events = trace.load_events(path, "collect")
    assert list(events["device"]) == ["/device:TPU:0"]
    ops = events["device"]["/device:TPU:0"]
    assert len(ops) == 336 and all(d > 0 for _, _, d in ops)
    assert sum(name == "collect" for name, _, _ in events["host"]) == 21
    s = trace.reduce_events(events, "collect")
    assert s["spans"] == 21
    assert s["window_s"] == pytest.approx(0.052240928, rel=1e-6)
    # ops of one core do not overlap: the union is the plain sum
    assert s["busy_s"] == pytest.approx(sum(d for _, _, d in ops) / 1e9)
    assert s["busy_s"] == pytest.approx(0.002187657, rel=1e-6)
    # the ten largest gap owners hold all but crumbs of the idle time
    accounted = s["busy_s"] + sum(g for _, g in s["idle_gaps"])
    assert 0.999 * s["window_s"] < accounted <= s["window_s"] * (1 + 1e-9)
    assert len(s["device_ops"]) == trace.TOP
    assert s["device_ops"][0][0].startswith("_fusion.18")
    assert s["device_ops"][0][1] == pytest.approx(0.000942196, rel=1e-6)
    # at 1 M rows the host holds the device back: the engine's own
    # Python under collect(), the wait in np.asarray, the launch
    assert [name for name, _ in s["idle_gaps"][:3]] == [
        "collect", "np.asarray_jax.Array_", "PjitFunction__agg_fn_"]
    assert all(len(name) <= 64 and " " not in name and "," not in name
               for name, _ in s["device_ops"] + s["idle_gaps"])
    # another span name finds nothing to read
    assert trace.reduce_events(trace.load_events(path, "no_such_span"),
                               "no_such_span") is None


class _FakeFrame:
    """collect() that takes ``wall`` seconds; the nth call raises."""

    def __init__(self, wall, fail_on=None):
        self.wall, self.fail_on, self.calls = wall, fail_on, 0

    def collect(self):
        import time

        self.calls += 1
        time.sleep(self.wall)
        if self.calls == self.fail_on:
            raise RuntimeError("planted")
        return [(self.calls,)]


@pytest.mark.parametrize("clients", [1, 2])
def test_the_closed_loop_counts_every_client_and_every_failure(clients):
    from benchmark.harness.loop import closed_loop

    frames = [_FakeFrame(0.02, fail_on=2 if i == 0 else None)
              for i in range(clients)]
    w, results = closed_loop(frames, 0.2, rows_per_collect=10)
    assert w.failed == 1 and w.attempted == sum(f.calls for f in frames)
    assert w.collects == len(results) == w.attempted - 1
    assert w.collects >= 5 * clients
    # a collect that started before the deadline is waited for
    assert 0.2 <= w.elapsed_s < 0.3 and (w.latencies_s >= 0.02).all()
    # every client waits for its own collect: the rate scales with them
    assert stats.rows_per_s(w) == pytest.approx(
        10 * w.collects / w.elapsed_s)
    assert 350 * clients < stats.rows_per_s(w) <= 500 * clients
