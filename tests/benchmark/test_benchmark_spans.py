"""The seven per-layer metrics read from the program's folded span table
(``perfcounters.span``: ``span_n|<path>``, ``span_ns|<path>``,
``span_self_ns|<path>`` in ``run.counters``): each reader on a hand-made
table, None for a program without spans, and the CPU rehearsal of every
cell reports them as numbers that add up."""
import time
from types import SimpleNamespace

import pytest

from benchmark import run as bench_run
from benchmark.harness.manifest import Manifest, load_module

from test_benchmark_harness import CELLS, tiny

COLLECTS = 4
C = "srt.collect"
AGG = C + "/srt.execute/srt.op.TpuHashAggregateExec"
JOIN = AGG + "/srt.op.TpuShuffledSymmetricHashJoinExec"
EXCH = JOIN + "/srt.op.TpuShuffleExchangeExec"
SCAN = AGG + "/srt.op.TpuFileSourceScanExec"

# path -> (count, inclusive ns, self ns) over COLLECTS collects: one
# client's thread under srt.collect, and the scan's staging thread, whose
# spans are roots of their own
TABLE = {
    C: (4, 40_000_000, 2_000_000),
    C + "/srt.plan": (4, 1_000_000, 1_000_000),
    C + "/srt.execute": (4, 30_000_000, 400_000),
    AGG: (8, 29_600_000, 1_200_000),
    AGG + "/srt.launch": (4, 2_000_000, 2_000_000),
    AGG + "/srt.sync": (4, 800_000, 800_000),
    JOIN: (8, 9_600_000, 1_600_000),
    JOIN + "/srt.join.build": (4, 2_400_000, 400_000),
    JOIN + "/srt.join.build/srt.launch": (4, 2_000_000, 2_000_000),
    EXCH: (8, 5_600_000, 1_000_000),
    EXCH + "/srt.exchange.partition": (4, 3_000_000, 600_000),
    EXCH + "/srt.exchange.partition/srt.launch": (4, 400_000, 400_000),
    EXCH + "/srt.exchange.partition/srt.sync": (4, 2_000_000, 2_000_000),
    EXCH + "/srt.op.TpuLocalTableScanExec": (8, 1_600_000, 1_600_000),
    SCAN: (8, 16_000_000, 2_000_000),
    SCAN + "/srt.scan.read": (4, 8_000_000_000, 8_000_000_000),
    SCAN + "/srt.scan.device_decode": (4, 400_000_000, 400_000_000),
    SCAN + "/srt.scan.prefetch_wait": (4, 6_000_000, 6_000_000),
    C + "/srt.sync": (4, 7_000_000, 7_000_000),
    "srt.scan.to_columns": (4, 12_000_000_000, 12_000_000_000),
    "srt.scan.h2d": (4, 2_000_000_000, 2_000_000_000),
}

WANT = {
    # self time beneath srt.collect outside every sync, scan and launch:
    # collect 2.0 + plan 1.0 + execute 0.4 + agg 1.2 + join 1.6
    # + build 0.4 + exchange 1.0 + partition 0.6 + its scan 1.6 + file
    # scan 2.0 = 11.8 ms over 4 collects
    "engine_host_ms_per_collect": 11.8 / 4,
    "launch_ms_per_collect": (2.0 + 2.0 + 0.4) / 4,
    "sync_ms_per_collect": (0.8 + 2.0 + 7.0) / 4,
    "scan_read_s_per_collect": (8.0 + 0.4) / 4,
    "scan_to_columns_s_per_collect": 12.0 / 4,
    "scan_h2d_s_per_collect": 2.0 / 4,
    # the exchange's own 1.0, its partition span 0.6, the launch 0.4 and
    # the sync 2.0 beneath it; not the scan it pulls
    "exchange_self_ms_per_collect": (1.0 + 0.6 + 0.4 + 2.0) / 4,
}


def _run(table, collects=COLLECTS, **other):
    counters = {"host_syncs": 7, "programs_launched": 3, **other}
    for path, (n, ns, self_ns) in table.items():
        counters["span_n|" + path] = n
        counters["span_ns|" + path] = ns
        counters["span_self_ns|" + path] = self_ns
    return SimpleNamespace(counters=counters,
                           window=SimpleNamespace(collects=collects))


def _reader(name):
    return load_module("layer_metrics", name)


def test_the_manifest_lists_the_seven_span_metrics():
    m = Manifest()
    spans = {p["name"]: p for p in m.data["per_layer"]
             if p["source"] == "program_span"}
    assert set(spans) == set(WANT)
    for p in spans.values():
        assert p["moves"] == "rows_per_s" and p["better"] == "lower"
        assert p["unit"] == ("s" if "_s_per_" in p["name"] else "ms")
    layers = {n: p["layer"] for n, p in spans.items()}
    assert {layers[n] for n in WANT if n.startswith("scan_")} \
        == {"scan and decode"}
    assert layers["exchange_self_ms_per_collect"] == "exchange"
    assert spans["exchange_self_ms_per_collect"]["workloads"] \
        == ["ds_shuffled_join"]
    assert spans["scan_h2d_s_per_collect"]["workloads"] \
        == ["q6_parquet_scan"]
    assert spans["engine_host_ms_per_collect"]["workloads"] == CELLS
    # the layers are ones the benchmark already names
    before = {p["layer"] for p in m.data["per_layer"]
              if p["source"] != "program_span"}
    assert set(layers.values()) - before <= {"exchange"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_on_a_hand_made_table(name):
    assert _reader(name).read(_run(TABLE)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_in_a_program_without_spans(name):
    """The parent of the PR that brought the spans: counters, no
    ``span_n|`` key.  None, and the line leaves the metric out."""
    assert _reader(name).read(_run({}, admission_wait_ns=5)) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_zero_where_its_span_never_opened(name):
    """A program with spans whose plan never reached this one: a number,
    not None (the q6_resident cell opens no scan span)."""
    got = _reader(name).read(_run({C: (4, 8_000_000, 8_000_000)}))
    assert got == (2.0 if name == "engine_host_ms_per_collect" else 0)


def test_the_three_collect_metrics_add_up_to_the_collect():
    """Where no scan runs beneath srt.collect, engine + launch + sync is
    the inclusive time of srt.collect."""
    table = {p: v for p, v in TABLE.items() if "srt.scan." not in p}
    # the file scan's self time stays: re-balance srt.collect's inclusive
    incl = sum(s for p, (_, _, s) in table.items() if p.startswith(C))
    run = _run(table)
    total = sum(_reader(n).read(run) for n in (
        "engine_host_ms_per_collect", "launch_ms_per_collect",
        "sync_ms_per_collect"))
    assert total == pytest.approx(incl / COLLECTS / 1e6)


@pytest.mark.parametrize("name", CELLS)
def test_the_rehearsal_of_a_cell_reports_its_span_metrics(name):
    m = Manifest()
    cell = tiny(m.cell(name))
    res = bench_run.measure(m, cell, 2**31 + 26, 0.3, True,
                            t0=time.time(), need_chip=False)
    assert res["correct"] is True
    mine = {p["name"] for p in cell.per_layer
            if p["source"] == "program_span"}
    assert mine == {n for n, p in
                    ((p["name"], p) for p in m.data["per_layer"])
                    if n in WANT and name in p["workloads"]}
    got = {n: res["metrics"][n]["value"] for n in mine}
    for n, v in got.items():
        assert isinstance(v, float) and v > 0, (n, v)
    # the three add up to at most the collect's wall, and to most of it
    three = sum(got[n] for n in (
        "engine_host_ms_per_collect", "launch_ms_per_collect",
        "sync_ms_per_collect"))
    mean_ms = res["window_s"] / res["collects"] * 1e3
    if name == "q6_parquet_scan":
        scan_ms = 1e3 * sum(got[n] for n in (
            "scan_read_s_per_collect", "scan_to_columns_s_per_collect",
            "scan_h2d_s_per_collect"))
        assert 0 < scan_ms < mean_ms
    else:
        assert 0.5 * mean_ms < three < mean_ms
