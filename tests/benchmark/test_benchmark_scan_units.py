"""``scan_units_per_collect`` (a PR after the span metrics brought it, as
new files and one appended entry): its reader on hand-made counters, and
its place in the manifest."""
from benchmark.harness.manifest import Manifest, load_module

from test_benchmark_spans import TABLE, _run


def test_the_reader_on_hand_made_counters():
    units = load_module("layer_metrics", "scan_units_per_collect")
    # 15 units a collect over the 4 collects of the table's window
    assert units.read(_run(TABLE, scan_units=60, scan_files_streamed=4)) \
        == 15
    # a file of one unit, and a plan that scans no file
    assert units.read(_run(TABLE, scan_units=4, scan_files_streamed=0)) == 1
    assert units.read(_run(TABLE, scan_units=0)) == 0
    # a program that does not count units (the parent of the PR that
    # brought the counter): nothing to read, and no error
    assert units.read(_run(TABLE)) is None
    assert units.read(_run({})) is None


def test_the_manifest_appends_it_to_the_scan_layer():
    m = Manifest()
    last = m.data["per_layer"][-1]
    assert last == {
        "name": "scan_units_per_collect", "unit": "count",
        "better": "higher", "source": "program_counter",
        "layer": "scan and decode", "moves": "rows_per_s",
        "workloads": ["q6_parquet_scan"]}
    cell = m.cell("q6_parquet_scan")
    assert "scan_units_per_collect" in cell.readers
    assert "scan_units_per_collect" not in m.cell("q6_resident").readers
