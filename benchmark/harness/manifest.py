"""BENCHMARK.json and the files it names.

A cell is found by name alone: ``workloads[i].config`` ->
``configs/<config>.json`` (tables, query, conf), ``.traffic`` ->
``traffic/<traffic>.json`` (loop, residency, warm-up, conf, optionally
the query), each table's ``generator`` -> ``datagen/<generator>.py``, the
query -> ``queries/<query>.py``, each metric -> a reader in
``layer_metrics/<metric>.py`` (per-layer) or ``harness/stats.py``
(end-to-end).  A later PR adds files and entries and edits none."""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _need(cond, msg):
    if not cond:
        raise ManifestError(msg)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module, by file path: the
    loader works the same on a copy of the benchmark in another place."""
    _need(_NAME.match(name), f"{kind} name {name!r} has characters a name may not have")
    path = os.path.join(bench_dir, kind, name + ".py")
    _need(os.path.isfile(path), f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path):
    _need(os.path.isfile(path), f"no file {path}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config: dict
    traffic: dict
    query_name: str
    query: object
    generators: dict            # table -> datagen module
    end_to_end: list            # metric entries this cell reports
    per_layer: list
    readers: dict = field(default_factory=dict)   # per-layer name -> module

    @property
    def conf(self) -> dict:
        return {**self.config.get("conf", {}), **self.traffic.get("conf", {})}

    @property
    def plan(self) -> dict:
        """Plan-shape expectations of the configuration and the traffic."""
        out = {"expect": [], "forbid": [], "count": {}}
        for src in (self.config.get("plan", {}), self.traffic.get("plan", {})):
            out["expect"] += src.get("expect", [])
            out["forbid"] += src.get("forbid", [])
            out["count"].update(src.get("count", {}))
            if "join_decision" in src:
                out["join_decision"] = src["join_decision"]
        return out

    @property
    def table_rows(self) -> dict:
        return {t: self.config["tables"][t]["rows"] for t in self.query.TABLES}

    @property
    def fact_rows(self) -> int:
        return self.config["tables"][self.config["fact_table"]]["rows"]


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.data["paths"][0])
        self._validate()

    def _validate(self):
        d = self.data
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in d[group]]
            _need(len(set(names)) == len(names), f"{group}: a name twice")
            for n in names:
                _need(_NAME.match(n), f"{group}: bad name {n!r}")
        e2e = {m["name"] for m in d["end_to_end"]}
        _need("setup_s" in e2e, "end_to_end lacks setup_s")
        cells = {w["name"] for w in d["workloads"]}
        for m in d["end_to_end"] + d["per_layer"]:
            _need(_UNIT.match(m["unit"]), f"{m['name']}: bad unit {m['unit']!r}")
            _need(m["better"] in ("lower", "higher"), f"{m['name']}: better?")
            _need(m["source"] in _SOURCES, f"{m['name']}: source?")
            for w in m.get("workloads", []):
                _need(w in cells, f"{m['name']}: no workload {w!r}")
        for m in d["per_layer"]:
            _need(m["moves"] in e2e, f"{m['name']} moves no end-to-end metric")
        for w in d["workloads"]:
            _need(w["chips"] in (1, 4), f"{w['name']}: chips?")
            _need(_NAME.match(w["traffic"]), f"{w['name']}: bad traffic name")
            _need(any(c["name"] == w["config"] for c in d["configs"]),
                  f"{w['name']}: no configuration {w['config']!r}")

    def workload_names(self):
        return [w["name"] for w in self.data["workloads"]]

    def _metrics_of(self, group: str, cell: str):
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.data["workloads"] if w["name"] == name),
                     None)
        _need(entry is not None, f"no workload {name!r} in BENCHMARK.json")
        cfg_entry = next(c for c in self.data["configs"]
                         if c["name"] == entry["config"])
        config = _load_json(os.path.join(self.root, cfg_entry["file"]))
        traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", entry["traffic"] + ".json"))
        _need(traffic.get("loop") == "closed"
              and isinstance(traffic.get("clients"), int)
              and 1 <= traffic["clients"] <= 16,
              f"traffic {entry['traffic']}: the loop knows closed, with "
              f"1 to 16 clients")
        _need(traffic.get("residency") in ("resident", "parquet"),
              f"traffic {entry['traffic']}: residency?")
        query_name = traffic.get("query", config["query"])
        query = load_module("queries", query_name, self.bench_dir)
        _need(config["fact_table"] in query.TABLES,
              f"{name}: the query does not read the fact table")
        generators = {}
        todo = list(query.TABLES)
        while todo:         # the query's tables and those they are made from
            table = todo.pop()
            _need(table in config["tables"], f"{name}: no table {table!r}")
            if table not in generators:
                spec = config["tables"][table]
                generators[table] = load_module(
                    "datagen", spec["generator"], self.bench_dir)
                todo += [spec["from"]] if "from" in spec else []
        per_layer = self._metrics_of("per_layer", name)
        cell = Cell(name=name, chips=entry["chips"], why=entry["why"],
                    config=config, traffic=traffic, query_name=query_name,
                    query=query, generators=generators,
                    end_to_end=self._metrics_of("end_to_end", name),
                    per_layer=per_layer)
        for m in per_layer:
            cell.readers[m["name"]] = load_module(
                "layer_metrics", m["name"], self.bench_dir)
        return cell
