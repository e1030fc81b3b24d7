"""Offline analysis over diagnostics event logs + the explain("analyze")
renderer.

Reference analog: the spark-rapids-tools profiler, which turns Spark
event logs into tuning reports (SURVEY.md L8).  Everything here is pure
functions over parsed JSONL dicts so ``tools/profile_report.py`` and the
tests share one implementation.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple


class QueryProfile:
    """One parsed query log."""

    __slots__ = ("path", "query_id", "trace_id", "started_at",
                 "metrics_level",
                 "plan", "operators", "events", "totals", "wall_ns",
                 "status", "parse_errors", "events_dropped")

    def __init__(self):
        self.path = ""
        self.query_id = ""
        self.trace_id = ""
        self.started_at = 0.0
        self.metrics_level = ""
        self.plan: List[Dict[str, Any]] = []
        self.operators: List[Dict[str, Any]] = []
        self.events: List[Dict[str, Any]] = []
        self.totals: Dict[str, int] = {}
        self.wall_ns = 0
        self.status = ""
        # data-quality flags (ISSUE 8 satellite): malformed/truncated
        # JSONL lines skipped while parsing this file (a query killed
        # mid-write leaves a torn trailing line), and the recorder-side
        # in-memory overflow count from query_end — either nonzero means
        # this query's aggregates are incomplete
        self.parse_errors = 0
        self.events_dropped = 0

    @property
    def incomplete(self) -> bool:
        return self.parse_errors > 0 or self.events_dropped > 0

    @property
    def plan_signature(self) -> str:
        """Stable per-plan key for diffing runs of the same query across
        two logs (operator names in path order)."""
        return "|".join(f"{n['path']}:{n['name']}" for n in self.plan)


def load_query_log(path: str) -> QueryProfile:
    """Parse one query log, tolerating torn lines: a query killed
    mid-write (SIGKILL between the sink's write and rename never
    happens, but a NON-atomic copy/tail of a live log does get truncated)
    must yield whatever parsed instead of raising — skipped lines are
    counted into ``parse_errors`` and the report flags the query's
    aggregates as incomplete."""
    qp = QueryProfile()
    qp.path = path
    with open(path, errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                e = json.loads(line)
                if not isinstance(e, dict):
                    raise ValueError("not an event object")
            except ValueError:
                qp.parse_errors += 1
                continue
            ev = e.get("ev")
            if ev == "query_start":
                qp.query_id = e.get("query_id", "")
                qp.trace_id = e.get("trace_id", "")
                qp.started_at = e.get("started_at", 0.0)
                qp.metrics_level = e.get("metrics_level", "")
                qp.plan = e.get("plan", [])
            elif ev == "operator":
                qp.operators.append(e)
            elif ev == "query_end":
                qp.totals = e.get("counters", {})
                qp.wall_ns = e.get("wall_ns", 0)
                qp.status = e.get("status", "")
                qp.events_dropped = int(e.get("events_dropped", 0) or 0)
            else:
                qp.events.append(e)
    return qp


def expand_log_paths(paths: List[str]) -> List[str]:
    """Files pass through; directories glob their query-*.jsonl."""
    out = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(
                os.path.join(p, n) for n in os.listdir(p)
                if n.startswith("query-") and n.endswith(".jsonl")))
        else:
            out.append(p)
    return out


def load_logs(paths: List[str]) -> List[QueryProfile]:
    return attach_worker_spans(
        [load_query_log(p) for p in expand_log_paths(paths)])


def attach_worker_spans(
        profiles: List[QueryProfile]) -> List[QueryProfile]:
    """Multi-process event logs (ISSUE 15): a file with no
    ``query_start`` whose events are worker spans (a worker-ring dump,
    a chaos harness timeline) is not a query — its spans attach to the
    loaded query whose trace id they carry, instead of surfacing as an
    anonymous empty profile (the old behavior: dropped as unknown
    operators).  Spans naming no loaded trace stay behind on the
    anonymous profile so nothing is silently discarded."""
    by_trace = {qp.trace_id: qp for qp in profiles
                if qp.query_id and qp.trace_id}
    out = []
    for qp in profiles:
        if qp.query_id or not qp.events:
            out.append(qp)
            continue
        orphans = []
        for e in qp.events:
            owner = by_trace.get(e.get("trace")) \
                if e.get("ev") == "worker_span" else None
            if owner is not None:
                owner.events.append(e)
            else:
                orphans.append(e)
        if orphans or qp.parse_errors:
            qp.events = orphans
            out.append(qp)
    return out


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def top_operators(profiles: List[QueryProfile], by: str = "wall_ns",
                  n: int = 10) -> List[Tuple[str, Dict[str, float]]]:
    """Aggregate operator summaries across queries by operator name.
    ``by``: 'wall_ns' or any counter key (e.g. 'host_syncs',
    'bytes_d2h', 'programs_launched')."""
    agg: Dict[str, Dict[str, float]] = {}
    for qp in profiles:
        for op in qp.operators:
            name = op.get("name", "?")
            a = agg.setdefault(name, {"wall_ns": 0.0, "self_wall_ns": 0.0,
                                      "batches": 0.0,
                                      "rows": 0.0, "queries": 0.0})
            a["wall_ns"] += op.get("wall_ns", 0)
            # logs predating self_wall_ns fall back to inclusive wall
            a["self_wall_ns"] += op.get("self_wall_ns",
                                        op.get("wall_ns", 0))
            a["batches"] += op.get("batches", 0)
            a["rows"] += op.get("rows", 0)
            a["queries"] += 1
            for k, v in (op.get("counters") or {}).items():
                a[k] = a.get(k, 0.0) + v
    ranked = sorted(agg.items(), key=lambda kv: -kv[1].get(by, 0.0))
    return [(name, a) for name, a in ranked if a.get(by, 0.0) > 0][:n]


def totals_summary(profiles: List[QueryProfile]) -> Dict[str, float]:
    tot: Dict[str, float] = {}
    for qp in profiles:
        for k, v in qp.totals.items():
            tot[k] = tot.get(k, 0.0) + v
        tot["wall_ns"] = tot.get("wall_ns", 0.0) + qp.wall_ns
    tot["queries"] = float(len(profiles))
    hits = tot.get("compile_cache_hits", 0.0)
    misses = tot.get("compile_cache_misses", 0.0)
    tot["compile_cache_hit_rate"] = (
        hits / (hits + misses) if hits + misses else 0.0)
    return tot


_RESILIENCE_KEYS = ("transient_retries", "oom_restarts",
                    "runtime_fallbacks", "breaker_trips",
                    "breaker_plan_fallbacks", "query_fallbacks")


def resilience_summary(profiles: List[QueryProfile]) -> Dict[str, Any]:
    counts = {k: 0 for k in _RESILIENCE_KEYS}
    by_kind: Dict[str, int] = {}
    for qp in profiles:
        for k in _RESILIENCE_KEYS:
            counts[k] += int(qp.totals.get(k, 0))
        for e in qp.events:
            if e.get("ev") == "resilience":
                kk = f"{e.get('kind')}@{e.get('op_name')}"
                by_kind[kk] = by_kind.get(kk, 0) + 1
    return {"counters": counts, "events": by_kind}


def stalls_summary(profiles: List[QueryProfile]) -> Dict[str, Any]:
    """Aggregate ``query_stall`` events (ISSUE 12): which operators
    queries wedge in, how often, and for how long — the offline
    companion of the live stall detector.  Fed by
    ``tools/profile_report.py --stalls``."""
    by_op: Dict[str, Dict[str, float]] = {}
    events: List[Dict[str, Any]] = []
    queries = set()
    for qp in profiles:
        for e in qp.events:
            if e.get("ev") != "query_stall":
                continue
            name = e.get("name") or "(no in-flight operator)"
            a = by_op.setdefault(name, {"stalls": 0.0, "stalled_ms": 0.0})
            a["stalls"] += 1
            a["stalled_ms"] += float(e.get("stalled_ms", 0) or 0)
            queries.add(qp.query_id or qp.path)
            events.append({"query": qp.query_id,
                           "op": name,
                           "path": e.get("path", ""),
                           "stalled_ms": float(e.get("stalled_ms", 0)
                                               or 0),
                           "detail": e.get("detail", "")})
    return {"total_stalls": len(events),
            "queries_with_stalls": len(queries),
            "by_operator": dict(sorted(
                by_op.items(), key=lambda kv: -kv[1]["stalled_ms"])),
            "events": events}


def render_stalls(summary: Dict[str, Any]) -> str:
    out = [f"== stalls: {summary['total_stalls']} query_stall event"
           f"{'' if summary['total_stalls'] == 1 else 's'} across "
           f"{summary['queries_with_stalls']} quer"
           f"{'y' if summary['queries_with_stalls'] == 1 else 'ies'} =="]
    for name, a in summary["by_operator"].items():
        out.append(f"  {name:<34} {int(a['stalls']):3d} stall"
                   f"{'' if a['stalls'] == 1 else 's'}  "
                   f"{a['stalled_ms']:9.1f}ms stalled")
    for e in summary["events"]:
        out.append(f"    {e['query']}: {e['stalled_ms']:.0f}ms in "
                   f"{e['op']}" + (f" at {e['path']}" if e["path"]
                                   else ""))
    return "\n".join(out)


def workers_summary(profiles: List[QueryProfile]) -> Dict[str, Any]:
    """Aggregate cluster-observability events (ISSUE 15): worker spans
    grouped by worker and by owning query (trace id), plus each
    worker's last federated counter snapshot — the offline companion
    of the live per-worker labeled series."""
    by_worker: Dict[str, Dict[str, Any]] = {}
    queries = set()
    for qp in profiles:
        for e in qp.events:
            ev = e.get("ev")
            if ev == "worker_span":
                wid = e.get("worker_id", "?")
                a = by_worker.setdefault(wid, {
                    "spans": 0, "bytes": 0, "wall_ns": 0,
                    "by_kind": {}, "queries": set(), "counters": {}})
                a["spans"] += 1
                a["bytes"] += int(e.get("bytes", 0) or 0)
                a["wall_ns"] += int(e.get("dur_ns", 0) or 0)
                kind = e.get("kind", "?")
                a["by_kind"][kind] = a["by_kind"].get(kind, 0) + 1
                a["queries"].add(qp.query_id or e.get("trace", "?"))
                queries.add(qp.query_id or qp.path)
            elif ev == "worker_telemetry":
                wid = e.get("worker_id", "?")
                a = by_worker.setdefault(wid, {
                    "spans": 0, "bytes": 0, "wall_ns": 0,
                    "by_kind": {}, "queries": set(), "counters": {}})
                a["counters"] = e.get("counters") or {}
                a["queries"].add(qp.query_id or qp.path)
    workers = {}
    for wid, a in sorted(by_worker.items()):
        workers[wid] = {
            "spans": a["spans"], "bytes": a["bytes"],
            "wall_ns": a["wall_ns"],
            "by_kind": dict(sorted(a["by_kind"].items())),
            "queries": sorted(a["queries"]),
            "counters": a["counters"]}
    return {"workers": workers,
            "total_spans": sum(a["spans"] for a in workers.values()),
            "queries_with_workers": len(queries)}


def render_workers(summary: Dict[str, Any]) -> str:
    out = [f"== distributed workers: {len(summary['workers'])} worker"
           f"{'' if len(summary['workers']) == 1 else 's'}, "
           f"{summary['total_spans']} span"
           f"{'' if summary['total_spans'] == 1 else 's'} across "
           f"{summary['queries_with_workers']} quer"
           f"{'y' if summary['queries_with_workers'] == 1 else 'ies'} =="]
    for wid, a in summary["workers"].items():
        kinds = ", ".join(f"{k}={v}" for k, v in a["by_kind"].items())
        out.append(f"  {wid:<12} {a['spans']:5d} spans  "
                   f"{_fmt_bytes(a['bytes']):>10}  "
                   f"{a['wall_ns'] / 1e6:8.1f}ms  [{kinds}]  "
                   f"({len(a['queries'])} quer"
                   f"{'y' if len(a['queries']) == 1 else 'ies'})")
        c = a["counters"]
        if c:
            out.append(
                f"    counters: puts={c.get('store_puts', 0)} "
                f"redrive={c.get('store_redrive_puts', 0)} "
                f"fetches={c.get('store_fetches', 0)} "
                f"served={_fmt_bytes(c.get('store_bytes_served', 0))} "
                f"overflow={_fmt_bytes(c.get('store_overflow_bytes', 0))}")
    return "\n".join(out)


def bills_summary(profiles: List[QueryProfile]) -> Dict[str, Any]:
    """Aggregate ``resource_bill`` + ``regression`` events (ISSUE 18):
    queries ranked by device-byte-seconds (the per-tenant quota number)
    and spill traffic, with any sentinel verdicts attached — fed by
    ``tools/profile_report.py --bills``."""
    bills: List[Dict[str, Any]] = []
    regressions: List[Dict[str, Any]] = []
    for qp in profiles:
        reg = None
        for e in qp.events:
            if e.get("ev") == "regression":
                reg = e
                regressions.append({
                    "query": e.get("query_id") or qp.query_id,
                    "dimension": e.get("dimension", ""),
                    "ratio": float(e.get("ratio", 0) or 0),
                    "op": f"{e.get('op_path', '')}:{e.get('op_name', '')}",
                    "detail": e.get("detail", "")})
        for e in qp.events:
            if e.get("ev") != "resource_bill":
                continue
            sp = e.get("spill") or {}
            bills.append({
                "query": e.get("query_id") or qp.query_id,
                "signature": e.get("signature", ""),
                "wall_ns": int(e.get("wall_ns", 0) or 0),
                "device_peak_bytes":
                    int(e.get("device_peak_bytes", 0) or 0),
                "device_byte_seconds":
                    float(e.get("device_byte_seconds", 0) or 0),
                "spilled_bytes": int(sp.get("host_bytes", 0) or 0)
                + int(sp.get("disk_bytes", 0) or 0),
                "restored_bytes": int(sp.get("restore_bytes", 0) or 0),
                "residual_bytes": int(e.get("residual_bytes", 0) or 0),
                "partitions": e.get("partitions") or {},
                "regression": (reg.get("dimension") if reg is not None
                               else None)})
    bills.sort(key=lambda b: b["device_byte_seconds"], reverse=True)
    return {"bills": bills,
            "queries_with_bills": len(bills),
            "total_device_byte_seconds": round(
                sum(b["device_byte_seconds"] for b in bills), 6),
            "total_spilled_bytes":
                sum(b["spilled_bytes"] for b in bills),
            "regressions": regressions}


def render_bills(summary: Dict[str, Any]) -> str:
    n = summary["queries_with_bills"]
    out = [f"== resource bills: {n} quer{'y' if n == 1 else 'ies'}, "
           f"{summary['total_device_byte_seconds']:.1f} device-byte-"
           f"seconds, {_fmt_bytes(summary['total_spilled_bytes'])} "
           f"spilled =="]
    for b in summary["bills"]:
        flag = f"  REGRESSED[{b['regression']}]" if b["regression"] \
            else ""
        out.append(
            f"  {b['query']:<24} {b['device_byte_seconds']:12.1f} B*s  "
            f"peak {_fmt_bytes(b['device_peak_bytes']):>10}  "
            f"spilled {_fmt_bytes(b['spilled_bytes']):>10}  "
            f"wall {b['wall_ns'] / 1e6:8.1f}ms{flag}")
        if b["partitions"]:
            hot = sorted(
                b["partitions"].items(),
                key=lambda kv: kv[1].get("spill_bytes", 0)
                + kv[1].get("restore_bytes", 0), reverse=True)[:4]
            parts = ", ".join(
                f"p{pid}={_fmt_bytes(d.get('spill_bytes', 0) + d.get('restore_bytes', 0))}"
                for pid, d in hot)
            out.append(f"    hot partitions: {parts}")
        if b["residual_bytes"]:
            out.append(f"    RESIDUAL {_fmt_bytes(b['residual_bytes'])}"
                       f" charged but never released")
    for r in summary["regressions"]:
        out.append(f"  regression: {r['query']} {r['dimension']} "
                   f"x{r['ratio']:.2f} worst op {r['op']}")
    return "\n".join(out)


def diff_profiles(base: List[QueryProfile],
                  new: List[QueryProfile]) -> List[Dict[str, Any]]:
    """Per-query regression diff: match queries by plan signature (falls
    back to position for unmatched), compare wall + key counters."""
    base_by_sig: Dict[str, List[QueryProfile]] = {}
    for qp in base:
        base_by_sig.setdefault(qp.plan_signature, []).append(qp)
    # signature matches first (they never conflict with each other), so
    # the positional fallback cannot consume a baseline a later query
    # matches exactly — a consumed baseline is never diffed twice
    matches: Dict[int, Optional[QueryProfile]] = {}
    consumed = set()
    for i, qp in enumerate(new):
        pool = base_by_sig.get(qp.plan_signature)
        if pool:
            m = pool.pop(0)
            matches[i] = m
            consumed.add(id(m))
    for i, qp in enumerate(new):
        if i not in matches:
            m = base[i] if i < len(base) else None
            matches[i] = m if m is not None and id(m) not in consumed \
                else None
    rows = []
    for i, qp in enumerate(new):
        match = matches[i]
        if match is None:
            rows.append({"query": qp.query_id, "matched": None})
            continue
        row = {"query": qp.query_id, "matched": match.query_id,
               "wall_ms": qp.wall_ns / 1e6,
               "base_wall_ms": match.wall_ns / 1e6,
               "wall_delta_pct": _pct(match.wall_ns, qp.wall_ns)}
        for k in ("programs_launched", "host_syncs", "bytes_d2h",
                  "compiles", "compile_cache_misses"):
            b, v = match.totals.get(k, 0), qp.totals.get(k, 0)
            row[k] = v
            row[f"base_{k}"] = b
            row[f"{k}_delta"] = v - b
        rows.append(row)
    return rows


def _pct(base, new) -> float:
    return 0.0 if not base else round((new - base) * 100.0 / base, 2)


# ---------------------------------------------------------------------------
# report rendering (text)
# ---------------------------------------------------------------------------

def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TB"


def data_quality_warnings(profiles: List[QueryProfile]) -> List[str]:
    """Header warnings for incomplete inputs: queries whose in-memory
    event list overflowed (events_dropped > 0 — their aggregates are
    lower bounds) and files with skipped malformed/truncated lines."""
    out = []
    dropped = [qp for qp in profiles if qp.events_dropped > 0]
    if dropped:
        ids = ", ".join((qp.query_id or qp.path) for qp in dropped[:5])
        more = "" if len(dropped) <= 5 else f" (+{len(dropped) - 5} more)"
        out.append(
            f"WARNING: {len(dropped)} quer"
            f"{'y' if len(dropped) == 1 else 'ies'} dropped events "
            f"in-memory — aggregates incomplete: {ids}{more}")
    torn = sum(qp.parse_errors for qp in profiles)
    if torn:
        files = sum(1 for qp in profiles if qp.parse_errors)
        out.append(
            f"WARNING: skipped {torn} malformed/truncated line"
            f"{'' if torn == 1 else 's'} across {files} file"
            f"{'' if files == 1 else 's'} (query killed mid-write?) — "
            f"affected aggregates incomplete")
    return out


def render_report(profiles: List[QueryProfile], top_n: int = 10) -> str:
    out = []
    tot = totals_summary(profiles)
    out.append(f"== profile report: {len(profiles)} quer"
               f"{'y' if len(profiles) == 1 else 'ies'} ==")
    out.extend(data_quality_warnings(profiles))
    out.append(
        f"total wall {tot.get('wall_ns', 0) / 1e9:.3f}s | launches "
        f"{int(tot.get('programs_launched', 0))} | host syncs "
        f"{int(tot.get('host_syncs', 0))} | D2H "
        f"{_fmt_bytes(tot.get('bytes_d2h', 0))} | H2D "
        f"{_fmt_bytes(tot.get('bytes_h2d', 0))}")
    out.append(
        f"compile cache: {int(tot.get('compile_cache_hits', 0))} hits / "
        f"{int(tot.get('compile_cache_misses', 0))} misses "
        f"(hit rate {tot['compile_cache_hit_rate'] * 100:.1f}%) | "
        f"inline compile wall "
        f"{tot.get('compile_wall_ns', 0) / 1e9:.3f}s | aot compiles "
        f"{int(tot.get('aot_compiles', 0))}")

    res = resilience_summary(profiles)
    if any(res["counters"].values()):
        parts = [f"{k}={v}" for k, v in res["counters"].items() if v]
        out.append("resilience: " + ", ".join(parts))
        for kk, v in sorted(res["events"].items()):
            out.append(f"  {kk}: x{v}")
    else:
        out.append("resilience: clean (no retries/fallbacks/trips)")

    # distributed workers (ISSUE 15): merged worker spans grouped by
    # trace id under their owning queries
    ws = workers_summary(profiles)
    if ws["workers"]:
        out.append("")
        out.append(render_workers(ws))

    def section(title, by, fmt):
        ranked = top_operators(profiles, by=by, n=top_n)
        if not ranked:
            return
        out.append("")
        out.append(f"-- top operators by {title} --")
        for name, a in ranked:
            out.append(f"  {name:<34} {fmt(a)}")

    section("self wall time", "self_wall_ns",
            lambda a: f"{a['self_wall_ns'] / 1e9:9.3f}s self "
                      f"({a['wall_ns'] / 1e9:.3f}s incl, "
                      f"{int(a['batches'])} batches, "
                      f"{int(a['rows'])} rows)")
    section("host syncs", "host_syncs",
            lambda a: f"{int(a.get('host_syncs', 0)):6d} syncs  "
                      f"({int(a.get('programs_launched', 0))} launches)")
    section("D2H bytes", "bytes_d2h",
            lambda a: f"{_fmt_bytes(a.get('bytes_d2h', 0)):>10}  "
                      f"({int(a.get('host_syncs', 0))} syncs)")
    section("launches", "programs_launched",
            lambda a: f"{int(a.get('programs_launched', 0)):6d} launches "
                      f"({int(a.get('compiles', 0))} compiles)")
    return "\n".join(out)


def render_diff(base: List[QueryProfile],
                new: List[QueryProfile]) -> str:
    rows = diff_profiles(base, new)
    out = [f"== regression diff: {len(base)} base vs {len(new)} new =="]
    for r in rows:
        if r.get("matched") is None:
            out.append(f"  {r['query']}: no baseline match")
            continue
        out.append(
            f"  {r['query']} vs {r['matched']}: wall "
            f"{r['base_wall_ms']:.1f} -> {r['wall_ms']:.1f}ms "
            f"({r['wall_delta_pct']:+.1f}%) | launches "
            f"{r['base_programs_launched']} -> {r['programs_launched']} | "
            f"syncs {r['base_host_syncs']} -> {r['host_syncs']} | D2H "
            f"{_fmt_bytes(r['base_bytes_d2h'])} -> "
            f"{_fmt_bytes(r['bytes_d2h'])}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# explain("analyze") rendering — in-process, over the live recorder
# ---------------------------------------------------------------------------

def analyze_tree(root, diag, meta=None,
                 metrics_level: str = "MODERATE") -> str:
    """Re-print the exec tree annotated with each node's metrics, counter
    deltas, compile-cache hits, and fallback status after execution (the
    AdaptiveSparkPlan `explain("analyze")` analog)."""
    from spark_rapids_tpu.diagnostics.recorder import _LEVELS
    from spark_rapids_tpu.exec.base import TpuExec

    max_rank = _LEVELS.get(str(metrics_level).upper(), 1)
    lines = []
    matched = [0]
    if diag is None:
        lines.append("(diagnostics were not enabled for the last "
                     "execution; set spark.rapids.tpu.diagnostics."
                     "enabled=true for counter deltas — showing operator "
                     "metrics only)")

    def annotate(node, indent, descend=True):
        st = None
        if diag is not None \
                and getattr(node, "_diag_qid", None) == diag.query_id:
            st = diag.ops.get(getattr(node, "_diag_path", None))
        parts = []
        # with a matching recorder, render ITS per-query metric deltas
        # (recorder.finish computed them from the registration baseline);
        # raw TpuMetric values are cumulative across collects of a cached
        # plan and would mix windows with the per-query counters below
        if st is not None:
            metric_items = sorted(st.metrics.items())
        else:
            metric_items = sorted((n, m.value)
                                  for n, m in node.metrics.items())
        for name, value in metric_items:
            if not value:
                continue
            m = node.metrics.get(name)
            if m is not None and _LEVELS.get(m.level, 1) > max_rank:
                continue
            if name.endswith(("Time", "time")):
                parts.append(f"{name}={value / 1e6:.1f}ms")
            else:
                parts.append(f"{name}={value}")
        if st is not None:
            matched[0] += 1
            if st.wall_ns:
                parts.insert(0, f"wall={st.wall_ns / 1e6:.1f}ms")
            for k in ("programs_launched", "host_syncs", "bytes_d2h",
                      "bytes_h2d", "compiles", "compile_cache_hits",
                      "compile_cache_misses"):
                v = st.counters.get(k, 0)
                if v:
                    parts.append(f"{k}={v}")
            if st.fallback:
                parts.append("fallback=CPU(runtime)")
        s = "  " * indent + node.describe()
        if parts:
            s += "  [" + ", ".join(parts) + "]"
        lines.append(s)
        if not descend:
            return
        # the exec that ran inside this node, then the children they share
        for inner in node.inner_execs():
            annotate(inner, indent + 1, descend=False)
        for c in node.children:
            if isinstance(c, TpuExec):
                annotate(c, indent + 1)
            elif hasattr(c, "pretty"):
                lines.append(c.pretty(indent + 1))

    annotate(root, 0)
    if diag is not None and matched[0] == 0:
        # the plan was re-planned since the recorded run (breaker
        # generation tick, conf change): the live tree no longer carries
        # the recorder's paths.  Render the recorder-side operator table
        # instead of silently dropping the run's stats.
        ran = [st for st in diag.operator_stats()
               if st.path and (st.batches or st.counters)]
        if ran:
            lines.append("(plan was re-planned since the recorded run; "
                         "recorder-side operator stats:)")
            for st in ran:
                parts = [f"wall={st.wall_ns / 1e6:.1f}ms",
                         f"batches={st.batches}", f"rows={st.rows}"]
                parts += [f"{k}={v}"
                          for k, v in sorted(st.counters.items()) if v]
                lines.append(f"  {st.path} {st.describe}  ["
                             + ", ".join(parts) + "]")
    if diag is not None:
        qb = diag.ops.get("")
        if qb is not None and qb.counters:
            parts = [f"{k}={v}" for k, v in sorted(qb.counters.items())
                     if v]
            lines.append("(query-level, unattributed)  ["
                         + ", ".join(parts) + "]")
        lines.append(f"query: wall={diag.wall_ns / 1e6:.1f}ms "
                     f"status={diag.status} "
                     f"events={diag.n_events or len(diag.events)}"
                     + (f" eventLog={diag.event_log_path}"
                        if diag.event_log_path else ""))
    if meta is not None:
        fb = meta.explain(only_fallback=True)
        if fb:
            lines.append("Fallback reasons:")
            lines.append(fb)
    return "\n".join(lines)
