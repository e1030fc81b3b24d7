"""Doc-drift rule — the conf/counter/event vocabulary checks folded in
from ``tools/check_counters.py`` (which remains as a thin CLI shim so
existing invocations and the pytest mirrors keep working).

Unlike the AST rules this one introspects the RUNTIME registries
(``perfcounters.COUNTERS``, the typed conf ``_REGISTRY``, the
diagnostics ``EVENT_SCHEMA``) and cross-checks the docs tree, so it
only runs against the real repo (``tools/lint.py`` default; fixture
runs exclude it).  Message strings are kept byte-compatible with the
old checker — tests assert on them.
"""
from __future__ import annotations

import os
from typing import List

from spark_rapids_tpu.analysis.core import Engine, Finding


def doc_drift_problems(repo_root: str) -> List[str]:
    """Every drift problem as a human-readable string (the legacy
    ``check_counters.check()`` contract)."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu.config import _REGISTRY
    from spark_rapids_tpu.diagnostics.recorder import EVENT_SCHEMA

    problems = []

    def read(name):
        path = os.path.join(repo_root, "docs", name)
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            problems.append(f"missing docs file: docs/{name}")
            return ""

    diag_md = read("diagnostics.md")
    configs_md = read("configs.md")

    for key in sorted(PC.COUNTERS):
        if key.startswith(PC.SPAN_KEYS):
            # the folded span table: one key a path, made at run time;
            # the docs name the three prefixes and the span names
            continue
        # backtick-delimited: a bare substring test is vacuous for
        # counter names that are ordinary words ("compiles")
        if f"`{key}`" not in diag_md:
            problems.append(
                f"perf counter '{key}' is not documented (backticked) in "
                f"docs/diagnostics.md")
    for prefix in PC.SPAN_KEYS:
        if f"`{prefix}<path>`" not in diag_md:
            problems.append(
                f"span counter prefix '{prefix}' is not documented as "
                f"`{prefix}<path>` in docs/diagnostics.md")
    if hasattr(PC, "ALIASES"):
        problems.append(
            "perfcounters.ALIASES still exists — the one-release "
            "camelCase compat window closed in ISSUE 7")

    diag_confs = [k for k in _REGISTRY
                  if k.startswith("spark.rapids.tpu.diagnostics.")]
    if not diag_confs:
        problems.append("no spark.rapids.tpu.diagnostics.* confs "
                        "registered")
    for key in sorted(diag_confs):
        if key not in diag_md:
            problems.append(
                f"conf '{key}' is not documented in docs/diagnostics.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")

    for ev in sorted(EVENT_SCHEMA):
        if f"`{ev}`" not in diag_md:
            problems.append(
                f"event type '{ev}' is not documented in "
                f"docs/diagnostics.md")

    # query lifecycle (ISSUE 4): confs + counters must be documented in
    # docs/concurrency.md (and confs in the regenerated configs.md)
    conc_md = read("concurrency.md")
    life_confs = [k for k in _REGISTRY
                  if k == "spark.rapids.tpu.concurrentQueries"
                  or k.startswith(("spark.rapids.tpu.admission.",
                                   "spark.rapids.tpu.query.",
                                   "spark.rapids.tpu.semaphore."))]
    if not life_confs:
        problems.append("no query-lifecycle confs registered")
    for key in sorted(life_confs):
        if f"`{key}`" not in conc_md:
            problems.append(
                f"conf '{key}' is not documented in docs/concurrency.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("queries_admitted", "queries_rejected",
                "queries_cancelled", "deadline_trips",
                "admission_wait_ns"):
        if key not in PC.COUNTERS:
            problems.append(f"lifecycle counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in conc_md:
            problems.append(
                f"lifecycle counter '{key}' is not documented in "
                f"docs/concurrency.md")

    # I/O fault domain (ISSUE 5): tolerance confs + counters must be
    # documented in docs/io_resilience.md (and confs in configs.md)
    io_md = read("io_resilience.md")
    io_confs = [k for k in _REGISTRY
                if k.startswith(("spark.sql.files.ignore",
                                 "spark.rapids.tpu.files."))]
    if not io_confs:
        problems.append("no I/O fault-tolerance confs registered")
    for key in sorted(io_confs):
        if f"`{key}`" not in io_md:
            problems.append(
                f"conf '{key}' is not documented in "
                f"docs/io_resilience.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("files_skipped_corrupt", "files_skipped_missing",
                "file_decoder_fallbacks"):
        if key not in PC.COUNTERS:
            problems.append(f"I/O counter '{key}' is not registered in "
                            f"perfcounters.COUNTERS")
        if f"`{key}`" not in io_md:
            problems.append(
                f"I/O counter '{key}' is not documented in "
                f"docs/io_resilience.md")
    if "io_fault" not in EVENT_SCHEMA:
        problems.append("diagnostics event type 'io_fault' is not "
                        "registered in EVENT_SCHEMA")

    # transport-aware scan pipeline (ISSUE 6): confs + counters must be
    # documented in docs/scan_pipeline.md (and confs in configs.md)
    scan_md = read("scan_pipeline.md")
    scan_confs = [k for k in _REGISTRY
                  if k.startswith(("spark.rapids.tpu.scan.",
                                   "spark.rapids.sql.format.parquet."
                                   "transfer."))]
    if not scan_confs:
        problems.append("no scan-pipeline confs registered")
    for key in sorted(scan_confs):
        if f"`{key}`" not in scan_md:
            problems.append(
                f"conf '{key}' is not documented in "
                f"docs/scan_pipeline.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("bytes_h2d_logical", "scan_transfer_ns",
                "pages_device_decompressed", "chunk_decode_fallbacks",
                "bytes_h2d_overlapped", "prefetch_stall_ns",
                "hot_cache_hits", "hot_cache_misses",
                "hot_cache_evictions"):
        if key not in PC.COUNTERS:
            problems.append(f"scan counter '{key}' is not registered "
                            f"in perfcounters.COUNTERS")
        if f"`{key}`" not in scan_md:
            problems.append(
                f"scan counter '{key}' is not documented in "
                f"docs/scan_pipeline.md")
    if "scan_prefetch" not in EVENT_SCHEMA:
        problems.append("diagnostics event type 'scan_prefetch' is not "
                        "registered in EVENT_SCHEMA")

    # telemetry tier (ISSUE 7): confs + counters + the sampler's gauge
    # vocabulary must be documented in docs/observability.md (and confs
    # in the regenerated configs.md)
    obs_md = read("observability.md")
    tel_confs = [k for k in _REGISTRY
                 if k.startswith("spark.rapids.tpu.telemetry.")]
    if not tel_confs:
        problems.append("no spark.rapids.tpu.telemetry.* confs "
                        "registered")
    for key in sorted(tel_confs):
        if f"`{key}`" not in obs_md:
            problems.append(
                f"conf '{key}' is not documented in "
                f"docs/observability.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("slo_violations", "postmortem_dumps"):
        if key not in PC.COUNTERS:
            problems.append(f"telemetry counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in obs_md:
            problems.append(
                f"telemetry counter '{key}' is not documented in "
                f"docs/observability.md")
    for gauge in ("admission_running", "admission_queued",
                  "active_queries", "hbm_pool_bytes", "hbm_used_bytes",
                  "hbm_occupancy", "hot_cache_hit_rate",
                  "compile_cache_hit_rate", "compile_registry_programs",
                  "query_latency_p95_ms"):
        if f"`{gauge}`" not in obs_md:
            problems.append(
                f"sampler gauge '{gauge}' is not documented in "
                f"docs/observability.md")

    # profile-driven cost model (ISSUE 8): confs + counters + the
    # cost_model event + the advisory/telemetry vocabulary must be
    # documented in docs/profiling.md (and confs in configs.md)
    prof_md = read("profiling.md")
    prof_confs = [k for k in _REGISTRY
                  if k.startswith("spark.rapids.tpu.profile.")]
    if not prof_confs:
        problems.append("no spark.rapids.tpu.profile.* confs registered")
    for key in sorted(prof_confs):
        if f"`{key}`" not in prof_md:
            problems.append(
                f"conf '{key}' is not documented in docs/profiling.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("cost_model_hits", "cost_model_misses",
                "cost_model_predicted_wall_ns",
                "cost_model_matched_actual_wall_ns",
                "advisor_plan_fallbacks"):
        if key not in PC.COUNTERS:
            problems.append(f"profiling counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in prof_md:
            problems.append(
                f"profiling counter '{key}' is not documented in "
                f"docs/profiling.md")
    if "cost_model" not in EVENT_SCHEMA:
        problems.append("diagnostics event type 'cost_model' is not "
                        "registered in EVENT_SCHEMA")
    for field in ("op_class", "fp"):
        if field not in EVENT_SCHEMA.get("operator", []):
            problems.append(
                f"operator event field '{field}' (the calibration "
                f"identity) is missing from EVENT_SCHEMA")
    for gauge in ("cost_model_predicted_wall_ms",
                  "cost_model_matched_actual_wall_ms",
                  "cost_model_hit_rate", "cost_model_prediction_error"):
        if f"`{gauge}`" not in prof_md:
            problems.append(
                f"profiling telemetry gauge '{gauge}' is not "
                f"documented in docs/profiling.md")
    # the advisory file vocabulary the plan-time consult depends on
    for word in ("`route`", "`device`", "`native`", "`cpu`",
                 "`fallback-heavy`", "`sync-bound`", "`transport-bound`",
                 "advisory.json", "calibration.json"):
        if word not in prof_md:
            problems.append(
                f"advisory/store vocabulary {word} is not documented "
                f"in docs/profiling.md")

    # out-of-core exchange + ICI shuffle (ISSUE 10): confs + counters +
    # the ici_shuffle event must be documented in docs/out_of_core.md
    # (and confs in the regenerated configs.md)
    ooc_md = read("out_of_core.md")
    ooc_confs = [k for k in _REGISTRY
                 if k.startswith(("spark.rapids.tpu.exchange.",
                                  "spark.rapids.tpu.ici."))]
    if not ooc_confs:
        problems.append("no spark.rapids.tpu.exchange.* / "
                        "spark.rapids.tpu.ici.* confs registered")
    for key in sorted(ooc_confs):
        if f"`{key}`" not in ooc_md:
            problems.append(
                f"conf '{key}' is not documented in "
                f"docs/out_of_core.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("exchange_partitions_planned", "exchange_partition_ns",
                "exchange_spill_ns", "exchange_host_blocks",
                "exchange_host_block_bytes", "partitions_coalesced",
                "ici_epochs", "ici_rows_exchanged", "ici_bytes_moved",
                "ici_shuffle_ns"):
        if key not in PC.COUNTERS:
            problems.append(f"out-of-core counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in ooc_md:
            problems.append(
                f"out-of-core counter '{key}' is not documented in "
                f"docs/out_of_core.md")
    if "ici_shuffle" not in EVENT_SCHEMA:
        problems.append("diagnostics event type 'ici_shuffle' is not "
                        "registered in EVENT_SCHEMA")

    # live progress (ISSUE 12): confs + counters + the query_stall /
    # progress event vocabulary + the sampler's aggregate gauges + the
    # history-server tooling must be documented in docs/progress.md
    # (and confs in the regenerated configs.md)
    prog_md = read("progress.md")
    prog_confs = [k for k in _REGISTRY
                  if k.startswith("spark.rapids.tpu.progress.")]
    if not prog_confs:
        problems.append("no spark.rapids.tpu.progress.* confs "
                        "registered")
    for key in sorted(prog_confs):
        if f"`{key}`" not in prog_md:
            problems.append(
                f"conf '{key}' is not documented in docs/progress.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("stalls_detected", "progress_snapshots"):
        if key not in PC.COUNTERS:
            problems.append(f"progress counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in prog_md:
            problems.append(
                f"progress counter '{key}' is not documented in "
                f"docs/progress.md")
    for ev in ("query_stall", "progress"):
        if ev not in EVENT_SCHEMA:
            problems.append(f"diagnostics event type '{ev}' is not "
                            f"registered in EVENT_SCHEMA")
    for gauge in ("progress_queries_running", "progress_min_pct",
                  "progress_median_pct", "progress_stalled"):
        if f"`{gauge}`" not in prog_md:
            problems.append(
                f"progress sampler gauge '{gauge}' is not documented "
                f"in docs/progress.md")
    for word in ("history.py", "`/progress`", "`aot_compile`",
                 "`scan_prefetch`", "`shuffle_write`", "`--stalls`",
                 "progressOverhead"):
        if word not in prog_md:
            problems.append(
                f"progress surface vocabulary {word} is not "
                f"documented in docs/progress.md")

    # overload governor (ISSUE 13): confs + counters + the sampler
    # gauges + the governor event + the stress/chaos driver vocabulary
    # must be documented in docs/overload.md (and confs in configs.md)
    ovl_md = read("overload.md")
    gov_confs = [k for k in _REGISTRY
                 if k.startswith("spark.rapids.tpu.governor.")]
    if not gov_confs:
        problems.append("no spark.rapids.tpu.governor.* confs "
                        "registered")
    for key in sorted(gov_confs):
        if f"`{key}`" not in ovl_md:
            problems.append(
                f"conf '{key}' is not documented in docs/overload.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("governor_transitions", "queries_shed",
                "preempt_pauses", "degraded_batches",
                "oom_retry_preempts", "oom_retry_splits"):
        if key not in PC.COUNTERS:
            problems.append(f"governor counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in ovl_md:
            problems.append(
                f"governor counter '{key}' is not documented in "
                f"docs/overload.md")
    if "governor" not in EVENT_SCHEMA:
        problems.append("diagnostics event type 'governor' is not "
                        "registered in EVENT_SCHEMA")
    for gauge in ("governor_state", "governor_pressure"):
        if f"`{gauge}`" not in ovl_md:
            problems.append(
                f"governor sampler gauge '{gauge}' is not documented "
                f"in docs/overload.md")
    for word in ("`--overload`", "`--pressure`", "`retry_after_ms`",
                 "`queue_depth`", "`pressure_state`", "`governor_red`",
                 "`QueryRejected`", "run_stress.py", "run_chaos.py",
                 "bench_gate.py"):
        if word not in ovl_md:
            problems.append(
                f"governor surface vocabulary {word} is not "
                f"documented in docs/overload.md")

    # distributed cross-host tier (ISSUE 14): confs + counters + the
    # sampler gauges + the distributed event + the chaos/bench surface
    # vocabulary must be documented in docs/distributed.md (confs in
    # configs.md, counters ALSO in diagnostics.md via the global check)
    dist_md = read("distributed.md")
    dist_confs = [k for k in _REGISTRY
                  if k.startswith("spark.rapids.tpu.distributed.")]
    if not dist_confs:
        problems.append("no spark.rapids.tpu.distributed.* confs "
                        "registered")
    for key in sorted(dist_confs):
        if f"`{key}`" not in dist_md:
            problems.append(
                f"conf '{key}' is not documented in "
                f"docs/distributed.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("workers_joined", "worker_lost",
                "worker_heartbeat_misses", "partitions_replayed",
                "dist_blocks_shipped", "dist_block_bytes"):
        if key not in PC.COUNTERS:
            problems.append(f"distributed counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in dist_md:
            problems.append(
                f"distributed counter '{key}' is not documented in "
                f"docs/distributed.md")
    if "distributed" not in EVENT_SCHEMA:
        problems.append("diagnostics event type 'distributed' is not "
                        "registered in EVENT_SCHEMA")
    for gauge in ("dist_workers_live", "dist_workers_quarantined",
                  "dist_replacement_backlog"):
        if f"`{gauge}`" not in dist_md:
            problems.append(
                f"distributed sampler gauge '{gauge}' is not "
                f"documented in docs/distributed.md")
    for word in ("`--worker-kill`", "`WorkerLost`", "QUARANTINED",
                 "`worker_lost`", "`partition_replayed`", "rung4_dist",
                 "`TKD1`", "`TKU2`", "`ProtocolCorruption`",
                 "run_chaos.py", "bench_gate", "lineage"):
        if word not in dist_md:
            problems.append(
                f"distributed surface vocabulary {word} is not "
                f"documented in docs/distributed.md")

    # cluster observability (ISSUE 15): the worker-local counter
    # vocabulary, the federation gauges, the trace-id contract and the
    # merged-bundle/trace surfaces must be documented in
    # docs/cluster_observability.md (worker counters are NOT
    # perfcounters.COUNTERS — they live in the worker process — so the
    # global diagnostics.md check cannot see them)
    from spark_rapids_tpu.distributed.worker import WORKER_COUNTER_KEYS

    cluster_md = read("cluster_observability.md")
    for key in WORKER_COUNTER_KEYS:
        if f"`{key}`" not in cluster_md:
            problems.append(
                f"worker-local counter '{key}' is not documented in "
                f"docs/cluster_observability.md")
    for gauge in ("dist_blocks_unacked",):
        if f"`{gauge}`" not in cluster_md:
            problems.append(
                f"cluster-observability gauge '{gauge}' is not "
                f"documented in docs/cluster_observability.md")
    for ev in ("worker_telemetry", "worker_span"):
        if ev not in EVENT_SCHEMA:
            problems.append(f"diagnostics event type '{ev}' is not "
                            f"registered in EVENT_SCHEMA")
        if f"`{ev}`" not in cluster_md:
            problems.append(
                f"cluster-observability event '{ev}' is not "
                f"documented in docs/cluster_observability.md")
    if "trace_id" not in EVENT_SCHEMA.get("query_start", []):
        problems.append(
            "query_start event is missing the 'trace_id' field (the "
            "cluster trace contract)")
    for key in ("dist_worker_dumps", "dist_worker_spans_merged"):
        if key not in PC.COUNTERS:
            problems.append(f"cluster-observability counter '{key}' is "
                            f"not registered in perfcounters.COUNTERS")
        if f"`{key}`" not in cluster_md:
            problems.append(
                f"cluster-observability counter '{key}' is not "
                f"documented in docs/cluster_observability.md")
    for word in ("trace id", "`trace`", "`span`", "`dump`",
                 "clock offset", "heartbeat", "piggyback",
                 "`worker=`", "history.py", "`/cluster`",
                 "`--telemetry-out`", "`--workers`",
                 "traceOverheadPct", "`redrive`", "Perfetto",
                 "worker_diagnostics", "mint_trace_id"):
        if word not in cluster_md:
            problems.append(
                f"cluster-observability vocabulary {word} is not "
                f"documented in docs/cluster_observability.md")
    for name, md in (("distributed.md", dist_md),
                     ("observability.md", obs_md)):
        if "cluster_observability.md" not in md:
            problems.append(
                f"docs/{name} does not cross-link "
                f"docs/cluster_observability.md")

    # crash-consistent recovery (ISSUE 16): confs + counters + the
    # recovery event + the journal/checkpoint/lease surface vocabulary
    # must be documented in docs/recovery.md (confs in configs.md,
    # counters ALSO in diagnostics.md via the global check)
    rec_md = read("recovery.md")
    rec_confs = [k for k in _REGISTRY
                 if k.startswith("spark.rapids.tpu.recovery.")]
    if not rec_confs:
        problems.append("no spark.rapids.tpu.recovery.* confs "
                        "registered")
    for key in sorted(rec_confs):
        if f"`{key}`" not in rec_md:
            problems.append(
                f"conf '{key}' is not documented in docs/recovery.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("journal_records_written", "stages_recovered",
                "queries_resumed", "journal_recovery_discards",
                "recovery_leases_expired"):
        if key not in PC.COUNTERS:
            problems.append(f"recovery counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in rec_md:
            problems.append(
                f"recovery counter '{key}' is not documented in "
                f"docs/recovery.md")
    if "recovery" not in EVENT_SCHEMA:
        problems.append("diagnostics event type 'recovery' is not "
                        "registered in EVENT_SCHEMA")
    for word in ("`TKJ1`", "`journal.wal`", "`journal.replay`",
                 "`coordinator.endpoint`", "MANIFEST.json",
                 "`completed`", "`resumable`", "`abandoned`",
                 "`--driver-kill`", "re-HELLO", "lease",
                 "`stage_committed`", "`stage_recovered`",
                 "`driver_crash`", "run_chaos.py", "rung5_recovery",
                 "journalOverheadPct"):
        if word not in rec_md:
            problems.append(
                f"recovery surface vocabulary {word} is not "
                f"documented in docs/recovery.md")
    for name, md in (("distributed.md", dist_md),
                     ("concurrency.md", conc_md)):
        if "recovery.md" not in md:
            problems.append(
                f"docs/{name} does not cross-link docs/recovery.md")

    # tracelint (ISSUE 11): every lint rule id and the fusibility
    # manifest vocabulary must be documented in docs/static_analysis.md
    from spark_rapids_tpu.analysis.core import all_rule_ids

    sa_md = read("static_analysis.md")
    for rid in all_rule_ids(include_docs=True):
        if f"`{rid}`" not in sa_md:
            problems.append(
                f"lint rule '{rid}' is not documented in "
                f"docs/static_analysis.md")
    for word in ("`fusable`", "`fusable-with-rewrite`", "`unfusable`",
                 "`op_class`", "fusibility.py", "`--sarif`",
                 "`--prune-baseline`", "`--rules`"):
        if word not in sa_md:
            problems.append(
                f"tracelint/fusibility vocabulary {word} is not "
                f"documented in docs/static_analysis.md")

    # whole-plan fusion (ISSUE 17): confs + counters + the runtime
    # dispatch / fusion / bench-gate surface vocabulary must be
    # documented in docs/whole_plan_fusion.md (confs in configs.md,
    # counters ALSO in diagnostics.md via the global check), and the
    # docs the pass's machinery rides on must cross-link it
    fus_md = read("whole_plan_fusion.md")
    fus_confs = [k for k in _REGISTRY
                 if k.startswith("spark.rapids.tpu.fusion.")]
    if not fus_confs:
        problems.append("no spark.rapids.tpu.fusion.* confs registered")
    for key in sorted(fus_confs):
        if f"`{key}`" not in fus_md:
            problems.append(
                f"conf '{key}' is not documented in "
                f"docs/whole_plan_fusion.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("subtrees_fused", "collect_shrinks_elided"):
        if key not in PC.COUNTERS:
            problems.append(f"fusion counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in fus_md:
            problems.append(
                f"fusion counter '{key}' is not documented in "
                f"docs/whole_plan_fusion.md")
    for word in ("`CONCERNS`", "`fusion_segment()`", "`PipelineSegment`",
                 "`MANIFEST_ELIGIBLE`", "`tools/fusibility_manifest.json`",
                 "`fusable-with-rewrite`", "trace-time aux", "`--check`",
                 "`predicted_intermediate_bytes`",
                 "`nProgramsLaunched`", "`nHostSyncs`",
                 "splits at the predicted boundary", "TpuFusedPipeline["):
        if word not in fus_md:
            problems.append(
                f"whole-plan-fusion surface vocabulary {word} is not "
                f"documented in docs/whole_plan_fusion.md")
    for name, md in (("out_of_core.md", read("out_of_core.md")),
                     ("static_analysis.md", sa_md),
                     ("profiling.md", read("profiling.md"))):
        if "whole_plan_fusion.md" not in md:
            problems.append(
                f"docs/{name} does not cross-link "
                f"docs/whole_plan_fusion.md")

    # per-query resource accounting + regression sentinel (ISSUE 18):
    # confs + counters + the bill gauges + the resource_bill/regression
    # events + the bill/sentinel surface vocabulary must be documented
    # in docs/accounting.md (confs in configs.md, counters ALSO in
    # diagnostics.md via the global check), and the observability docs
    # the layer rides on must cross-link it
    acct_md = read("accounting.md")
    acct_confs = [k for k in _REGISTRY
                  if k.startswith("spark.rapids.tpu.accounting.")]
    if not acct_confs:
        problems.append("no spark.rapids.tpu.accounting.* confs "
                        "registered")
    for key in sorted(acct_confs):
        if f"`{key}`" not in acct_md:
            problems.append(
                f"conf '{key}' is not documented in docs/accounting.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("acct_device_bytes_charged", "acct_device_bytes_released",
                "acct_spill_bytes_host", "acct_spill_bytes_disk",
                "acct_bytes_restored", "bills_settled",
                "perf_regressions_flagged"):
        if key not in PC.COUNTERS:
            problems.append(f"accounting counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in acct_md:
            problems.append(
                f"accounting counter '{key}' is not documented in "
                f"docs/accounting.md")
    for ev in ("resource_bill", "regression"):
        if ev not in EVENT_SCHEMA:
            problems.append(f"diagnostics event type '{ev}' is not "
                            f"registered in EVENT_SCHEMA")
        if f"`{ev}`" not in acct_md:
            problems.append(
                f"accounting event '{ev}' is not documented in "
                f"docs/accounting.md")
    for gauge in ("bill_device_peak_bytes", "bill_device_byte_seconds",
                  "bill_spilled_bytes"):
        if f"`{gauge}`" not in acct_md:
            problems.append(
                f"accounting bill gauge '{gauge}' is not documented "
                f"in docs/accounting.md")
    for word in ("device-byte-seconds", "`(unowned)`", "`--bills`",
                 "`residual_bytes`", "`perf_regression`",
                 "`devicePeakBytes`", "`deviceByteSeconds`",
                 "`spilledBytes`", "accountingOverhead", "bench_gate",
                 "history.py", "`df.cache()`", "plan-signature"):
        if word not in acct_md:
            problems.append(
                f"accounting surface vocabulary {word} is not "
                f"documented in docs/accounting.md")
    for name, md in (("observability.md", obs_md),
                     ("profiling.md", read("profiling.md")),
                     ("overload.md", ovl_md)):
        if "accounting.md" not in md:
            problems.append(
                f"docs/{name} does not cross-link docs/accounting.md")

    # multi-tenant serving tier (ISSUE 19): confs + counters + the
    # sampler gauges + the session/fair-share/result-cache/warm-start
    # surface vocabulary must be documented in docs/serving.md (confs
    # in configs.md, counters ALSO in diagnostics.md via the global
    # check), and the docs the tier composes over must cross-link it
    srv_md = read("serving.md")
    srv_confs = [k for k in _REGISTRY
                 if k.startswith("spark.rapids.tpu.serving.")]
    if not srv_confs:
        problems.append("no spark.rapids.tpu.serving.* confs registered")
    for key in sorted(srv_confs):
        if f"`{key}`" not in srv_md:
            problems.append(
                f"conf '{key}' is not documented in docs/serving.md")
        if f"`{key}`" not in configs_md:
            problems.append(
                f"conf '{key}' missing from docs/configs.md — re-run "
                f"python docs/gen_docs.py")
    for key in ("serving_sessions_opened", "serving_sessions_closed",
                "fair_share_admissions", "result_cache_hits",
                "result_cache_misses", "result_cache_evictions",
                "tenant_sheds", "tenant_preempts"):
        if key not in PC.COUNTERS:
            problems.append(f"serving counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in srv_md:
            problems.append(
                f"serving counter '{key}' is not documented in "
                f"docs/serving.md")
    for gauge in ("serving_tenants_active", "serving_queue_depth",
                  "serving_running", "result_cache_entries",
                  "result_cache_bytes"):
        if f"`{gauge}`" not in srv_md:
            problems.append(
                f"serving sampler gauge '{gauge}' is not documented "
                f"in docs/serving.md")
    for word in ("fair-share", "`retry_after_ms`", "`QueryRejected`",
                 "`tenant=<name>`", "`drop_tenant`", "warm_cache.py",
                 "`--serve`", "`--serving`", "`--trace`",
                 "work-conserving", "half-life",
                 "`result_plan_key`", "`shutdown_serving()`",
                 "starved", "bench_gate", "`close()`"):
        if word not in srv_md:
            problems.append(
                f"serving surface vocabulary {word} is not documented "
                f"in docs/serving.md")
    for name, md in (("concurrency.md", conc_md),
                     ("overload.md", ovl_md),
                     ("observability.md", obs_md)):
        if "serving.md" not in md:
            problems.append(
                f"docs/{name} does not cross-link docs/serving.md")

    # gray-failure resilience (ISSUE 20): the hedging/DEGRADED counters
    # + sampler gauges + the soft-deadline/netchaos surface vocabulary
    # must be documented in docs/distributed.md (confs are covered by
    # the ISSUE 14 prefix loop above, counters ALSO in diagnostics.md
    # via the global check), and the failure taxonomy in
    # docs/resilience.md must carry the workerDegraded class
    for key in ("fetch_hedges", "hedges_won", "workers_degraded",
                "speculative_redrives"):
        if key not in PC.COUNTERS:
            problems.append(f"gray-failure counter '{key}' is not "
                            f"registered in perfcounters.COUNTERS")
        if f"`{key}`" not in dist_md:
            problems.append(
                f"gray-failure counter '{key}' is not documented in "
                f"docs/distributed.md")
    for gauge in ("dist_workers_degraded", "dist_fleet_lat_p95_ms"):
        if f"`{gauge}`" not in dist_md:
            problems.append(
                f"gray-failure sampler gauge '{gauge}' is not "
                f"documented in docs/distributed.md")
    for word in ("DEGRADED", "soft deadline", "hedge", "`--net`",
                 "netchaos", "`worker_degraded`", "`worker_promoted`",
                 "`WorkerDegraded`", "`ProtocolDesync`",
                 "first-complete-wins", "p95", "fleet median",
                 "test_gray_failure"):
        if word not in dist_md:
            problems.append(
                f"gray-failure surface vocabulary {word} is not "
                f"documented in docs/distributed.md")
    res_md = read("resilience.md")
    for word in ("`workerDegraded`", "`WorkerDegraded`", "`--net`",
                 "netchaos"):
        if word not in res_md:
            problems.append(
                f"gray-failure taxonomy vocabulary {word} is not "
                f"documented in docs/resilience.md")
    return problems


def _docs_file_of(problem: str) -> str:
    """Best-effort anchor: the docs file the message names, else the
    shim (registry-side problems)."""
    for tok in problem.split():
        tok = tok.rstrip(".,;:)")
        if tok.startswith("docs/") and tok.endswith(".md"):
            return tok
    return "tools/check_counters.py"


class DocDriftRule:
    """Repo-level rule: runs once per analysis, not per file."""

    id = "doc-drift"
    node_types = ()
    HINT = ("update the named docs file (and re-run python "
            "docs/gen_docs.py for configs.md) so the registered "
            "vocabulary and the documentation stay in sync")

    def end_run(self, engine: Engine) -> None:
        for problem in doc_drift_problems(engine.repo_root):
            engine.findings.append(Finding(
                _docs_file_of(problem), 1, 0, self.id, problem,
                self.HINT, "doc-drift"))
