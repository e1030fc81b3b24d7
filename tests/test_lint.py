"""tpulint (ISSUE 9): fixture corpus, pragma/baseline mechanics, JSON
determinism, the tier-1 repo gate, the CLI exit-code contract, and
regression pins for the real in-repo findings the new rules surfaced
(and this PR fixed).
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from spark_rapids_tpu.analysis import Baseline, run_paths, to_json
from spark_rapids_tpu.analysis.core import default_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")


def _lint_fixtures():
    return run_paths([FIXTURES], FIXTURES,
                     rules=default_rules(include_docs=False))


def _rules_by_file(findings):
    out = {}
    for f in findings:
        out.setdefault(os.path.basename(f.file), set()).add(f.rule)
    return out


# ---------------------------------------------------------------------------
# golden fixture corpus: one firing + one non-firing case per rule
# ---------------------------------------------------------------------------

# file basename -> (rule, must_fire)
_MATRIX = [
    ("fire_direct.py", "counter-write", True),
    ("ok_bump.py", "counter-write", False),
    ("fire_swallow.py", "cancel-swallow", True),
    ("fire_bare.py", "cancel-swallow", True),
    ("fire_narrow_then_broad.py", "cancel-swallow", True),
    ("fire_rejected_then_broad.py", "cancel-swallow", True),
    ("ok_reraise.py", "cancel-swallow", False),
    ("ok_classified.py", "cancel-swallow", False),
    ("ok_cancel_first.py", "cancel-swallow", False),
    ("ok_pragma.py", "cancel-swallow", False),
    ("ok_outside_scope.py", "cancel-swallow", False),
    ("fire_devget.py", "unaccounted-sync", True),
    ("ok_sync_event.py", "unaccounted-sync", False),
    ("fire_unregistered.py", "conf-vocabulary", True),
    ("ok_registered.py", "conf-vocabulary", False),
    ("fire_unlocked.py", "module-state", True),
    ("ok_locked.py", "module-state", False),
    ("ok_single_writer.py", "module-state", False),
    ("fire_mixed.py", "lock-mixed-guard", True),
    ("ok_guarded.py", "lock-mixed-guard", False),
    ("fire_inverted.py", "lock-order", True),
    ("fire_transitive.py", "lock-order", True),
    ("fire_sem_call_inverted.py", "lock-order", True),
    ("ok_consistent.py", "lock-order", False),
    ("fire_rmw.py", "unlocked-rmw", True),
    ("ok_rmw.py", "unlocked-rmw", False),
    # tracelint tier (ISSUE 11): firing + non-firing + pragma per rule
    ("fire_conf_read.py", "trace-conf-read", True),
    ("ok_conf_read.py", "trace-conf-read", False),
    ("pragma_conf_read.py", "trace-conf-read", False),
    ("fire_side_effect.py", "trace-side-effect", True),
    ("ok_side_effect.py", "trace-side-effect", False),
    ("pragma_side_effect.py", "trace-side-effect", False),
    ("fire_host_sync.py", "trace-host-sync", True),
    ("ok_host_sync.py", "trace-host-sync", False),
    ("pragma_host_sync.py", "trace-host-sync", False),
    ("fire_branch.py", "trace-branch", True),
    ("ok_branch.py", "trace-branch", False),
    ("pragma_branch.py", "trace-branch", False),
    # HOF body DEFINED INSIDE the kernel joins the region (regression:
    # _hof_fn_refs resolved fn args against the enclosing scope, so
    # nested bodies were invisible to every trace rule)
    ("fire_hof_nested.py", "trace-branch", True),
    ("fire_hof_nested.py", "trace-host-sync", True),
    ("fire_closure_state.py", "trace-closure-state", True),
    ("ok_closure_state.py", "trace-closure-state", False),
    ("pragma_closure_state.py", "trace-closure-state", False),
    ("fire_split_sync.py", "trace-split-sync", True),
    ("ok_split_sync.py", "trace-split-sync", False),
    ("pragma_split_sync.py", "trace-split-sync", False),
    ("fire_retrace_key.py", "retrace-key", True),
    ("ok_retrace_key.py", "retrace-key", False),
    ("pragma_retrace_key.py", "retrace-key", False),
]


@pytest.fixture(scope="module")
def fixture_rules():
    return _rules_by_file(_lint_fixtures())


@pytest.mark.parametrize("fname,rule,fires", _MATRIX,
                         ids=[f"{r}-{f}" for f, r, _ in _MATRIX])
def test_fixture_matrix(fixture_rules, fname, rule, fires):
    fired = rule in fixture_rules.get(fname, set())
    assert fired == fires, (
        f"{fname}: expected {rule} {'to fire' if fires else 'NOT to fire'}"
        f"; got rules {sorted(fixture_rules.get(fname, set()))}")


def test_pragma_suppresses_identical_code(fixture_rules):
    """fire_swallow.py and ok_pragma.py are the same handler; only the
    # tpulint: disable= pragma separates them."""
    assert "cancel-swallow" in fixture_rules["fire_swallow.py"]
    assert "cancel-swallow" not in fixture_rules.get("ok_pragma.py",
                                                     set())


def test_lock_order_cycle_names_both_directions():
    findings = [f for f in _lint_fixtures()
                if f.rule == "lock-order"
                and "fire_inverted" in f.file]
    assert len(findings) == 1
    msg = findings[0].message
    assert "SEMAPHORE->SPILL" in msg and "SPILL->SEMAPHORE" in msg


def test_sync_rule_flags_both_forms():
    """device_get AND block_until_ready each count."""
    findings = [f for f in _lint_fixtures()
                if f.rule == "unaccounted-sync"
                and "fire_devget" in f.file]
    assert len(findings) == 2


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_baseline_matches_and_staleness():
    findings = [f for f in _lint_fixtures()
                if os.path.basename(f.file) == "fire_direct.py"]
    assert findings
    entries = [{"rule": f.rule, "file": f.file, "context": f.context,
                "message": f.message, "justification": "fixture"}
               for f in findings]
    b = Baseline(entries)
    new, stale = b.split(findings)
    assert new == [] and stale == []
    # dropping one entry makes exactly that finding "new"
    b2 = Baseline(entries[1:])
    new2, _ = b2.split(findings)
    assert len(new2) == 1 and new2[0].identity == findings[0].identity
    # an entry that no longer fires is reported stale
    ghost = dict(entries[0])
    ghost["message"] = "no longer exists"
    _, stale3 = Baseline(entries + [ghost]).split(findings)
    assert stale3 == [ghost]


def test_baseline_requires_justification():
    with pytest.raises(ValueError, match="justification"):
        Baseline([{"rule": "x", "file": "y", "message": "z",
                   "justification": "  "}])


def test_shipped_baseline_every_entry_justified():
    with open(BASELINE) as f:
        data = json.load(f)
    for e in data.get("entries", []):
        assert str(e.get("justification", "")).strip(), e
    Baseline.load(BASELINE)   # loader enforces the same invariant


# ---------------------------------------------------------------------------
# determinism + the tier-1 repo gate
# ---------------------------------------------------------------------------

def _lint_repo():
    return run_paths([os.path.join(REPO, "spark_rapids_tpu"),
                      os.path.join(REPO, "tools")],
                     REPO, rules=default_rules(include_docs=True))


@pytest.fixture(scope="module")
def repo_lint():
    """THE whole-repo pass of this file (all rules incl. doc-drift) and
    its wall: (findings, seconds).  A test that needs the repo's
    findings takes them from here; only the determinism test runs a
    second pass."""
    t0 = time.monotonic()
    findings = _lint_repo()
    return findings, time.monotonic() - t0


def test_json_determinism_over_repo(repo_lint):
    """Two runs over the repo produce byte-identical JSON findings."""
    a = to_json(repo_lint[0])
    b = to_json(_lint_repo())
    assert a == b
    json.loads(a)             # well-formed


def test_repo_lint_gate(repo_lint):
    """The tier-1 gate: zero non-baselined findings over
    spark_rapids_tpu/ + tools/ (all rules incl. doc-drift), bounded
    runtime."""
    findings, elapsed = repo_lint
    new, stale = Baseline.load(BASELINE).split(findings)
    assert new == [], "non-baselined findings:\n" + "\n".join(
        f.render() for f in new)
    assert stale == [], f"stale baseline entries: {stale}"
    # BOTH tiers (invariants/lockset + tracelint) under one wall bound
    assert elapsed < 45.0, f"full-repo analysis took {elapsed:.1f}s"


def test_scoped_run_knows_repo_vocabulary():
    """A scoped run (`lint.py tools`) must judge conf reads against the
    WHOLE repo's declarations — keys declared in config.py are not
    false positives just because config.py was out of scope."""
    findings = run_paths([os.path.join(REPO, "tools")], REPO,
                         rules=default_rules(include_docs=False))
    assert [f for f in findings if f.rule == "conf-vocabulary"] == []


def test_analysis_package_self_clean():
    """Lint-rule self-application: analysis/ runs clean under its own
    rules (no pragmas, no baseline)."""
    findings = run_paths(
        [os.path.join(REPO, "spark_rapids_tpu", "analysis")],
        REPO, rules=default_rules(include_docs=False))
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# CLI exit-code contract (bench.py-independent)
# ---------------------------------------------------------------------------

def _cli(args, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py")] + args,
        cwd=cwd, capture_output=True, text=True, env=env, timeout=120)


def test_cli_clean_repo_exits_zero():
    """A real process over a clean tree exits 0 (scoped to tools/: the
    whole repo's cleanliness is test_repo_lint_gate's, in this
    process)."""
    r = _cli(["--fail-on-new", os.path.join(REPO, "tools")])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_new_finding_exits_one(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("COUNTERS = {}\n\n\ndef f():\n"
                   "    COUNTERS['x'] = 1\n")
    empty = tmp_path / "baseline.json"
    empty.write_text('{"entries": []}\n')
    r = _cli(["--fail-on-new", "--no-docs-rule",
              "--baseline", str(empty), str(bad)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "counter-write" in r.stdout
    # --json output is parseable and names the same finding
    r2 = _cli(["--json", "--no-docs-rule", "--baseline", str(empty),
               str(bad)])
    assert r2.returncode == 1
    payload = json.loads(r2.stdout)
    assert payload and payload[0]["rule"] == "counter-write"


# ---------------------------------------------------------------------------
# tracelint (ISSUE 11): fusibility manifest, SARIF, CLI satellites
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def repo_manifest():
    """THE fusibility manifest of this file; only the byte-identity
    test builds a second one."""
    from spark_rapids_tpu.analysis.fusibility import build_manifest

    return build_manifest(REPO)


def test_fusibility_manifest_covers_every_registered_exec(repo_manifest):
    """Every EXECS plan class has a classification; none is unknown."""
    from spark_rapids_tpu.overrides.overrides import EXECS

    m = repo_manifest
    ops = m["operators"]
    for cls in EXECS:
        assert cls.__name__ in ops, f"{cls.__name__} missing"
    for op, e in ops.items():
        c = e["classification"]
        assert c.split("(", 1)[0] in ("fusable", "fusable-with-rewrite",
                                      "unfusable"), (op, c)
        assert "unknown" not in c, (op, c)
    # the hot fusion targets classify as expected (pins the taint +
    # resolution machinery end-to-end)
    assert ops["HashAggregate"]["classification"] == "fusable"
    assert ops["Project"]["classification"].startswith(
        "fusable-with-rewrite")
    assert "TpuStageExec" in m["execs"]


def test_fusibility_manifest_byte_identical(repo_manifest):
    from spark_rapids_tpu.analysis.fusibility import (
        build_manifest,
        manifest_json,
    )

    a = manifest_json(repo_manifest)
    b = manifest_json(build_manifest(REPO))
    assert a == b
    json.loads(a)


def test_fusibility_manifest_drift_gate(repo_manifest):
    """ISSUE 17: the committed tools/fusibility_manifest.json must stay
    byte-identical to a fresh regeneration — the whole-plan fusion pass
    derives its eligible set from it, so a stale manifest silently
    changes what fuses.  Regenerate with
    ``python tools/fusibility.py --out tools/fusibility_manifest.json``."""
    from spark_rapids_tpu.analysis.fusibility import manifest_json

    committed = os.path.join(REPO, "tools", "fusibility_manifest.json")
    with open(committed, "r", encoding="utf-8") as f:
        on_disk = f.read()
    assert on_disk == manifest_json(repo_manifest), (
        "tools/fusibility_manifest.json is stale — regenerate with "
        "python tools/fusibility.py --out tools/fusibility_manifest.json")


def test_fusibility_cli_check_flag(tmp_path, monkeypatch, capsys,
                                   repo_manifest):
    """--check: exit 0 against the committed manifest, exit 1 on drift
    (the tool's ``main`` in this process, on the manifest the file
    already built)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_fusibility_cli", os.path.join(REPO, "tools", "fusibility.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(tool, "build_manifest",
                        lambda repo_root: repo_manifest)
    assert tool.main(["--check"]) == 0, capsys.readouterr().err
    stale = tmp_path / "stale.json"
    stale.write_text("{}\n")
    capsys.readouterr()
    assert tool.main(["--check", str(stale)]) == 1
    assert "stale" in capsys.readouterr().err


def test_sarif_deterministic_and_well_formed(tmp_path):
    """--sarif: byte-identical across runs, valid SARIF 2.1.0 shape,
    findings carry rule + location."""
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\n\n\n"
                   "def kernel(x):\n"
                   "    if jnp.max(x) > 0:\n"
                   "        x = x - 1\n"
                   "    return x\n\n\n"
                   "J = tpu_jit(kernel)\n")
    empty = tmp_path / "baseline.json"
    empty.write_text('{"entries": []}\n')
    s1, s2 = tmp_path / "a.sarif", tmp_path / "b.sarif"
    for out in (s1, s2):
        r = _cli(["--no-docs-rule", "--baseline", str(empty),
                  "--sarif", str(out), str(bad)])
        assert r.returncode == 1
    assert s1.read_bytes() == s2.read_bytes()
    payload = json.loads(s1.read_text())
    assert payload["version"] == "2.1.0"
    results = payload["runs"][0]["results"]
    assert results and results[0]["ruleId"] == "trace-branch"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["region"]["startLine"] == 5
    rule_ids = {r["id"] for r in
                payload["runs"][0]["tool"]["driver"]["rules"]}
    assert "trace-branch" in rule_ids and "lock-order" in rule_ids


def test_cli_rules_scoping(tmp_path):
    """--rules scopes the run; unknown ids exit 2."""
    bad = tmp_path / "bad.py"
    bad.write_text("COUNTERS = {}\n\n\ndef f():\n"
                   "    COUNTERS['x'] = 1\n")
    empty = tmp_path / "baseline.json"
    empty.write_text('{"entries": []}\n')
    # counter-write fires when in scope...
    r = _cli(["--no-docs-rule", "--rules", "counter-write",
              "--baseline", str(empty), str(bad)])
    assert r.returncode == 1 and "counter-write" in r.stdout
    # ...and is silent when scoped to an unrelated rule
    r2 = _cli(["--no-docs-rule", "--rules", "trace-branch",
               "--baseline", str(empty), str(bad)])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    r3 = _cli(["--no-docs-rule", "--rules", "no-such-rule", str(bad)])
    assert r3.returncode == 2
    assert "unknown rule id" in r3.stderr


def test_cli_stale_count_and_prune(tmp_path):
    """The stale-entry count prints on every run; --prune-baseline
    drops entries that no longer fire and keeps the rest."""
    bad = tmp_path / "bad.py"
    bad.write_text("COUNTERS = {}\n\n\ndef f():\n"
                   "    COUNTERS['x'] = 1\n")
    # repo_root must match the CLI's (tools/lint.py anchors at REPO) so
    # the baseline identity's file field lines up
    findings = run_paths([str(bad)], REPO,
                         rules=default_rules(include_docs=False))
    assert findings
    live = {"rule": findings[0].rule, "file": findings[0].file,
            "context": findings[0].context,
            "message": findings[0].message, "justification": "fixture"}
    ghost = dict(live, message="no longer fires")
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"entries": [live, ghost]}) + "\n")
    r = _cli(["--no-docs-rule", "--baseline", str(base), str(bad)],
             cwd=str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 stale baseline entry" in r.stderr
    r2 = _cli(["--no-docs-rule", "--baseline", str(base),
               "--prune-baseline", str(bad)], cwd=str(tmp_path))
    assert r2.returncode == 0, r2.stdout + r2.stderr
    kept = json.loads(base.read_text())["entries"]
    assert len(kept) == 1 and kept[0]["message"] == live["message"]
    # a clean run reports zero stale
    r3 = _cli(["--no-docs-rule", "--baseline", str(base), str(bad)],
              cwd=str(tmp_path))
    assert "0 stale baseline entries" in r3.stderr


# ---------------------------------------------------------------------------
# regression pins for the real findings ISSUE 9 fixed
# ---------------------------------------------------------------------------

def test_serialize_batch_is_one_logical_sync():
    """shuffle/serializer.py: the whole-batch fetch counts ONE
    host_syncs round trip (it used to count one per column leaf)."""
    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.shuffle.serializer import serialize_batch

    schema = T.StructType([T.StructField("i", T.INT),
                           T.StructField("d", T.DOUBLE),
                           T.StructField("s", T.STRING)])
    b = ColumnarBatch.from_pydict(
        {"i": [1, 2, None], "d": [0.5, None, 1.5],
         "s": ["a", None, "bc"]}, schema)
    snap = PC.snapshot()
    serialize_batch(b, codec="none")
    assert PC.since(snap)["host_syncs"] == 1


@pytest.mark.parametrize("which", ["csv", "json"])
def test_text_fast_path_propagates_cancellation(monkeypatch, tmp_path,
                                                which):
    """io/text.py: a PROPAGATE-class failure (tripped CancelToken)
    escaping the fast parse path must unwind, not silently degrade to
    the strict loop."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.io import text as TX
    from spark_rapids_tpu.lifecycle.context import QueryCancelled

    schema = T.StructType([T.StructField("a", T.INT)])
    if which == "csv":
        p = tmp_path / "t.csv"
        p.write_text("1\n2\n")
        entry, fast = TX._read_csv_spark, "_read_csv_fast"
    else:
        p = tmp_path / "t.json"
        p.write_text('{"a": 1}\n')
        entry, fast = TX._read_json_spark, "_read_json_fast"

    def boom(*a, **k):
        raise QueryCancelled("q1: cancelled mid-scan")

    monkeypatch.setattr(TX, fast, boom)
    with pytest.raises(QueryCancelled):
        entry(str(p), schema, {})

    # a non-PROPAGATE surprise still degrades to the strict loop
    def surprise(*a, **k):
        raise ValueError("fast-path surprise")

    monkeypatch.setattr(TX, fast, surprise)
    cols, n = entry(str(p), schema, {})
    assert n >= 1


def test_shuffle_manager_counters_survive_concurrency():
    """shuffle/manager.py: bytes_written/blocks_written increments are
    locked — N racing writers lose no updates."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

    schema = T.StructType([T.StructField("i", T.INT)])
    mgr = TpuShuffleManager(TpuConf())
    assert mgr.mode == "MULTITHREADED"
    n_threads, maps_per_thread, parts = 8, 4, 3
    batch = ColumnarBatch.from_pydict({"i": list(range(16))}, schema)
    sids = [mgr.register_shuffle() for _ in range(n_threads)]
    barrier = threading.Barrier(n_threads)
    errs = []

    def writer(tid):
        try:
            barrier.wait()
            for m in range(maps_per_thread):
                mgr.write_map_output(sids[tid], m, [batch] * parts)
        except Exception as e:          # surfaced via errs
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert errs == []
        assert mgr.blocks_written == n_threads * maps_per_thread * parts
        assert mgr.bytes_written > 0
    finally:
        for sid in sids:
            mgr.unregister_shuffle(sid)


def test_bounds_scope_is_thread_local():
    """ops/segment.py: one query's ambient SegBounds must not leak into
    a concurrently tracing query's trace (the stack is per-thread)."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.segment import (
        SegBounds,
        _active_bounds,
        bounds_scope,
    )

    seg_ids = jnp.array([0, 0, 1, 2], dtype=jnp.int32)
    a_in = threading.Event()
    b_in = threading.Event()
    results = {}

    def thread_a():
        ba = SegBounds(seg_ids, 3)
        with bounds_scope(ba):
            a_in.set()
            b_in.wait(5)
            results["a"] = _active_bounds(3, None) is ba

    def thread_b():
        a_in.wait(5)
        bb = SegBounds(seg_ids, 3)
        with bounds_scope(bb):
            results["b"] = _active_bounds(3, None) is bb
            b_in.set()

    ta = threading.Thread(target=thread_a)
    tb = threading.Thread(target=thread_b)
    ta.start()
    tb.start()
    ta.join(10)
    tb.join(10)
    assert results == {"a": True, "b": True}
    # outside any scope on THIS thread: no ambient bounds
    assert _active_bounds(3, None) is None


def test_arm_conf_spec_races_arm_once():
    """resilience/faults.py: concurrent collects racing the same NEW
    testInject spec arm it exactly once."""
    from spark_rapids_tpu.resilience import faults as F

    F.clear_faults()
    try:
        spec = "transient:TpuSortExec:1"
        n_threads = 8
        barrier = threading.Barrier(n_threads)
        armed = []

        def arm():
            barrier.wait()
            armed.append(F.arm_conf_spec(spec))

        threads = [threading.Thread(target=arm)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(armed) == 1, armed
        assert len(F.active_faults()) == 1
    finally:
        F.clear_faults()


def test_stage_ansi_flags_are_one_logical_sync():
    """exec/basic.py: an ANSI stage's row count + every error flag
    materialize as ONE logical round trip (a per-flag bool() used to be
    one device sync per flag per batch)."""
    import numpy as np

    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import HostColumn
    from spark_rapids_tpu.exec.basic import (
        TpuLocalTableScanExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.expr.base import Alias, col, lit

    schema = T.StructType([T.StructField("v", T.LONG, False)])
    host = [HostColumn.from_numpy(np.arange(6, dtype=np.int64), T.LONG)]
    scan = TpuLocalTableScanExec(host, schema)
    e = Alias((col("v") + lit(1)).resolve(schema), "v1")
    e.resolve(schema)
    proj = TpuProjectExec([e], scan, True)   # ANSI: overflow flag
    snap = PC.snapshot()
    outs = list(proj.execute_columnar())
    assert [b.num_rows for b in outs] == [6]
    assert PC.since(snap)["host_syncs"] == 1


def test_expand_ansi_flags_are_one_logical_sync():
    """exec/generate.py TpuExpandExec: all of one projection's error
    flags fetch as ONE logical sync."""
    import numpy as np

    from spark_rapids_tpu import perfcounters as PC
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.column import HostColumn
    from spark_rapids_tpu.exec.basic import TpuLocalTableScanExec
    from spark_rapids_tpu.exec.generate import TpuExpandExec
    from spark_rapids_tpu.expr.base import Alias, col, lit

    schema = T.StructType([T.StructField("v", T.LONG, False)])
    out_schema = T.StructType([T.StructField("a", T.LONG, True),
                               T.StructField("b", T.LONG, True)])
    host = [HostColumn.from_numpy(np.arange(5, dtype=np.int64), T.LONG)]
    scan = TpuLocalTableScanExec(host, schema)
    exprs = []
    for name, add in (("a", 2), ("b", 3)):
        e = Alias((col("v") + lit(add)).resolve(schema), name)
        e.resolve(schema)
        exprs.append(e)
    # TWO ANSI-flagged projections: the old per-flag bool() cost two
    # round trips here, the batched fetch costs one
    expand = TpuExpandExec([exprs], scan, out_schema, ansi=True)
    snap = PC.snapshot()
    outs = list(expand.execute_columnar())
    assert [b.num_rows for b in outs] == [5]
    assert PC.since(snap)["host_syncs"] == 1


def test_fused_agg_tag_never_uses_raw_id(monkeypatch):
    """exec/fused.py: an unfingerprintable agg variant gets a
    process-unique tag PINNED on the object (a raw id() can be reused
    after GC, aliasing two different aggs to one registry program), and
    a private tag forces the program out of the shared registry."""
    import types as pytypes

    from spark_rapids_tpu.exec import fused as FU

    class FakeAgg:
        def _program_fp(self):
            return None

    exec_ = object.__new__(FU.TpuJoinAggFusedExec)
    a, b = FakeAgg(), FakeAgg()
    ta, tb = exec_._agg_tag(a), exec_._agg_tag(b)
    assert ta != tb                       # distinct objects: distinct
    assert exec_._agg_tag(a) == ta        # stable per object
    assert ta[:1] == ("private",)
    # fingerprintable aggs keep their shared identity
    good = pytypes.SimpleNamespace(_program_fp=lambda: ("fp", 1))
    assert exec_._agg_tag(good) == ("fp", 1)

    # a private tag in the key must force key_parts=None (instance-
    # private jit) — never a process-wide registry entry
    captured = {}

    def fake_cached_jit_program(key_parts, builder, label=""):
        captured["key_parts"] = key_parts
        return object()

    import spark_rapids_tpu.compilecache.registry as REG

    monkeypatch.setattr(REG, "cached_jit_program",
                        fake_cached_jit_program)
    exec_._jit_cache = {}
    exec_._reg_scope = ("joinagg", "scope")
    exec_._cached(("uniq_agg", ta, None), lambda: None)
    assert captured["key_parts"] is None
    exec_._cached(("uniq_agg", ("fp", 1), None), lambda: None)
    assert captured["key_parts"] == ("joinagg", "scope",
                                     ("uniq_agg", ("fp", 1), None))


def test_arm_conf_spec_bad_spec_mutates_nothing():
    """A spec that fails to parse leaves the previous arming fully
    intact (no partially-armed faults, spec un-claimed), and a
    corrected retry arms cleanly."""
    from spark_rapids_tpu.resilience import faults as F

    F.clear_faults()
    try:
        assert F.arm_conf_spec("transient:TpuSortExec:1") == 1
        with pytest.raises(ValueError):
            F.arm_conf_spec("transient:TpuFilterExec:1;badpart")
        # previous spec still armed, exactly as before the bad call
        assert [(op, k) for op, k, _ in F.active_faults()] == [
            ("TpuSortExec", "transient")]
        # a corrected spec replaces it atomically
        assert F.arm_conf_spec("oom:TpuFilterExec:1") == 1
        assert [(op, k) for op, k, _ in F.active_faults()] == [
            ("TpuFilterExec", "oom")]
    finally:
        F.clear_faults()


# ---------------------------------------------------------------------------
# the suite's own ids (ISSUE 31)
# ---------------------------------------------------------------------------

def test_test_ids_are_stable_and_unique(request):
    """No id of the session holds an object address (an id made of a
    default ``repr`` differs between two runs, so a failed name cannot be
    found again, and between two xdist workers, so ``-n`` cannot collect)
    and no two tests share one."""
    import re

    ids = [item.nodeid for item in request.session.items]
    addressed = [i for i in ids if re.search(r"0x[0-9a-fA-F]{6,}", i)]
    assert addressed == [], (
        "ids made of an object address (give the parameter a __repr__ "
        f"or an ids=): {addressed[:10]}")
    seen, twice = set(), set()
    for i in ids:
        (twice if i in seen else seen).add(i)
    assert twice == set(), f"duplicate test ids: {sorted(twice)[:10]}"
