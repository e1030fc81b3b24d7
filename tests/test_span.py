"""``perfcounters.span`` (ISSUE 26): the one timing primitive of a
collect.  The arithmetic on a fake clock, the thread-local stacks, every
unwind, the counters it feeds and leaves alone, the spans a collect of
each benchmark plan opens, the events it writes into a profiler trace,
the program names and the adaptive join's inner exec."""
import ast
import functools
import glob
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from spark_rapids_tpu import perfcounters as PC

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Clock:
    def __init__(self):
        self.t = 1000

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(PC, "_clock", c)
    return c


def _table(delta):
    """{path: (n, inclusive, self)} of a since() delta."""
    return {k.split("|", 1)[1]: (v, delta["span_ns|" + k.split("|", 1)[1]],
                                 delta["span_self_ns|" + k.split("|", 1)[1]])
            for k, v in delta.items() if k.startswith("span_n|") and v}


# ---------------------------------------------------------------------------
# the arithmetic
# ---------------------------------------------------------------------------

def test_nesting_and_self_time_on_a_fake_clock(clock):
    snap = PC.snapshot()
    with PC.span("srt.t.a") as a:
        clock.t += 5
        with PC.span("srt.t.b"):
            clock.t += 20
            with PC.span("srt.t.c"):
                clock.t += 300
        with PC.span("srt.t.b"):
            clock.t += 4000
        clock.t += 50000
        # nothing is merged while the outermost span is open
        assert not _table(PC.since(snap))
    assert a.ns == 54325
    assert _table(PC.since(snap)) == {
        "srt.t.a": (1, 54325, 50005),
        "srt.t.a/srt.t.b": (2, 4320, 4020),
        "srt.t.a/srt.t.b/srt.t.c": (1, 300, 300)}
    # self times add up to the outermost span's inclusive time
    assert sum(s for _, _, s in _table(PC.since(snap)).values()) == a.ns


def test_a_second_outermost_span_adds_to_the_same_keys(clock):
    snap = PC.snapshot()
    for dt in (7, 11):
        with PC.span("srt.t.again"):
            clock.t += dt
    assert _table(PC.since(snap)) == {"srt.t.again": (2, 18, 18)}


def test_one_span_object_opens_again_once_closed(clock, monkeypatch):
    """The runtime loop keeps one span per operator iterator: every
    opening counts, and under a profiler session every opening is an
    event of its own."""
    opened = []

    class Ann:
        def __init__(self, name, **ids):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(PC, "_TraceAnnotation", Ann)
    monkeypatch.setattr(PC, "_tracing", lambda: True)
    snap = PC.snapshot()
    sp = PC.span("srt.t.pull")
    with PC.span("srt.t.loop"):
        for dt in (3, 40, 500):
            with sp:
                clock.t += dt
            assert sp.ns == dt
    assert _table(PC.since(snap))["srt.t.loop/srt.t.pull"] == (3, 543, 543)
    assert opened == ["srt.t.loop"] + ["srt.t.pull"] * 3


def test_feeds_keeps_an_old_counter(clock):
    snap = PC.snapshot()
    with PC.span("srt.t.outer"):
        with PC.span("srt.t.fed", feeds="scan_transfer_ns") as sp:
            clock.t += 123
    d = PC.since(snap)
    assert sp.ns == 123 == d["scan_transfer_ns"]
    assert d["span_ns|srt.t.outer/srt.t.fed"] == 123


def test_since_carries_the_span_keys_and_reset_clears_them(clock):
    with PC.span("srt.t.reset"):
        clock.t += 1
    snap = PC.snapshot()
    assert snap["span_n|srt.t.reset"] >= 1
    with PC.span("srt.t.reset"):
        clock.t += 9
    d = PC.since(snap)
    assert (d["span_n|srt.t.reset"], d["span_ns|srt.t.reset"],
            d["span_self_ns|srt.t.reset"]) == (1, 9, 9)
    before = PC.snapshot()
    try:
        PC.reset()
        cur = PC.snapshot()
        assert not [k for k in cur if k.startswith(PC.SPAN_KEYS)]
        assert cur["host_syncs"] == 0 and "host_syncs" in cur
    finally:
        with PC._LOCK:          # the other tests' snapshots stay valid
            PC.COUNTERS.update(before)


# ---------------------------------------------------------------------------
# threads and unwinds
# ---------------------------------------------------------------------------

def test_two_threads_keep_separate_stacks_and_both_merge():
    snap = PC.snapshot()
    inside = threading.Barrier(2)
    errors = []

    def work(name):
        try:
            with PC.span("srt.t." + name):
                inside.wait(5)          # both outermost spans are open
                with PC.span("srt.t.leaf"):
                    inside.wait(5)
                inside.wait(5)
        except Exception as e:          # pragma: no cover
            errors.append(e)

    ts = [threading.Thread(target=work, args=(n,)) for n in ("x", "y")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors
    tab = _table(PC.since(snap))
    assert set(tab) == {"srt.t.x", "srt.t.x/srt.t.leaf",
                        "srt.t.y", "srt.t.y/srt.t.leaf"}
    assert all(n == 1 for n, _, _ in tab.values())


def test_a_span_closes_on_an_exception_and_does_not_swallow_it(clock):
    snap = PC.snapshot()
    with pytest.raises(ValueError, match="boom"):
        with PC.span("srt.t.raises"):
            with PC.span("srt.t.inner"):
                clock.t += 3
                raise ValueError("boom")
    assert _table(PC.since(snap)) == {
        "srt.t.raises": (1, 3, 0), "srt.t.raises/srt.t.inner": (1, 3, 3)}
    assert PC._tls.top is None


def test_a_span_closes_when_its_generator_is_closed(clock):
    def gen():
        while True:
            with PC.span("srt.t.pull"):     # around the work, not the yield
                clock.t += 2
            yield 1

    def bad():
        with PC.span("srt.t.held"):         # held across the yield
            clock.t += 1
            yield 1

    snap = PC.snapshot()
    with PC.span("srt.t.consumer"):
        g = gen()
        next(g), next(g)
        g.close()
        b = bad()
        next(b)                 # suspended with its span open
        b.close()               # GeneratorExit unwinds the with
        assert PC._tls.top.name == "srt.t.consumer"
    tab = _table(PC.since(snap))
    assert tab["srt.t.consumer/srt.t.pull"] == (2, 4, 4)
    assert tab["srt.t.consumer/srt.t.held"][0] == 1
    assert PC._tls.top is None


def test_a_span_left_open_by_a_suspended_generator_corrupts_nothing(clock):
    """The generator suspends inside its span; the thread goes on, opens
    and closes spans above it, closes its own span below it.  The
    abandoned span is dropped; every other one is recorded; the stack
    ends empty."""
    def bad():
        with PC.span("srt.t.abandoned"):
            yield 1

    snap = PC.snapshot()
    with PC.span("srt.t.outer"):
        b = bad()
        next(b)
        with PC.span("srt.t.after"):
            clock.t += 5
        clock.t += 1
    assert PC._tls.top is None
    tab = _table(PC.since(snap))
    assert tab["srt.t.outer"][:2] == (1, 6)
    assert tab["srt.t.outer/srt.t.abandoned/srt.t.after"] == (1, 5, 5)
    assert "srt.t.outer/srt.t.abandoned" not in tab
    b.close()                   # closes a dead span: nothing happens
    assert PC._tls.top is None
    with PC.span("srt.t.next"):
        clock.t += 2
    assert _table(PC.since(snap))["srt.t.next"] == (1, 2, 2)


def test_a_span_closed_by_another_thread_is_dropped_by_its_own(clock):
    snap = PC.snapshot()
    sp = PC.span("srt.t.foreign")
    sp.__enter__()
    t = threading.Thread(target=sp.__exit__, args=(None, None, None))
    t.start()
    t.join()
    assert sp._path is None     # dropped
    with PC.span("srt.t.mine"):         # the owner drops the dead span
        clock.t += 4
    assert PC._tls.top is None
    assert _table(PC.since(snap)) == {"srt.t.mine": (1, 4, 4)}


def test_bind_owner_carries_the_submitters_ids_to_a_pool_thread():
    seen = {}

    def job():
        with PC.span("srt.t.job") as sp:
            seen["ids"] = sp.ids
        return PC._tls.ids

    with PC.span("srt.t.client") as root:
        assert PC.bind_owner(job) is job        # no ids yet: nothing to carry
        root.annotate(query_id="q42", trace_id="t-42")
        with PC.span("srt.t.nested"):
            owned = PC.bind_owner(job)
    out = {}
    t = threading.Thread(target=lambda: out.setdefault("ids", owned()))
    t.start()
    t.join()
    assert seen["ids"] == {"query_id": "q42", "trace_id": "t-42"}
    assert out["ids"] == seen["ids"]


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _span_names():
    """Every literal name passed to span() in the program."""
    names = {}
    for path in glob.glob(os.path.join(ROOT, "spark_rapids_tpu", "**",
                                       "*.py"), recursive=True):
        with open(path) as f:
            src = f.read()
        if "span(" not in src:
            continue
        for node in ast.walk(ast.parse(src)):
            f = node.func if isinstance(node, ast.Call) else None
            # span(...) / _span(...) imported from perfcounters, or
            # PC.span(...) / _PC.span(...): no other object's .span
            ours = (isinstance(f, ast.Name) and f.id in ("span", "_span")) \
                or (isinstance(f, ast.Attribute) and f.attr == "span"
                    and isinstance(f.value, ast.Name)
                    and f.value.id in ("PC", "_PC"))
            if ours and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                names.setdefault(node.args[0].value, []).append(
                    os.path.relpath(path, ROOT))
    return names


SPAN_TABLE = {
    "srt.collect", "srt.admit", "srt.observe", "srt.plan", "srt.prepare",
    "srt.execute", "srt.launch", "srt.sync", "srt.rows", "srt.scan.read", "srt.scan.to_columns", "srt.scan.h2d",
    "srt.scan.device_decode", "srt.scan.prefetch_wait",
    "srt.exchange.partition", "srt.exchange.queue", "srt.join.build",
    "srt.join.probe", "srt.join.materialize", "srt.join.unique",
    "srt.join.lookup", "srt.joinagg.unique", "srt.joinagg.probe_sizes",
    "srt.joinagg.mat_agg", "srt.ici.partial", "srt.ici.exchange",
    "srt.ici.finalize", "srt.ici.emit"}


def test_span_names_are_the_documented_table_and_none_is_collect():
    names = _span_names()
    assert set(names) == SPAN_TABLE
    for name in names:
        # benchmark/harness/trace.py counts host events named "collect"
        assert name.startswith("srt.") and name != "collect"
        assert "/" not in name and "|" not in name
    with open(os.path.join(ROOT, "docs", "diagnostics.md")) as f:
        doc = f.read()
    for name in sorted(SPAN_TABLE | {"srt.op.<node_name>"}):
        assert f"`{name}`" in doc, name


@pytest.mark.parametrize("name,files", [
    ("srt.collect", ["session.py"]),
    ("srt.plan", ["session.py"]),
    ("srt.execute", ["exec/transitions.py"]),
    ("srt.launch", ["perfcounters.py"]),
    ("srt.scan.h2d", ["io/scan.py"]),
    ("srt.exchange.partition", ["exec/exchange.py"]),
    ("srt.joinagg.unique", ["exec/fused.py"]),
    ("srt.ici.partial", ["exec/ici.py"]),
])
def test_a_span_has_one_site(name, files):
    assert _span_names()[name] == [
        os.path.join("spark_rapids_tpu", f) for f in files]


def test_the_hand_rolled_timer_pairs_are_gone_from_the_span_sites():
    for rel, left in [("exec/exchange.py", 0), ("exec/runtime.py", 0),
                      ("shuffle/partition_queues.py", 0),
                      ("perfcounters.py", 1)]:     # the clock itself
        with open(os.path.join(ROOT, "spark_rapids_tpu", rel)) as f:
            assert f.read().count("perf_counter_ns") == left, rel
    with open(os.path.join(ROOT, "spark_rapids_tpu", "io", "scan.py")) as f:
        src = f.read()
    # what is left in the scan is progress/'s background attribution
    assert src.count("perf_counter_ns") == 2
    assert 'feeds="scan_transfer_ns"' in src
    assert 'feeds="prefetch_stall_ns"' in src


# ---------------------------------------------------------------------------
# the sync span
# ---------------------------------------------------------------------------

def test_the_sync_span_counts_syncs_and_bytes_as_before():
    x = jnp.arange(8, dtype=jnp.int32)
    snap = PC.snapshot()
    assert int(x[3]) == 3
    d = PC.since(snap)
    assert d["host_syncs"] == 1 and d["bytes_d2h"] == 4
    assert d["span_n|srt.sync"] == 1

    snap = PC.snapshot()
    out = PC.sync_get({"a": x, "b": jnp.ones(8, jnp.float32)})
    d = PC.since(snap)
    assert out["a"][2] == 2
    assert d["host_syncs"] == 1 and d["bytes_d2h"] == 64
    # one span for the batched fetch, none for its leaves
    assert d["span_n|srt.sync"] == 1


def test_a_sync_is_one_await(monkeypatch):
    """The span times the real read and nothing else: no
    ``block_until_ready`` or ``copy_to_host_async`` ahead of it (a second
    wake-up of the client's thread, 0.3 ms a collect on the chip)."""
    from jax._src import array as jarray

    calls = []
    for name in ("block_until_ready", "copy_to_host_async"):
        real = getattr(jarray.ArrayImpl, name)
        monkeypatch.setattr(
            jarray.ArrayImpl, name,
            lambda self, *a, _n=name, _r=real, **kw:
                (calls.append(_n), _r(self, *a, **kw))[1])
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda t: calls.append("jax.block_until_ready"))
    x = jnp.arange(8, dtype=jnp.int32) + 1
    snap = PC.snapshot()
    assert float(x[0]) == 1.0 and bool(x[1]) and int(x[3]) == 4
    assert PC.sync_get({"a": x})["a"][2] == 3
    assert PC.since(snap)["span_n|srt.sync"] == 4
    assert "block_until_ready" not in calls
    assert "jax.block_until_ready" not in calls


def test_nested_sync_events_count_once_and_leaves_open_no_span():
    y = jnp.arange(8)
    snap = PC.snapshot()
    with PC.span("srt.t.sync"):
        with PC.sync_event():
            PC.sync_get({"a": y})           # nested: part of the same trip
            with PC.sync_event():
                int(y[0])                   # a leaf inside an event
    d = PC.since(snap)
    assert d["host_syncs"] == 1
    tab = _table(d)
    assert tab["srt.t.sync/srt.sync"][0] == 1
    assert not [p for p in tab if p.count("srt.sync") > 1]


def test_a_batched_sync_reports_its_span_s_time_to_the_recorder(monkeypatch):
    from spark_rapids_tpu.diagnostics import context as DIAG

    class Rec:
        durs = []

        def attribute(self, key, n):
            pass

        def d2h(self, nbytes, counted):
            pass

        def sync_batched(self, dur_ns):
            self.durs.append(dur_ns)

    y = jnp.arange(8)
    monkeypatch.setattr(DIAG, "RECORDER", Rec())
    snap = PC.snapshot()
    PC.sync_get((y, y))
    d = PC.since(snap)
    assert Rec.durs == [d["span_ns|srt.sync"]]


def test_a_launch_feeds_launch_wall_ns_from_its_span():
    fn = PC.tpu_jit(lambda x: x + 1, "add_one")
    x = jnp.arange(4)
    fn(x)
    snap = PC.snapshot()
    with PC.span("srt.t.launch"):
        fn(x)
    d = PC.since(snap)
    assert d["programs_launched"] == 1 and d["compiles"] == 0
    assert d["launch_wall_ns"] == d["span_ns|srt.t.launch/srt.launch"] > 0


# ---------------------------------------------------------------------------
# a collect of each benchmark plan
# ---------------------------------------------------------------------------

_COLLECT = {"srt.collect", "srt.admit", "srt.observe", "srt.plan",
            "srt.prepare", "srt.execute", "srt.launch", "srt.sync",
            "srt.rows"}
REACHED = {
    "q6_resident": _COLLECT | {
        "srt.op.TpuLocalTableScanExec"},
    "q6_parquet_scan": _COLLECT | {
        "srt.op.TpuFileSourceScanExec", "srt.scan.read",
        "srt.scan.to_columns", "srt.scan.h2d"},
    "ds_shuffled_join": _COLLECT | {
        "srt.op.TpuHashAggregateExec", "srt.op.TpuAdaptiveJoinExec",
        "srt.op.TpuShuffledSymmetricHashJoinExec",
        "srt.op.TpuShuffleExchangeExec", "srt.op.TpuLocalTableScanExec",
        "srt.join.build", "srt.join.unique", "srt.join.lookup"},
}


def _cell_frame(name, tmp_path, conf=None):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    from benchmark.harness import cell as C
    from benchmark.harness.manifest import Manifest
    from spark_rapids_tpu.session import TpuSession
    from test_benchmark_harness import tiny

    cell = tiny(Manifest().cell(name))
    tables = C.make_tables(cell, 2**31 + 26)
    session = TpuSession({**cell.conf, **(conf or {})})
    frames = C.make_frames(cell, session, tables, str(tmp_path))
    return cell.query.build(frames)


@pytest.mark.parametrize("name", sorted(REACHED))
def test_one_collect_yields_every_span_its_plan_can_reach(name, tmp_path):
    df = _cell_frame(name, tmp_path)
    df.collect()
    df.collect()
    snap = PC.snapshot()
    rows = df.collect()
    d = PC.since(snap)
    assert rows
    tab = _table(d)
    parts = {p for path in tab for p in path.split("/")}
    assert REACHED[name] <= parts, REACHED[name] - parts
    assert tab["srt.collect"][0] == 1
    # everything the client's thread did lies beneath srt.collect, whose
    # self times add up to its inclusive time
    under = {p: v for p, v in tab.items() if p.startswith("srt.collect")}
    assert sum(s for _, _, s in under.values()) == tab["srt.collect"][1]
    # the old counters are fed by the spans that replaced their timers
    launches = sum(ns for p, (_, ns, _) in tab.items()
                   if p.endswith("/srt.launch"))
    assert d["launch_wall_ns"] == launches
    if name == "q6_parquet_scan":
        h2d = sum(ns for p, (_, ns, _) in tab.items()
                  if p.endswith("srt.scan.h2d"))
        assert d["scan_transfer_ns"] == h2d > 0
        # the staging thread's spans are roots of their own
        assert "srt.scan.h2d" in tab and "srt.scan.to_columns" in tab


def test_a_partitioned_exchange_opens_its_spans(tmp_path):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.session import TpuSession, sum_

    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.sql.shuffle.partitions": 4})
    schema = T.StructType([T.StructField("k", T.INT),
                           T.StructField("v", T.LONG)])
    df = s.create_dataframe({"k": [i % 7 for i in range(200)],
                             "v": list(range(200))}, schema)
    q = df.repartition(4, "k").group_by("k").agg(sum_("v", "s"))
    q.collect()
    snap = PC.snapshot()
    assert len(q.collect()) == 7
    d = PC.since(snap)
    parts = {p for path in _table(d) for p in path.split("/")}
    assert {"srt.exchange.partition", "srt.exchange.queue",
            "srt.op.TpuShuffleExchangeExec"} <= parts
    part = sum(v for k, v in d.items() if k.startswith("span_ns|")
               and k.endswith("/srt.exchange.partition"))
    assert d["exchange_partition_ns"] == part > 0
    queue = sum(v for k, v in d.items() if k.startswith("span_ns|")
                and k.endswith("/srt.exchange.queue"))
    assert d["exchange_spill_ns"] == queue > 0


def test_a_traced_collect_writes_its_spans_into_the_profilers_trace(
        tmp_path):
    """The spans are TraceAnnotations on the profiler's clock, nested in
    the caller's own ``collect`` span on the caller's thread, and
    ``srt.collect`` carries the ids of the lifecycle context."""
    import jax
    from jax.profiler import ProfileData

    from benchmark.harness.trace import find_xplane

    df = _cell_frame("q6_resident", tmp_path / "data")
    df.collect()
    trace_dir = str(tmp_path / "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("collect"):
            df.collect()
    finally:
        jax.profiler.stop_trace()
    lines = [ln for plane in ProfileData.from_file(
                 find_xplane(trace_dir)).planes
             if plane.name.startswith("/host:CPU") for ln in plane.lines]
    client = [ln for ln in lines
              if any(ev.name == "collect" for ev in ln.events)]
    assert len(client) == 1
    events = {}
    for ev in client[0].events:
        events.setdefault(ev.name, []).append(ev)
    assert len(events["collect"]) == 1      # no program span of that name
    outer = events["collect"][0]
    for name in ("srt.collect", "srt.admit", "srt.plan", "srt.prepare",
                 "srt.execute", "srt.sync", "srt.rows",
                 "srt.op.TpuLocalTableScanExec"):
        assert name in events, sorted(events)
        for ev in events[name]:
            assert outer.start_ns <= ev.start_ns
            assert ev.start_ns + ev.duration_ns \
                <= outer.start_ns + outer.duration_ns
    assert "srt.launch" not in events       # table only
    stats = dict(events["srt.collect"][0].stats)
    assert stats["query_id"].startswith("q") and stats["trace_id"]


# ---------------------------------------------------------------------------
# program names
# ---------------------------------------------------------------------------

def test_the_join_cells_programs_have_names_of_their_own(tmp_path):
    df = _cell_frame("ds_shuffled_join", tmp_path)
    df.collect()
    join = _find_exec(df._planned()[0], "TpuAdaptiveJoinExec").shuffled
    names = sorted(j.__wrapped__.__name__
                   for j in (c._jitted for c in join._jit_cache.values()))
    # the store_returns keys are unique: a lookup, no pairs
    assert names == ["join_build", "join_has_dup", "join_lookup"]


@pytest.mark.parametrize("key,name", [
    ("covered", "join_covered"), (("mat", 8, True), "join_materialize"),
    (("build", "fp"), "join_build"), (("probe", "fp"), "join_probe"),
    (("semi", True, "fp"), "join_semi"), ("has_dup", "join_has_dup"),
    (("lookup", "fp"), "join_lookup")])
def test_join_program_names(key, name):
    from spark_rapids_tpu.exec.join import _program_name

    assert _program_name(key) == name


def test_tpu_jit_names_a_function_and_leaves_the_registry_key_alone():
    def body(x):
        return x * 2

    fn = PC.tpu_jit(body, "doubled")
    # the caller's own function, renamed in place: no wrapper frame
    assert fn._jitted.__wrapped__ is body and body.__name__ == "doubled"
    assert int(fn(jnp.int32(4))) == 8
    from spark_rapids_tpu.compilecache.registry import cached_jit_program

    a = cached_jit_program(("span-test", 1), lambda x: x + 1, name="plus")
    b = cached_jit_program(("span-test", 1), lambda x: x + 1, name="other")
    assert a is b                   # the name is no part of the key
    assert a._jitted.__wrapped__.__name__ == "plus"


# ---------------------------------------------------------------------------
# the adaptive join surfaces the join that runs inside it
# ---------------------------------------------------------------------------

def _find_exec(node, cls_name):
    if type(node).__name__ == cls_name:
        return node
    for c in getattr(node, "children", []):
        hit = _find_exec(c, cls_name)
        if hit is not None:
            return hit


@pytest.mark.parametrize("branch", ["shuffled", "broadcast"])
def test_adaptive_join_surfaces_its_inner_join(tmp_path, branch):
    df = _cell_frame("ds_shuffled_join", tmp_path,
                     {"spark.rapids.tpu.diagnostics.enabled": True})
    node = _find_exec(df._planned()[0], "TpuAdaptiveJoinExec")
    if branch == "broadcast":
        # the cell's conf plans the shuffled join; the measured build
        # side is far below this, so the node re-plans at run time
        node.threshold = 10 << 20
    df.collect()
    rows = df.collect()
    assert rows and node.decision.startswith(branch)
    diag = df._last_diag
    assert diag is not None
    by_name = {}
    for st in diag.operator_stats():
        by_name.setdefault(st.name, []).append(st)
    adaptive = by_name["TpuAdaptiveJoinExec"]
    assert len(adaptive) == 1
    inner = by_name["TpuShuffledSymmetricHashJoinExec"]
    # a stable path beside the node's children, not a +N of the run
    assert [st.path for st in inner] == [adaptive[0].path + ".i0"]
    text = df.explain("analyze")
    assert "TpuShuffledSymmetricHashJoinExec" in text
    assert text.count("TpuShuffleExchange ") == 2
    ops = {e["path"]: e for e in diag.events if e["ev"] == "operator"}
    a, i = ops[adaptive[0].path], ops[inner[0].path]
    kids_wall = sum(ops[f"{a['path']}.{k}"]["wall_ns"] for k in (0, 1))
    if branch == "shuffled":
        assert inner[0].batches >= 1 and inner[0].wall_ns > 0
        assert inner[0].counters.get("programs_launched", 0) >= 3
        # the inner join's metrics are the adaptive node's, as the
        # broadcast branch's are
        assert adaptive[0].metrics.get("numOutputRows", 0) \
            == inner[0].metrics.get("numOutputRows", 0) > 0
        # self wall: the inner join counts against the node it runs in,
        # and the exchanges it pulled against the inner join
        assert a["self_wall_ns"] == a["wall_ns"] - i["wall_ns"] > 0
        assert i["self_wall_ns"] == i["wall_ns"] - kids_wall > 0
    else:
        assert "TpuBroadcastHashJoinExec" in by_name
        assert inner[0].batches == 0
        # the inner join never ran: it has no wall and takes none of the
        # children's; they count against the node itself (the broadcast
        # join is a +N op of the run, inside the node's self time)
        assert i["wall_ns"] == 0 == i["self_wall_ns"]
        assert a["self_wall_ns"] == a["wall_ns"] - kids_wall > 0
