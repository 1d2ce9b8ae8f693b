"""Observability spine: spans, Chrome-trace export, metrics/task
system tables, query-log JSONL sink, trace-token propagation.

Reference analogs: QueryStats/OperatorStats, the EventListener SPI
query-log pattern, system.runtime tables, and the
X-Presto-Trace-Token correlation filter — unified here behind
``presto_tpu/obs`` (docs/observability.md)."""

import itertools
import json
import threading
import time
import urllib.request

import pytest

from presto_tpu import obs
from presto_tpu.catalog import Catalog
from presto_tpu.connectors.system import QueryHistory, SystemConnector
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.runner import QueryRunner

from tests.tpch_queries import QUERIES


def make_runner(sf=0.001, split_rows=4096):
    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=sf, split_rows=split_rows))
    history = QueryHistory()
    catalog.register("system", SystemConnector(history))
    runner = QueryRunner(catalog)
    runner.events.add(history)
    return runner, history


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------

def test_span_nesting_and_args():
    tr = obs.Tracer("q_test_nest")
    with obs.tracing(tr):
        with obs.span("outer", cat="engine"):
            with obs.span("inner", cat="engine") as sp:
                sp.set(rows=7)
    names = [s.name for s in tr.spans]
    assert names == ["inner", "outer"]  # completion order: inner first
    inner = tr.spans[0]
    outer = tr.spans[1]
    assert inner.args == {"rows": 7}
    # temporal nesting: inner starts after and ends before outer
    assert inner.t0 >= outer.t0
    assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9


def test_span_disabled_is_noop_singleton():
    """With no active tracer, span() must return the shared no-op —
    no allocation, no clock read (the <2% disabled-overhead budget)."""
    assert obs.current_tracer() is None
    assert obs.span("anything") is obs.NULL_SPAN
    t0 = time.perf_counter()
    for _ in range(100_000):
        with obs.span("x", cat="y"):
            pass
    assert time.perf_counter() - t0 < 2.0  # generous CI bound


def test_span_thread_safety():
    tr = obs.Tracer("q_test_threads")
    N, M = 8, 50
    barrier = threading.Barrier(N)

    def work(k):
        with obs.tracing(tr):
            barrier.wait()
            for i in range(M):
                with obs.span(f"t{k}", cat="engine"):
                    with obs.span(f"t{k}:inner", cat="engine"):
                        pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans) == N * M * 2
    summary = tr.summary()
    for k in range(N):
        assert summary[f"t{k}"]["count"] == M
        assert summary[f"t{k}:inner"]["count"] == M


def test_span_retention_cap_drops_not_grows():
    tr = obs.Tracer("q_test_cap", max_spans=10)
    with obs.tracing(tr):
        for _ in range(25):
            with obs.span("x"):
                pass
    assert len(tr.spans) == 10
    assert tr.dropped == 15
    assert obs.chrome_trace(tr)["otherData"]["dropped_spans"] == 15


def test_tracing_activation_is_thread_local():
    tr = obs.Tracer("q_test_tls")
    seen = {}

    def other():
        seen["tracer"] = obs.current_tracer()

    with obs.tracing(tr):
        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert obs.current_tracer() is tr
    assert seen["tracer"] is None
    assert obs.current_tracer() is None


# ---------------------------------------------------------------------------
# Chrome-trace export (golden shape)
# ---------------------------------------------------------------------------

def test_chrome_trace_export_well_formed():
    runner, history = make_runner()
    runner.session.set("trace", "true")
    runner.execute(QUERIES[6])
    qid = history.completed[-1].query_id
    tracer = obs.lookup(qid)
    assert tracer is not None

    blob = json.dumps(obs.chrome_trace(tracer))  # must be valid JSON
    doc = json.loads(blob)
    events = doc["traceEvents"]
    assert doc["otherData"]["query_id"] == qid
    for ev in events:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0
            assert ev["dur"] >= 0
    names = {ev["name"] for ev in events if ev["ph"] == "X"}
    # lifecycle + operator + device attribution all present
    for want in ("query", "parse", "plan", "execute", "device_get"):
        assert want in names, names
    assert any(n.startswith("op:") for n in names)


def test_trace_covers_wall_time():
    """The acceptance bar: lifecycle spans cover >= 95% of the query
    span's wall time (parse/plan/execute attribution, no dark time)."""
    runner, history = make_runner()
    runner.session.set("trace", "true")
    runner.execute(QUERIES[1])
    tracer = obs.lookup(history.completed[-1].query_id)
    root = [s for s in tracer.spans if s.name == "query"]
    assert len(root) == 1
    covered = sum(s.dur for s in tracer.spans
                  if s.name in ("parse", "plan", "execute"))
    assert covered / root[0].dur >= 0.95


def test_trace_dir_writes_file(tmp_path):
    obs.set_trace_dir(str(tmp_path))
    try:
        runner, history = make_runner()
        runner.execute("select count(*) from nation")  # dir alone enables
        qid = history.completed[-1].query_id
        path = tmp_path / f"{qid}.trace.json"
        assert path.exists()
        doc = json.loads(path.read_text())
        assert any(e["name"] == "query" for e in doc["traceEvents"])
    finally:
        obs.set_trace_dir(None)


def test_compile_spans_attributed():
    """A cold structurally-new query must attribute XLA compile spans
    (the 'how much was compile' headline) and compile_ms must land in
    the completed event and system_runtime_queries."""
    runner, history = make_runner(sf=0.002)
    runner.session.set("trace", "true")
    runner.execute("select l_tax, min(l_quantity + 0.0625) from lineitem"
                   " group by l_tax")
    e = history.completed[-1]
    assert e.compile_ms is not None
    tracer = obs.lookup(e.query_id)
    assert any(s.name == "xla_compile" for s in tracer.spans)
    res = runner.execute(
        "select planning_ms, compile_ms, execution_ms"
        " from system_runtime_queries where query_id = '%s'" % e.query_id)
    p_ms, c_ms, x_ms = res.rows[0]
    assert p_ms is not None and p_ms > 0
    assert c_ms == pytest.approx(e.compile_ms)
    assert x_ms is not None and x_ms > 0


# ---------------------------------------------------------------------------
# system tables
# ---------------------------------------------------------------------------

def test_system_metrics_queryable():
    runner, _ = make_runner()
    runner.execute("select count(*) from nation")
    res = runner.execute("select name, value from system_metrics")
    metrics = {name: value for name, value in res.rows}
    # the documented catalog is pre-registered and the lifecycle
    # counters move (docs/observability.md)
    for want in ("query.started", "query.finished", "query.failed",
                 "query.planning_seconds_total",
                 "query.execution_seconds_total",
                 "xla.programs_compiled", "xla.compile_seconds_total",
                 "xla.registry_hits", "xla.registry_misses",
                 "device.get_calls", "device.get_bytes", "spill.bytes",
                 "exchange.bytes_serialized", "dist.fallbacks",
                 "multihost.fallbacks", "tasks.started"):
        assert want in metrics, want
    assert metrics["query.started"] >= 1
    assert metrics["device.get_calls"] >= 1
    res = runner.execute(
        "select value from system_metrics where name = 'query.finished'")
    assert res.rows[0][0] >= 1


def test_metrics_histogram_flattens():
    h = obs.METRICS.histogram("test.histogram_ms")
    h.observe(0.5)
    h.observe(3.0)
    h.observe(3000.0)
    rows = dict(h.rows())
    assert rows["test.histogram_ms.count"] == 3
    assert rows["test.histogram_ms.bucket_le_1"] == 1
    assert rows["test.histogram_ms.bucket_le_4"] == 1
    assert rows["test.histogram_ms.bucket_le_4096"] == 1


def test_system_runtime_tasks_records_local_queries():
    runner, history = make_runner()
    runner.execute("select count(*) from region")
    qid = history.completed[-1].query_id
    res = runner.execute(
        "select task_id, source, state, elapsed_ms, rows"
        " from system_runtime_tasks where task_id = '%s'" % qid)
    assert len(res.rows) == 1
    tid, source, state, elapsed, rows = res.rows[0]
    assert (tid, source, state) == (qid, "local", "FINISHED")
    assert elapsed is not None and elapsed > 0
    assert rows == 1


# ---------------------------------------------------------------------------
# QueryStats stable keying (EXPLAIN ANALYZE totals survive re-plans)
# ---------------------------------------------------------------------------

def test_querystats_merges_across_identical_plans():
    from presto_tpu.exec.local import QueryStats

    runner, _ = make_runner()
    plan_a = runner.binder.plan("select count(*) from nation")
    plan_b = runner.binder.plan("select count(*) from nation")
    assert plan_a is not plan_b
    stats = QueryStats()
    stats.register_plan(plan_a)
    stats.register_plan(plan_b)
    stats.record(plan_a, 0.1, 5)
    stats.record(plan_b, 0.2, 5)  # the re-built plan's twin root
    ann = stats.annotation(plan_a)
    assert "rows=10" in ann and "pages=2" in ann
    assert stats.annotation(plan_b) == ann


def test_querystats_twins_in_one_plan_stay_distinct():
    from presto_tpu.exec.local import QueryStats

    runner, _ = make_runner()
    plan = runner.binder.plan(
        "select a.n_name, b.n_name from nation a, nation b")

    def scans(node, out):
        from presto_tpu.planner.plan import TableScanNode

        if isinstance(node, TableScanNode):
            out.append(node)
        for s in node.sources:
            scans(s, out)
        return out

    twins = scans(plan, [])
    same_sig = [n for n in twins
                if QueryStats._sig(n) == QueryStats._sig(twins[0])]
    if len(same_sig) < 2:
        pytest.skip("planner differentiated the twin scans")
    stats = QueryStats()
    stats.register_plan(plan)
    stats.record(same_sig[0], 0.1, 3)
    assert "rows=3" in stats.annotation(same_sig[0])
    assert stats.annotation(same_sig[1]) == ""  # not merged


def test_explain_analyze_still_annotates():
    runner, _ = make_runner()
    res = runner.execute("explain analyze select count(*) from orders")
    text = res.rows[0][0]
    assert "rows=" in text and "wall=" in text


# ---------------------------------------------------------------------------
# query-log JSONL sink
# ---------------------------------------------------------------------------

def test_query_log_jsonl_sink(tmp_path):
    log_path = tmp_path / "queries.jsonl"
    runner, _ = make_runner()
    runner.events.add(obs.QueryLogListener(str(log_path)))
    runner.session.set("trace", "true")
    runner.execute("select count(*) from nation")
    runner.execute("select count(*) from region")
    with pytest.raises(Exception):
        runner.execute("select bogus from nation")
    lines = log_path.read_text().strip().splitlines()
    assert len(lines) == 3  # one line per completed query, failures too
    recs = [json.loads(l) for l in lines]
    assert [r["state"] for r in recs] == ["FINISHED", "FINISHED", "FAILED"]
    assert recs[0]["rows"] == 1
    assert recs[0]["planning_ms"] > 0
    assert recs[0]["execution_ms"] > 0
    assert "spans" in recs[0]  # traced queries carry the span rollup
    assert recs[0]["spans"]["query"]["count"] == 1
    assert "error" in recs[2]


# ---------------------------------------------------------------------------
# trace-token propagation: coordinator -> workers, one stitched trace
# ---------------------------------------------------------------------------

def test_trace_token_round_trips_two_worker_query():
    from presto_tpu.parallel.multihost import MultiHostRunner
    from presto_tpu.server.worker import WorkerServer

    def make_catalog():
        catalog = Catalog()
        catalog.register("tpch", Tpch(sf=0.002, split_rows=1024))
        return catalog

    workers = [WorkerServer(make_catalog()) for _ in range(2)]
    for w in workers:
        w.start()
    try:
        catalog = make_catalog()
        local = QueryRunner(catalog)
        multi = MultiHostRunner(catalog, [w.uri for w in workers])
        token = "trace_roundtrip_test"
        tracer = obs.register(obs.Tracer("q_mh_trace", token))
        plan = local.binder.plan(
            "select l_returnflag, count(*), sum(l_quantity) from lineitem"
            " group by l_returnflag")
        with obs.tracing(tracer):
            out = multi.run(plan)
        assert out.dist_fallback is None, out.dist_fallback
        # every worker client stamped the token on its task POSTs
        assert all(w.trace_token == token for w in multi.workers)
        # the worker side saw the token (X-Presto-Trace-Token header)
        worker_tasks = [t for t in obs.TASKS.entries()
                        if t.source == "worker" and t.trace_token == token]
        assert worker_tasks, "no worker task carried the trace token"
        assert all(t.state == "FINISHED" for t in worker_tasks)
        # co-resident workers resolve tracer_for(token) to the SAME
        # tracer, so distributed stage + operator spans stitched into
        # one trace
        assert obs.tracer_for(token) is tracer
        names = {s.name for s in tracer.spans}
        assert "mh_stage:aggregation" in names
        assert any(n.startswith("op:") for n in names), names
        # and more than one thread contributed (worker task threads)
        tids = {s.tid for s in tracer.spans}
        assert len(tids) >= 2, tids
    finally:
        for w in workers:
            try:
                w.stop()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# coordinator REST surface
# ---------------------------------------------------------------------------

def test_coordinator_trace_endpoint_and_stage_stats():
    from presto_tpu.server.coordinator import CoordinatorServer

    runner, _ = make_runner()
    runner.session.set("trace", "true")
    srv = CoordinatorServer(runner)
    srv.start()
    try:
        token = "trace_rest_roundtrip"
        req = urllib.request.Request(
            f"{srv.uri}/v1/statement",
            data=b"select count(*) from nation", method="POST",
            headers={"X-Presto-Trace-Token": token})
        with urllib.request.urlopen(req, timeout=60) as r:
            doc = json.load(r)
        assert doc["stats"]["state"] == "FINISHED"
        # per-stage lifecycle times in the statement-protocol stats
        assert doc["stats"]["planningMs"] > 0
        assert doc["stats"]["executionMs"] > 0
        assert "compileMs" in doc["stats"]
        qid = doc["id"]
        for key in (qid, token):  # by query id AND by trace token
            with urllib.request.urlopen(
                    f"{srv.uri}/v1/query/{key}/trace", timeout=10) as r:
                trace = json.load(r)
            names = {e["name"] for e in trace["traceEvents"]}
            assert "query" in names and "execute" in names
        # unknown id answers 404, not a crash
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"{srv.uri}/v1/query/nope/trace", timeout=10)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# names inside the compiled programs, and host reads
# ---------------------------------------------------------------------------

# q6 -> q14 -> q1 -> q3 at SF0.01: every program each query adds, as
# XLA names its module, with the scopes its lowered text holds.  The
# names enter the persistent compile cache's key: a change here makes
# every deployment's next start a cold one, so it is pinned letter for
# letter.  (chain_leaf and the first chain_leaf_project move columns
# only and hold no operation to scope.)
_FILTER_AGG = {"op:Filter", "op:Aggregation", "agg:reduce"}
_PROBE_AGG = _FILTER_AGG | {"op:Join", "join:lookup"}
PROGRAMS = {
    6: {"jit_chain_leaf_filter_agg_k0a1": _FILTER_AGG,
        "jit_agg_fold": {"op:Aggregation", "agg:reduce"},
        "jit_agg_final": {"op:Aggregation", "agg:reduce"},
        "jit_chain_leaf_project": set()},
    14: {"jit_chain_leaf": set(),
         "jit_join_build": {"op:JoinBuild"},
         "jit_chain_leaf_filter_compact_probe_agg_k0a2":
             _PROBE_AGG | {"filter:compact"},
         "jit_agg_fold": {"op:Aggregation", "agg:reduce"},
         "jit_agg_final": {"op:Aggregation", "agg:reduce"},
         "jit_chain_leaf_project": {"op:Project"}},
    1: {"jit_chain_leaf_filter_agg_k2a8": _FILTER_AGG,
        "jit_agg_packed_final": {"op:Aggregation"},
        "jit_chain_leaf_project": set(),
        "jit_sort": {"op:Sort"}},
    3: {"jit_chain_leaf_filter": {"op:Filter"},
        "jit_join_build": {"op:JoinBuild"},
        "jit_chain_leaf_filter_probe": {"op:Filter", "op:Join",
                                        "join:lookup"},
        "jit_chain_leaf_filter_probe_agg_k3a1_compact_in_probe0":
            _PROBE_AGG | {"agg:sort", "filter:compact"},
        "jit_agg_tower_final": {"op:Aggregation", "agg:sort", "agg:reduce"},
        "jit_topn": {"op:TopN"},
        "jit_chain_leaf_project": set()},
}
# ProgramRegistry.program_count() after each query of that sequence,
# as measured on the commit before the programs had names or scopes
PROGRAM_COUNTS = [4, 10, 14, 22]


def _run_four(monkeypatch, qids=(6, 14, 1, 3)):
    """q6, q14, q1, q3 on a fresh registry: per query its rows, the
    registry's program count after it and {module name: scopes} of the
    programs it added, read from their lowered text."""
    import re

    from presto_tpu.exec import programs

    first = {}
    real_call = programs.Program.__call__

    def call(self, *args, **kwargs):
        first.setdefault(id(self), (self, args, kwargs))
        return real_call(self, *args, **kwargs)

    monkeypatch.setattr(programs.Program, "__call__", call)
    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=0.01))
    registry = programs.ProgramRegistry()
    runner = QueryRunner(catalog, programs=registry)
    out = {}
    for q in qids:
        seen = len(first)
        rows = runner.execute(QUERIES[q]).rows
        added = {}
        for prog, args, kwargs in list(first.values())[seen:]:
            text = prog.fn.lower(*args, **kwargs).as_text(debug_info=True)
            module = re.search(r"module @(\S+)", text).group(1)
            added.setdefault(module, set()).update(
                re.findall(r"\b((?:op|agg|join|filter):[A-Za-z]+)", text))
        out[q] = (rows, registry.program_count(), added)
    return out


@pytest.fixture(scope="module")
def four_queries():
    with pytest.MonkeyPatch.context() as mp:
        return _run_four(mp)


@pytest.mark.parametrize("q", [6, 14, 1, 3])
def test_program_names_and_scopes(four_queries, q):
    assert four_queries[q][2] == PROGRAMS[q]


def test_scopes_change_neither_answers_nor_program_count(
        four_queries, monkeypatch):
    import contextlib

    import jax

    from presto_tpu.exec import local
    from presto_tpu.ops import aggregate, join

    assert [four_queries[q][1] for q in (6, 14, 1, 3)] == PROGRAM_COUNTS
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(local, "_named", lambda f, name: f)
    for module, names in (
            (aggregate, ("_sorted_runs", "_presorted_group_ids",
                         "_partial_states", "_merge_states")),
            (join, ("_lookup_first", "_lookup_range"))):
        for name in names:
            monkeypatch.setattr(module, name,
                                getattr(module, name).__wrapped__)
    bare = _run_four(monkeypatch)
    for q in (6, 14, 1, 3):
        rows, count, added = bare[q]
        assert not any(added.values()), added  # the scopes are gone
        # named after the closure
        assert ("jit_compact_stage" if q in (14, 3)
                else "jit_agg_stage") in added
        assert (rows, count) == four_queries[q][:2]


def test_q13_build_and_probe_carry_the_join_scopes(monkeypatch):
    """The legs no primary-key build reaches (PR 27): the sorted index
    with its CSR ``starts`` table is ``join:index`` inside the build,
    the expansion ``join:expand`` beside the range lookup inside the
    probe.  Under the leg a TPU takes."""
    from presto_tpu.ops import join

    join.set_direct_join_override(True)
    try:
        (rows, _, added), = _run_four(monkeypatch, qids=(13,)).values()
    finally:
        join.set_direct_join_override(None)
    assert rows and rows[0][1] > 0
    assert added["jit_join_build"] == {"op:JoinBuild", "join:index"}
    assert added["jit_join_probe"] == {"op:Join", "join:lookup",
                                       "join:expand"}
    # no program of q6, q14, q1, q3 holds either (PROGRAMS above): their
    # builds leave by the unique-direct leg, their probes do not expand
    assert not any({"join:index", "join:expand"} & scopes
                   for programs in PROGRAMS.values()
                   for scopes in programs.values())


@pytest.fixture(scope="module")
def statement_server():
    """A coordinator over SF0.01 resident in a ``MemoryConnector`` (the
    benchmark's connector: its bind-time row counts read the device),
    with a program registry of its own."""
    from presto_tpu.client import StatementClient
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.exec.programs import ProgramRegistry
    from presto_tpu.server.coordinator import CoordinatorServer

    tpch = Tpch(sf=0.01, split_rows=8192)
    mem = MemoryConnector()
    for table in ("lineitem", "orders", "customer", "part"):
        mem.load_from(tpch, table)
    catalog = Catalog()
    catalog.register("tpch", mem)
    srv = CoordinatorServer(QueryRunner(catalog, programs=ProgramRegistry()))
    srv.start()
    try:
        yield StatementClient(srv.uri)
    finally:
        srv.stop()


_FRESH = itertools.count(1)


def _statement(client, sql: str, trace: str):
    """The final page's stats and the statement's tracer (None when it
    does not trace) for ``sql`` under a text the server has not seen
    (so it binds and plans); waits for ``statement:respond``, which the
    handler thread appends once the page is written."""
    client.execute(f"SET SESSION trace = {trace}")
    pages = []
    client.execute(sql + " " * next(_FRESH), on_progress=pages.append)
    tracer = obs.lookup(client.last_query_id)
    deadline = time.time() + 5.0
    while tracer is not None and time.time() < deadline and not any(
            s.name == "statement:respond" for s in list(tracer.spans)):
        time.sleep(0.01)
    return pages[-1], tracer


def _inside(inner, outer) -> bool:
    return (inner.tid == outer.tid and outer.t0 <= inner.t0
            and inner.t0 + inner.dur <= outer.t0 + outer.dur)


@pytest.mark.parametrize("q", [6, 14, 1, 3])
def test_reads_and_dispatches_in_stats_and_spans(statement_server, q):
    """``stats.planReads`` + ``stats.hostReads`` equals the
    ``host_read:*`` spans and ``stats.dispatches`` the ``dispatch:*``
    spans, and all three are the same with tracing on and off (PR 24's
    invariant had ``hostReads`` alone)."""
    counts = {}
    for trace in ("false", "true"):
        stats, tracer = _statement(statement_server, QUERIES[q], trace)
        counts[trace] = (stats["planReads"], stats["hostReads"],
                         stats["dispatches"])
        if trace == "false":
            assert tracer is None
            continue
        names = [s.name for s in tracer.spans]
        reads = [n for n in names if n.startswith("host_read:")]
        assert len(reads) == stats["planReads"] + stats["hostReads"]
        # the bind-time row counts: one read a page of each table
        assert reads.count("host_read:row_count") == stats["planReads"] >= 1
        assert reads[-1] == "host_read:result"
        if q in (14, 3):  # one uniqueness check per primary-key build
            assert "host_read:unique_ok" in reads
        # q14's chain compacts in front of its probe and q3's inside
        # it: one read says whether every page fitted, and the stats
        # count the pages
        assert reads.count("host_read:compact_taken") == (q in (14, 3))
        assert (stats["compactedPages"] > 0) == (q in (14, 3))
        assert stats["compactFallbackPages"] == 0
        dispatched = [n for n in names if n.startswith("dispatch:")]
        assert len(dispatched) == stats["dispatches"] >= 2
        assert "dispatch:chain" in dispatched
    assert counts["false"] == counts["true"]


@pytest.mark.parametrize("q", [6, 14, 1, 3])
def test_new_spans_are_present_and_nested(statement_server, q):
    """``plan:*`` inside ``plan``; ``dispatch:*`` and ``chain:lower``
    inside ``execute``; ``parse``, ``plan`` and ``execute`` inside the
    root ``query``; ``statement:*`` under the statement's own tracer
    (admission and the finish on the query's thread, the response on
    the handler's)."""
    _, tracer = _statement(statement_server, QUERIES[q], "true")
    spans = list(tracer.spans)
    one = {}
    for s in spans:
        one.setdefault(s.name, []).append(s)
    for name in ("query", "parse", "plan", "execute", "plan:bind",
                 "plan:optimize", "plan:estimates", "statement:admit",
                 "statement:finish"):
        assert len(one[name]) == 1, name
    # one a page the client fetches: the POST's, then each poll's
    assert len(one["statement:respond"]) >= 1
    [root], [plan], [execute] = one["query"], one["plan"], one["execute"]
    assert all(_inside(one[n][0], root) for n in ("parse", "plan", "execute"))
    for s in spans:
        if s.name.startswith("plan:"):
            assert _inside(s, plan), s.name
        if s.name.startswith("dispatch:") or s.name == "chain:lower":
            assert _inside(s, execute), s.name
    # plan:check: the binder's, the runner's after the plan and its
    # access check
    assert 2 <= len(one["plan:check"]) <= 3
    assert len(one["chain:lower"]) >= 2
    admit, finish = one["statement:admit"][0], one["statement:finish"][0]
    assert admit.tid == root.tid and admit.t0 + admit.dur <= root.t0
    assert finish.tid == root.tid and finish.t0 >= execute.t0 + execute.dur
    respond = one["statement:respond"][0]
    assert respond.tid != root.tid and respond.t0 >= admit.t0


def test_no_span_is_made_without_a_tracer(statement_server, monkeypatch):
    """Tracing off, a statement opens no live span anywhere (the
    coordinator's, the runner's, the executor's): every ``span`` call
    is the shared no-op."""
    from presto_tpu.obs import trace

    made = []
    real = trace._LiveSpan.__init__

    def spy(self, *args, **kwargs):
        made.append(args[1])
        real(self, *args, **kwargs)

    statement_server.execute("SET SESSION trace = false")
    monkeypatch.setattr(trace._LiveSpan, "__init__", spy)
    for q in (6, 3):
        statement_server.execute(QUERIES[q] + "  ")
    assert made == []
    assert obs.lookup(statement_server.last_query_id) is None


def test_program_names_and_count_unchanged_by_the_spans():
    """The spans and counters around program calls change no program:
    after q6, q14, q1, q3 the registry holds the programs, under the
    names, that it held before them (PR 38's tree, sandbox)."""
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.exec.programs import ProgramRegistry

    tpch = Tpch(sf=0.01)
    mem = MemoryConnector()
    for table in ("lineitem", "orders", "customer", "part"):
        mem.load_from(tpch, table)
    catalog = Catalog()
    catalog.register("tpch", mem)
    registry = ProgramRegistry()
    runner = QueryRunner(catalog, programs=registry)
    runner.session.set("trace", "true")
    for q in (6, 14, 1, 3):
        runner.execute(QUERIES[q])
    assert registry.program_count() == 22
    assert registry.callable_count() == 24
    names = sorted(p.fn.__name__ for p in registry._programs.values())
    assert names == sorted(
        ["agg_final"] * 2 + ["agg_fold"] * 2 + [
            "agg_packed_final", "agg_packed_fold", "agg_tower_final",
            "agg_tower_fold", "chain_leaf", "chain_leaf_filter",
            "chain_leaf_filter_agg_k0a1", "chain_leaf_filter_agg_k2a8",
            "chain_leaf_filter_compact_probe_agg_k0a2",
            "chain_leaf_filter_probe",
            "chain_leaf_filter_probe_agg_k3a1_compact_in_probe0",
            "sort", "topn"] + ["chain_leaf_project"] * 4
        + ["join_build"] * 3)


def test_host_read_opens_no_span_without_tracer(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.obs import reads

    opened = []
    real_span = reads.span

    def spy(*args, **kwargs):
        opened.append(real_span(*args, **kwargs))
        return opened[-1]

    monkeypatch.setattr(reads, "span", spy)
    calls = obs.METRICS.counter("device.get_calls").value
    nbytes = obs.METRICS.counter("device.get_bytes").value
    before = reads.host_reads()
    assert obs.current_tracer() is None
    out = reads.host_read(jnp.arange(3, dtype=jnp.int32), "unit")
    assert isinstance(out, np.ndarray) and out.tolist() == [0, 1, 2]
    assert opened == [obs.NULL_SPAN]
    tr = obs.Tracer("q_host_read")
    with obs.tracing(tr):
        reads.host_read((jnp.zeros(2, jnp.int32), jnp.ones(1, jnp.bool_)),
                        "unit")
    assert [(s.name, s.cat) for s in tr.spans] == [
        ("host_read:unit", "device")]
    assert reads.host_reads() == before + 2
    assert obs.METRICS.counter("device.get_calls").value == calls + 2
    assert obs.METRICS.counter("device.get_bytes").value == nbytes + 12 + 9


def test_dispatch_counts_and_spans_a_helper_call():
    """``exec/programs.dispatched``: one count on the calling thread
    and one ``dispatch:<kind>`` span where a tracer is active; the
    helper's program keeps its name."""
    import jax.numpy as jnp

    from presto_tpu.exec import local, programs

    mask = jnp.array([True, False, True, False])
    before = programs.dispatches()
    assert local._extent_live(mask).tolist() == [3, 2]
    tr = obs.Tracer("q_dispatch")
    with obs.tracing(tr):
        local._extent_live(mask)
    assert programs.dispatches() == before + 2
    assert [(s.name, s.cat) for s in tr.spans] == [
        ("dispatch:extent_live", "dispatch")]


def test_spans_are_profiler_annotations_during_a_session(tmp_path):
    """While ``jax.profiler`` runs, a traced query's live spans are
    ``presto:<name>`` events of the profile's host plane; the tracer's
    own spans are the same with or without a session."""
    import glob

    import jax
    from jax.profiler import ProfileData

    runner, _ = make_runner()
    runner.session.set("trace", "true")
    before = runner.execute(QUERIES[6])  # compiles outside the session
    plain = {s.name for s in obs.lookup(before.query_id).spans}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        res = runner.execute(QUERIES[6] + " ")
    finally:
        jax.profiler.stop_trace()
    assert res.rows == before.rows
    spans = {s.name for s in obs.lookup(res.query_id).spans}
    assert spans - {"xla_compile"} == plain - {"xla_compile"}
    [path] = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = {e.name for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("presto:")}
    # the one retroactive span, xla_compile, is not an event; the root
    # and parse are live since PR 39, like the plan's, the executor's
    # and the statement's edges
    assert events == {"presto:" + n for n in spans - {"xla_compile"}}
    assert {"presto:query", "presto:parse", "presto:plan",
            "presto:plan:bind", "presto:plan:optimize",
            "presto:op:Aggregation", "presto:dispatch:chain",
            "presto:chain:lower", "presto:statement:finish",
            "presto:host_read:result"} <= events
