"""PR 39: the host's spans on the device's clock
(``benchmark/host_spans.py``) and the six metrics that read them and
the statement counters beside them: on runs made by hand, on PR 24's
recorded trace (which has no ``presto:`` event), on a pass of
``tpch_sf1.join_agg`` recorded on one ``TPU v5 lite`` with this PR's
spans (benchmark/testdata/join_agg_pass_v5e_spans.xplane.pb.gz), and in
a CPU rehearsal of ``tpch_sf10.scan_agg``."""

import gzip
import os

import pytest

import bench_rehearsal as rehearsal
import manifest_shape as shape
from benchmark import host_spans, loadgen, run as bench_run, specs, xplane

TESTDATA = os.path.join(rehearsal.BENCHMARK, "testdata")
PR24_TRACE = os.path.join(TESTDATA, "join_agg_pass_v5e.xplane.pb.gz")
SPANS_TRACE = os.path.join(TESTDATA, "join_agg_pass_v5e_spans.xplane.pb.gz")
READERS = {r.NAME: r for r in bench_run.layer_metric_readers()}
#: metric -> (unit, better, source, layer, moves), ISSUE 39's table
SIX = {
    "plan_reads_per_pass": ("count", "lower", "program_counter",
                            "Parse / bind / plan", "pass_p50_ms"),
    "plan_read_ms": ("ms", "lower", "program_span", "Parse / bind / plan",
                     "pass_p50_ms"),
    "plan_optimize_ms": ("ms", "lower", "program_span",
                         "Parse / bind / plan", "pass_p50_ms"),
    "dispatches_per_pass": ("count", "lower", "program_counter", "Executor",
                            "pass_p50_ms"),
    "dispatch_ms": ("ms", "lower", "program_span", "Executor",
                    "pass_p50_ms"),
    "idle_named_pct": ("%", "higher", "device_trace", "Device",
                       "rows_per_s"),
}
SPAN_READERS = ("plan_read_ms", "plan_optimize_ms", "dispatch_ms")


def _run(cell="tpch_sf1.join_agg", stats=None, busy=((0.0, 0.2),),
         passes=((0.0, 1.0),)):
    """A ``run.Run`` of ``cell`` with one statement a traced pass, each
    pass's interval marked on the trace's clock and chip 0 busy in
    ``busy``."""
    loaded = specs.load_cell(cell)
    traced, marks = [], {}
    for i, (lo, hi) in enumerate(passes):
        q = loadgen.QueryRecord("q03", i, 0.0, 0.0, True,
                                stats=dict(stats or {}))
        marks[f"{xplane.MARK}q03:{i}"] = (lo, hi)
        traced.append(loadgen.Pass(0, i, 0.0, [q]))
    ops = [xplane.Op(s, e, "fusion", "jit_f") for s, e in busy]
    trace = xplane.Trace({0: ops}, {}, marks, False)
    return bench_run.Run(loaded, {}, None, {}, {}, traced, {}, trace=trace,
                         traced=traced)


def _events(monkeypatch, *spans):
    events = [host_spans.Event(n, s, e) for n, s, e in spans]
    monkeypatch.setattr(host_spans, "for_run", lambda run: events)
    return events


@pytest.mark.parametrize("name", [
    "parse", "plan:bind", "plan:check", "plan:optimize", "plan:estimates",
    "host_read:row_count", "host_read:extent", "dispatch:chain",
    "dispatch:extent_live", "chain:lower", "statement:admit",
    "statement:finish", "statement:respond", "device_get"])
def test_leaves_are_named(name):
    assert host_spans.is_leaf(name)


@pytest.mark.parametrize("name", [
    None, "query", "plan", "execute", "op:Aggregation", "op:Filter",
    "chain:other", "parser", "chain_split:split"])
def test_containers_and_nothing_are_not(name):
    assert not host_spans.is_leaf(name)


def test_idle_booked_to_the_innermost_span_at_the_gap_middle(monkeypatch):
    # busy [0, 0.2]; idle [0.2, 0.5] under plan:bind (inside plan), and
    # [0.6, 1.0] under op:Aggregation with a read in its first half:
    # that gap's middle, 0.8, is under the page pull alone
    run = _run(busy=((0.0, 0.2), (0.5, 0.6)))
    _events(monkeypatch, ("query", 0.0, 1.0), ("plan", 0.15, 0.55),
            ("plan:bind", 0.19, 0.52), ("op:Aggregation", 0.6, 1.0),
            ("host_read:extent", 0.61, 0.7))
    booked = host_spans.idle_booking(run)
    assert booked == pytest.approx({"plan:bind": 0.3, "op:Aggregation": 0.4})
    assert READERS["idle_named_pct"].read(run) == pytest.approx(100 * 3 / 7)
    # a gap under no span is nobody's
    _events(monkeypatch, ("plan:bind", 0.19, 0.52))
    assert host_spans.idle_booking(run) == pytest.approx(
        {"plan:bind": 0.3, None: 0.4})


def test_idle_named_outside_statements_and_without_idle(monkeypatch):
    # two passes; only the time inside the marked statements counts
    run = _run(busy=((0.0, 0.5), (2.0, 2.5)),
               passes=((0.0, 1.0), (2.0, 3.0)))
    _events(monkeypatch, ("device_get", 0.5, 1.0), ("op:Join", 1.0, 2.0),
            ("execute", 2.5, 3.0))
    assert READERS["idle_named_pct"].read(run) == pytest.approx(50.0)
    busy_all = _run(busy=((0.0, 1.0),))
    _events(monkeypatch, ("plan", 0.0, 1.0))
    assert READERS["idle_named_pct"].read(busy_all) is None


def test_span_readers_per_pass(monkeypatch):
    run = _run(passes=((0.0, 1.0), (2.0, 3.0)))
    _events(monkeypatch,
            ("plan", 0.0, 0.1), ("plan:bind", 0.0, 0.05),
            ("host_read:row_count", 0.01, 0.02),
            ("host_read:row_count", 0.02, 0.04),
            ("plan:optimize", 0.05, 0.06), ("execute", 0.1, 0.9),
            ("dispatch:chain", 0.2, 0.21), ("dispatch:agg_fold", 0.3, 0.31),
            ("host_read:extent", 0.4, 0.45),
            ("plan", 2.0, 2.1), ("plan:bind", 2.0, 2.05),
            ("host_read:row_count", 2.01, 2.03),
            ("plan:optimize", 2.05, 2.07), ("dispatch:chain", 2.2, 2.24))
    # reads inside plan: 30 ms and 20; the execute's extent is not one
    assert READERS["plan_read_ms"].read(run) == pytest.approx(25.0)
    assert READERS["plan_optimize_ms"].read(run) == pytest.approx(15.0)
    assert READERS["dispatch_ms"].read(run) == pytest.approx(30.0)


def test_a_plan_without_reads_reads_zero(monkeypatch):
    run = _run()
    _events(monkeypatch, ("plan", 0.0, 0.1), ("plan:bind", 0.0, 0.05),
            ("host_read:extent", 0.2, 0.3))
    assert READERS["plan_read_ms"].read(run) == 0.0


def test_a_program_from_before_the_spans(monkeypatch):
    """PR 24's spans and counters only: the five new readers say
    nothing; ``idle_named_pct`` reports from the reads."""
    run = _run(stats={"hostReads": 3, "planningMs": 1.0},
               busy=((0.0, 0.2), (0.5, 0.6)))
    _events(monkeypatch, ("plan", 0.1, 0.55), ("op:Aggregation", 0.6, 1.0),
            ("host_read:extent", 0.7, 0.9))
    for name in SPAN_READERS + ("plan_reads_per_pass", "dispatches_per_pass"):
        assert READERS[name].read(run) is None, name
    assert READERS["idle_named_pct"].read(run) == pytest.approx(100 * 4 / 7)


def test_counter_readers_sum_a_pass():
    run = _run(stats={"planReads": 8, "dispatches": 21})
    run.passes = run.traced + [loadgen.Pass(0, 9, 0.0, [
        loadgen.QueryRecord("q06", 9, 0.0, 0.0, True,
                            stats={"planReads": 8, "dispatches": 3}),
        loadgen.QueryRecord("q01", 10, 0.0, 0.0, True,
                            stats={"planReads": 8, "dispatches": 17})])]
    assert READERS["plan_reads_per_pass"].read(run) == 12  # median of 8, 16
    assert READERS["dispatches_per_pass"].read(run) == 20.5


@pytest.mark.parametrize("name", sorted(SIX))
def test_manifest_entry_is_the_issues(name):
    """Appended at the end of ``per_layer``, general (no list of cells
    in reader or manifest), as ISSUE 39's table has them."""
    manifest = shape.load(rehearsal.REPO)
    shape.is_general(manifest, specs.ROOT, name)
    shape.metric_entry_is_sound(manifest, specs.ROOT, name)
    entry = shape.entry_of(manifest, name)
    unit, better, source, layer, moves = SIX[name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == SIX[name]
    assert READERS[name].UNIT == unit
    assert "very cell" in READERS[name].__doc__


def _recorded(trace_gz, tmp_path, monkeypatch, stats=None):
    """A ``run.Run`` of ``tpch_sf1.join_agg`` whose one traced pass is
    the recorded trace's statements, the trace where ``run.py`` writes
    it (``trace_out/<cell>``)."""
    cell = "tpch_sf1.join_agg"
    directory = tmp_path / cell / "plugins" / "profile" / "recorded"
    directory.mkdir(parents=True)
    with gzip.open(trace_gz, "rb") as f:
        (directory / "v5e.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(host_spans, "TRACE_OUT", str(tmp_path))
    host_spans._read_cached.cache_clear()
    trace = xplane.read(trace_gz)
    queries = [
        loadgen.QueryRecord(mark.split(":")[1], int(mark.split(":")[2]),
                            0.0, 0.0, True, stats=dict(stats or {}))
        for mark in sorted(trace.marks, key=lambda m: trace.marks[m])]
    one = loadgen.Pass(0, 1, 0.0, queries)
    return bench_run.Run(specs.load_cell(cell), {}, None, {}, {}, [one], {},
                         trace, [one])


@pytest.mark.parametrize("name", SPAN_READERS + ("idle_named_pct",))
def test_pr24_recorded_trace_has_no_host_span(name, tmp_path, monkeypatch):
    """PR 24's trace kept the device plane and the marks, no
    ``presto:`` event: every host-span reader says nothing."""
    run = _recorded(PR24_TRACE, tmp_path, monkeypatch)
    assert run.pass_intervals and host_spans.for_run(run) == []
    assert READERS[name].read(run) is None


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One traced run of ``tpch_sf10.scan_agg`` at SF0.01 on XLA:CPU:
    ``lineitem``'s 60,175 rows in 8 splits of 8,192."""
    checkout = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench_spans")))
    proc = rehearsal.run_cell(checkout, "tpch_sf10.scan_agg", trace=1,
                              seconds=3.0)
    return rehearsal.last_line(proc)


def test_cpu_rehearsal_reports_the_six(rehearsed):
    assert rehearsed["correct"] is True and rehearsed["failed"] == 0
    metrics = rehearsed["metrics"]
    assert set(SIX) <= set(metrics)
    for name, (unit, *_) in SIX.items():
        assert metrics[name]["unit"] == unit
    # q6 and q1 each resolve lineitem once at bind time: a read a page
    assert metrics["plan_reads_per_pass"]["value"] == 16
    # per statement at least its chain, its fold and its final
    assert metrics["dispatches_per_pass"]["value"] >= 6
    assert metrics["plan_read_ms"]["value"] > 0
    assert 0 < metrics["plan_optimize_ms"]["value"] < metrics[
        "planning_ms"]["value"]
    assert metrics["dispatch_ms"]["value"] > 0
    assert 0 < metrics["idle_named_pct"]["value"] <= 100
