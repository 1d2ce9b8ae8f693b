"""The per-operator reduction (benchmark/scopes.py and its readers under
benchmark/layer_metrics/) against one pass of ``tpch_sf1.join_agg``
recorded on one ``TPU v5 lite`` by PR 24 (q3 then q14; the first device
plane's operations with the stats ``tf_op`` and ``program_id`` kept,
names cut to their heads; benchmark/testdata/), against the older
recorded trace that kept no stat, and in the CPU rehearsal."""

import gzip
import json
import os

import pytest

import bench_rehearsal as rehearsal
from benchmark import loadgen, run as bench_run, scopes, specs, xplane

TESTDATA = os.path.join(rehearsal.BENCHMARK, "testdata")
PASS_TRACE = os.path.join(TESTDATA, "join_agg_pass_v5e.xplane.pb.gz")
BARE_TRACE = os.path.join(TESTDATA, "tiny_q6_v5e.xplane.pb.gz")
READERS = {r.NAME: r for r in bench_run.layer_metric_readers()}
#: the readers of the device's time, with what they read on the pass
DEVICE = {
    "op_filter_project_ms": 0.0045624,
    "op_join_probe_ms": 1108.36926,
    "op_join_build_ms": 66.234507,
    "op_agg_ms": 1156.150463,
    "agg_sort_ms": 321.009785,
    "device_unscoped_pct": 1.97502973,
}
HOST = ("host_reads_per_pass", "host_read_wait_ms")


def run_over(trace_gz, cell, tmp_path, monkeypatch, stats=None, spans=()):
    """A ``run.Run`` whose one traced pass is the recorded trace's
    statements in order, the trace itself where ``run.py`` would have
    written it (``trace_out/<cell>``)."""
    directory = tmp_path / cell / "plugins" / "profile" / "recorded"
    directory.mkdir(parents=True)
    with gzip.open(trace_gz, "rb") as f:
        (directory / "v5e.xplane.pb").write_bytes(f.read())
    monkeypatch.setattr(scopes, "TRACE_OUT", str(tmp_path))
    trace = xplane.read(trace_gz)
    queries = [
        loadgen.QueryRecord(mark.split(":")[1], int(mark.split(":")[2]),
                            0.0, 0.0, True, stats=dict(stats or {}),
                            spans=list(spans))
        for mark in sorted(trace.marks, key=lambda m: trace.marks[m])]
    one = loadgen.Pass(0, 1, 0.0, queries)
    return bench_run.Run(specs.load_cell(cell), {}, None, {}, {}, [one], {},
                         trace, [one])


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    scopes._read_cached.cache_clear()
    return run_over(PASS_TRACE, "tpch_sf1.join_agg", tmp_path, monkeypatch)


def test_recorded_pass_names_programs_and_scopes():
    ops = scopes.read(PASS_TRACE)
    chip0 = xplane.read(PASS_TRACE).chips[0]
    assert len(ops) == len(chip0) == 8988
    # the same operations, on the same clock, in the same programs
    assert all(abs(a.start - b.start) < 1e-8 and a.module == b.module
               for a, b in zip(ops, chip0))
    assert {o.module for o in ops if o.scopes} == {
        "jit_chain_leaf_filter", "jit_chain_leaf_filter_probe",
        "jit_chain_leaf_filter_probe_agg_k0a2",
        "jit_chain_leaf_filter_probe_agg_k3a1", "jit_chain_leaf_project",
        "jit_join_build", "jit_agg_fold", "jit_agg_final",
        "jit_agg_tower_fold", "jit_agg_tower_final", "jit_topn"}
    assert {o.scopes for o in ops} == {
        (), ("op:Filter",), ("op:Project",), ("op:Join",),
        ("op:Join", "join:lookup"), ("op:JoinBuild",), ("op:Aggregation",),
        ("op:Aggregation", "agg:sort"), ("op:Aggregation", "agg:reduce"),
        ("op:TopN",)}
    assert not any(o.module.startswith("jit_agg_stage") for o in ops)


@pytest.mark.parametrize("name", sorted(DEVICE))
def test_device_reader_on_the_recorded_pass(recorded, name):
    assert READERS[name].read(recorded) == pytest.approx(DEVICE[name],
                                                         rel=1e-5)


def test_operator_scopes_and_the_unscoped_part_add_up_to_busy(recorded):
    ops = scopes.for_run(recorded)
    (lo, hi), = recorded.pass_intervals
    operators = {o.scopes[0] for o in ops if o.scopes}
    assert operators == {"op:Filter", "op:Project", "op:Join", "op:JoinBuild",
                         "op:Aggregation", "op:TopN"}
    booked = sum(scopes.busy_s(scopes.under(ops, op), lo, hi)
                 for op in operators)
    bare = scopes.busy_s([o for o in ops if not o.scopes], lo, hi)
    busy_ms = READERS["device_busy_ms"].read(recorded)
    assert busy_ms == pytest.approx(2381.781519, rel=1e-6)
    assert (booked + bare) * 1e3 == pytest.approx(busy_ms, rel=0.02)
    # and through the readers: the four operator metrics, TopN, the rest
    named = sum(READERS[n].read(recorded) for n in (
        "op_filter_project_ms", "op_join_probe_ms", "op_join_build_ms",
        "op_agg_ms"))
    topn = scopes.ms_per_pass(recorded, "op:TopN")
    unscoped = READERS["device_unscoped_pct"].read(recorded) / 100 * busy_ms
    assert named + topn + unscoped == pytest.approx(busy_ms, rel=0.02)


@pytest.mark.parametrize("name", sorted(DEVICE))
def test_device_reader_says_nothing_of_a_trace_without_stats(
        tmp_path, monkeypatch, name):
    """The trace recorded before PR 24 was trimmed of every stat, so
    it cannot say which scope an operation ran under."""
    scopes._read_cached.cache_clear()
    assert scopes.read(BARE_TRACE) is None
    bare = run_over(BARE_TRACE, "tpch_sf10.scan_agg", tmp_path, monkeypatch)
    assert len(bare.pass_intervals) == 1
    assert READERS[name].read(bare) is None
    assert READERS["device_busy_ms"].read(bare) > 0


def test_no_trace_no_number(tmp_path, monkeypatch):
    run = run_over(PASS_TRACE, "tpch_sf1.join_agg", tmp_path, monkeypatch)
    monkeypatch.setattr(scopes, "TRACE_OUT", str(tmp_path / "elsewhere"))
    scopes._read_cached.cache_clear()
    assert all(READERS[n].read(run) is None for n in DEVICE)
    run.trace = None
    assert all(READERS[n].read(run) is None for n in DEVICE)


@pytest.mark.parametrize("op_name,path", [
    ("jit(chain_leaf_filter_probe_agg_k3a1)/op:Join/join:lookup/gather:",
     ("op:Join", "join:lookup")),
    ("jit(agg_tower_fold)/op:Aggregation/agg:sort/sort:",
     ("op:Aggregation", "agg:sort")),
    ("jit(f)/op:Aggregation/agg:reduce/jit(_where)/select_n:",
     ("op:Aggregation", "agg:reduce")),
    ("jit(f)/op:Filter/and:", ("op:Filter",)),
    ("jit(_reduce_sum)/reduce_sum:", ()),
    ("jit(f)/agg:sort/sort:", ()),  # no operator above it: not booked
    ("", ()),
])
def test_scope_path(op_name, path):
    assert scopes.scope_path(op_name) == path


def test_wire_format_fields():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64 7,
    # field 4 fixed32 9, field 1 varint 2**64 - 1 (int64 -1)
    message = (b"\x08\xac\x02" b"\x12\x02ab" b"\x19" + (7).to_bytes(8, "little")
               + b"\x25" + (9).to_bytes(4, "little")
               + b"\x08" + b"\xff" * 9 + b"\x01")
    got = [(n, bytes(v) if isinstance(v, memoryview) else v)
           for n, v in scopes.fields(memoryview(message))]
    assert got == [(1, 300), (2, b"ab"), (3, 7), (4, 9), (1, 2 ** 64 - 1)]
    assert scopes._signed(2 ** 64 - 1) == -1 and scopes._signed(5) == 5
    with pytest.raises(ValueError):
        list(scopes.fields(memoryview(b"\x0b")))  # a group: not read


def test_host_readers_on_a_pass(tmp_path, monkeypatch):
    spans = [("op:Aggregation", 0.0, 0.5), ("host_read:extent", 0.1, 0.11),
             ("host_read:fold_count", 0.2, 0.23), ("device_get", 0.6, 0.7),
             ("host_read:result", 0.6, 0.65)]
    run = run_over(PASS_TRACE, "tpch_sf1.join_agg", tmp_path, monkeypatch,
                   stats={"hostReads": 3}, spans=spans)
    assert READERS["host_reads_per_pass"].read(run) == 6  # two statements
    # 10 ms + 30 ms a statement; the result's 50 ms is device_get's
    assert READERS["host_read_wait_ms"].read(run) == pytest.approx(80.0)
    # a pass that only read its results waited for nothing in mid-query
    for q in run.traced[0].queries:
        q.spans = spans[-2:]
    assert READERS["host_read_wait_ms"].read(run) == 0.0
    # a program from before PR 24 has neither the counter nor the spans
    for q in run.traced[0].queries:
        q.stats, q.spans = {"executionMs": 1.0}, spans[:1] + spans[3:4]
    assert READERS["host_reads_per_pass"].read(run) is None
    assert READERS["host_read_wait_ms"].read(run) is None


def test_manifest_lists_the_new_metrics_where_their_readers_report():
    with open(os.path.join(rehearsal.REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in list(DEVICE) + list(HOST):
        entry = listed[name]
        assert entry.get("workloads") == getattr(READERS[name], "WORKLOADS",
                                                 None)
        assert entry["moves"] == "pass_p50_ms" and entry["better"] == "lower"
        assert entry["layer"] == ("Executor" if name in HOST else "Kernels")


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One traced run of ``tpch_sf1.join_agg`` at SF0.01 on XLA:CPU."""
    checkout = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench_scopes")))
    proc = rehearsal.run_cell(checkout, "tpch_sf1.join_agg", trace=1,
                              seconds=3.0)
    return rehearsal.last_line(proc)


@pytest.mark.parametrize("name", sorted(DEVICE))
def test_cpu_rehearsal_reports_no_device_scope_metric(rehearsed, name):
    """XLA:CPU's trace has no device plane and its events no
    ``tf_op``: the reader returns None, the line leaves the metric
    out, nothing raises."""
    assert rehearsed["correct"] is True and rehearsed["failed"] == 0
    assert name not in rehearsed["metrics"]
    assert "device_busy_ms" in rehearsed["metrics"]


def test_cpu_rehearsal_reports_host_reads_and_names_programs(rehearsed):
    metrics = rehearsed["metrics"]
    # q14: unique_ok, result; q3: two unique_ok, extents, result
    assert metrics["host_reads_per_pass"]["value"] >= 6
    assert metrics["host_reads_per_pass"]["unit"] == "count"
    assert metrics["host_read_wait_ms"]["value"] > 0
    programs = {name.split("/")[0]
                for name, _ in rehearsed["breakdown"]["device_ops"]}
    assert programs and not any(p.startswith("jit_agg_stage")
                                for p in programs)
    # q3's chain, by its leaf and its aggregation and not by what stands
    # between them: a later PR may name the stages of its probe
    assert any(p.startswith("jit_chain_leaf_filter") and "agg_k3a1" in p
               for p in programs)
