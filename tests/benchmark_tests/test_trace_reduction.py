"""The reduction from a profiler trace to numbers (benchmark/xplane.py)
against a small trace recorded on one ``TPU v5 lite`` (q6 over the
SF0.01 rehearsal tables, four statements; benchmark/testdata/), the
bytes-needed function against values computed by hand, and
``hbm_roofline_pct`` over the recorded one-chip pass and the recorded
four-chip statement."""

import os

import pytest

import bench_rehearsal as rehearsal
from benchmark import bytes_needed, loadgen, run as bench_run, specs, tables
from benchmark import xplane

TESTDATA = os.path.join(rehearsal.BENCHMARK, "testdata")
TRACE = os.path.join(TESTDATA, "tiny_q6_v5e.xplane.pb.gz")
MESH_TRACE = os.path.join(TESTDATA, "mesh_q14_v5e_4chips.xplane.pb.gz")
PASS_TRACE = os.path.join(TESTDATA, "join_agg_pass_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace():
    return xplane.read(TRACE)


def test_recorded_trace_has_one_chip_and_its_marks(trace):
    assert sorted(trace.chips) == [0] and not trace.stands_in
    assert len(trace.chips[0]) == 4464  # 3 statements x 1488 operations
    assert len(trace.async_ops[0]) == 111
    assert sorted(trace.marks) == [f"bench:q06:{n}" for n in (6, 7, 8, 9)]
    lo, hi = trace.marks["bench:q06:6"]
    assert lo == pytest.approx(0.151101332) and hi == pytest.approx(0.197957903)


def test_busy_union_and_idle_share(trace):
    ops = trace.chips[0]
    merged = xplane.busy(ops)
    lo, hi = trace.marks["bench:q06:6"][0], trace.marks["bench:q06:8"][1]
    busy_s = xplane.covered(merged, lo, hi)
    # operations of one chip do not overlap: the union is their sum
    assert busy_s == pytest.approx(sum(o.end - o.start for o in ops), rel=1e-9)
    assert busy_s == pytest.approx(0.001327243, rel=1e-6)
    assert hi - lo == pytest.approx(0.141252032, rel=1e-9)
    assert 1.0 - busy_s / (hi - lo) == pytest.approx(0.990604, abs=1e-6)
    # per statement: the same 1488 operations, the same 0.44 ms
    for n in (6, 7, 8):
        s, e = trace.marks[f"bench:q06:{n}"]
        assert sum(1 for o in ops if s <= o.start < e) == 1488
        assert xplane.covered(merged, s, e) == pytest.approx(0.0004424, rel=2e-3)
    assert xplane.covered(merged, *trace.marks["bench:q06:9"]) == 0.0
    idle = xplane.gaps(merged, lo, hi)
    assert sum(e - s for s, e in idle) == pytest.approx(hi - lo - busy_s)


def test_per_op_totals_carry_program_and_op_names(trace):
    lo, hi = trace.marks["bench:q06:6"][0], trace.marks["bench:q06:8"][1]
    top = xplane.op_totals(trace.chips[0], lo, hi, top=3)
    assert [n for n, _ in top] == ["jit_agg_stage/fusion.1201",
                                   "jit_fold/and_or_fusion",
                                   "jit_agg_stage/add_select_fusion.518"]
    assert top[0][1] == pytest.approx(0.00021386, rel=1e-4)
    assert len(xplane.op_totals(trace.chips[0], lo, hi)) == 10
    assert xplane.short_name(
        "%fusion.12 = (u32[8]{0}, pred[8]{0}) fusion(u32[8]{0} %p), "
        "kind=kLoop") == "fusion.12"


def test_no_collective_on_one_chip(trace):
    lo, hi = trace.marks["bench:q06:6"][0], trace.marks["bench:q06:8"][1]
    assert xplane.collective_s(trace.chips[0], trace.async_ops[0], lo, hi) == 0.0


def test_recorded_four_chip_trace_one_mesh_statement():
    """One q14 of ``tpch_sf1.mesh_join`` on four ``TPU v5 lite`` chips
    (26.6 s, text new to the server): every chip busy 1.43 s at the end
    of it, and no collective operation ran."""
    mesh = xplane.read(MESH_TRACE)
    assert sorted(mesh.chips) == [0, 1, 2, 3]
    assert [len(mesh.chips[c]) for c in range(4)] == [3707, 2700, 2700, 2700]
    (lo, hi), = mesh.marks.values()
    assert hi - lo == pytest.approx(26.608283, rel=1e-6)
    shares = [xplane.covered(xplane.busy(mesh.chips[c]), lo, hi) / (hi - lo)
              for c in range(4)]
    assert min(shares) == pytest.approx(0.05391, rel=1e-3)
    assert max(shares) == pytest.approx(0.05392, rel=1e-3)
    for c in range(4):
        assert xplane.collective_s(mesh.chips[c], mesh.async_ops.get(c, []),
                                   lo, hi) == 0.0
    # the device works in the last three seconds of the statement
    merged = xplane.busy(mesh.chips[0])
    assert xplane.covered(merged, lo + 2.0, hi - 3.0) == 0.0
    assert xplane.op_totals(mesh.chips[0], lo, hi, top=1)[0][0] \
        == "jit_per_device_wave/fusion.23"


def _recorded_run(cell, path):
    """A ``run.Run`` of ``cell`` whose one traced pass is the recorded
    trace's statements in order, with the ``TPU v5 lite`` peak of
    ``peaks.json`` and the row counts of the cell's configuration."""
    trace = xplane.read(path)
    one = loadgen.Pass(0, 1, 0.0, [
        loadgen.QueryRecord(mark.split(":")[1], int(mark.split(":")[2]),
                            0.0, 0.0, True, stats={})
        for mark in sorted(trace.marks, key=lambda m: trace.marks[m])])
    loaded = specs.load_cell(cell)
    conn = tables.generator_for(loaded.config)
    rows = {t: conn.row_count(t) for t in loaded.config["tables"]}
    peaks = specs.read_json(specs.ROOT, "peaks.json")["TPU v5 lite"]
    return bench_run.Run(loaded, {}, peaks, rows, {}, [one], {},
                         trace=trace, traced=[one])


def _hbm_roofline():
    return {r.NAME: r for r in bench_run.layer_metric_readers()}[
        "hbm_roofline_pct"]


def test_hbm_roofline_on_the_recorded_mesh_statement_by_hand():
    """One q14 on four chips: SF1's q14 bytes over four chips' peak,
    over the time the busiest chip (chip 3, not chip 0) was busy."""
    run = _recorded_run("tpch_sf1.mesh_join", MESH_TRACE)
    assert run.cell.chips == 4 and run.row_counts["lineitem"] == 5_997_031
    # lineitem: l_partkey 8 + l_extendedprice 8 + l_discount 8 +
    # l_shipdate 4; part: p_partkey 8 + p_type 4
    need = 28 * 5_997_031 + 12 * 200_000
    assert bytes_needed.pass_bytes(run.cell.config, run.cell.queries,
                                   run.row_counts) == need == 170_316_868
    (lo, hi), = run.pass_intervals
    per_chip = [xplane.covered(xplane.busy(run.trace.chips[c]), lo, hi)
                for c in range(4)]
    busiest = max(per_chip)
    assert per_chip.index(busiest) == 3 and busiest > per_chip[0]
    assert busiest == pytest.approx(1.43465533, rel=1e-8)
    assert run.busiest_s_per_pass() == [busiest]
    value = _hbm_roofline().read(run)
    assert value == pytest.approx(100.0 * need / (4 * 819e9) / busiest,
                                  rel=1e-12)
    assert value == pytest.approx(0.0036238164567567, rel=1e-9)


def test_hbm_roofline_on_the_recorded_one_chip_pass_reads_as_the_parent():
    """One pass of ``tpch_sf1.join_agg`` (q3 then q14): the number the
    one-chip reader read before the mesh formula, to the bit."""
    run = _recorded_run("tpch_sf1.join_agg", PASS_TRACE)
    assert run.busiest_s_per_pass() == run.busy_s_per_pass()
    assert _hbm_roofline().read(run) == 0.019584653520843178


Op = xplane.Op


def test_collective_total_counts_each_collective_once():
    ops = [Op(0.0, 1.0, "fusion.1", "jit_wave"),
           Op(1.0, 1.5, "all-reduce.3", "jit_wave"),
           Op(1.5, 1.6, "all-to-all-start.1", "jit_final"),
           Op(2.0, 2.1, "all-to-all-done.1", "jit_final"),
           Op(9.0, 9.5, "all-gather.2", "jit_wave")]  # outside [0, 5)
    in_flight = [Op(1.5, 2.1, "all-to-all-start.1", "jit_final"),
                 Op(1.0, 1.2, "copy-start.4", "jit_wave")]
    assert xplane.collective_s(ops, in_flight, 0.0, 5.0) == pytest.approx(0.5 + 0.6)
    assert xplane.is_collective("collective-permute.7")
    assert not xplane.is_collective("fusion.7")


@pytest.mark.parametrize("intervals,merged", [
    ([], []),
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
    ([(2, 3), (0, 1), (0.5, 2.5)], [(0, 3)]),
    ([(0, 5), (1, 2)], [(0, 5)]),
    ([(0, 1), (1, 2)], [(0, 2)]),
])
def test_union(intervals, merged):
    assert xplane.union(intervals) == merged


def test_covered_and_gaps_clip_to_the_stretch():
    merged = [(0.0, 1.0), (2.0, 3.0), (5.0, 9.0)]
    assert xplane.covered(merged, 0.5, 6.0) == pytest.approx(0.5 + 1.0 + 1.0)
    assert xplane.gaps(merged, 0.5, 6.0) == [(1.0, 2.0), (3.0, 5.0)]
    assert xplane.gaps(merged, 3.5, 4.0) == [(3.5, 4.0)]
    assert xplane.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_gap_attribution_names_the_query_and_the_innermost_span():
    queries = [("q06", 0.0, 4.0), ("q01", 5.0, 9.0)]
    spans = [("plan", 0.0, 1.0), ("execute", 1.0, 4.0),
             ("op:Aggregation", 1.5, 3.5), ("device_get", 3.0, 3.4),
             ("plan", 5.0, 6.0)]
    idle = [(0.2, 0.8),    # q06, planning
            (1.1, 1.3),    # q06, executing, no operator open
            (2.0, 2.5),    # q06, in the aggregation
            (3.1, 3.3),    # q06, the read inside the aggregation
            (4.2, 4.8),    # between the statements
            (5.2, 5.6),    # q01, planning
            (8.0, 8.5)]    # q01, outside every program span
    got = dict(xplane.attribute_gaps(idle, queries, spans))
    assert got == pytest.approx({
        "q06/plan": 0.6, "q06/execute": 0.2, "q06/op:Aggregation": 0.5,
        "q06/device_get": 0.2, "client/between_queries": 0.6,
        "q01/plan": 0.4, "q01/protocol": 0.5})
    assert len(xplane.attribute_gaps(idle, queries, spans, top=2)) == 2


# rows of the spec's population at SF1 (connectors/tpch.py: 6,001,215
# would be dbgen's; this generator makes 5,998,826 lineitem rows)
ROWS = {"lineitem": 1000, "part": 40, "orders": 250, "customer": 25}


def test_bytes_needed_q6_and_q14_by_hand():
    cell = specs.load_cell("tpch_sf10.scan_agg")
    q6 = next(q for q in cell.queries if q.name == "q06")
    # l_quantity 8 + l_extendedprice 8 + l_discount 8 + l_shipdate 4
    assert bytes_needed.query_bytes(cell.config, q6.reads, ROWS) == 28 * 1000
    q1 = next(q for q in cell.queries if q.name == "q01")
    # four decimals 32 + two dictionary codes 8 + the date 4
    assert bytes_needed.query_bytes(cell.config, q1.reads, ROWS) == 44 * 1000
    assert bytes_needed.pass_bytes(cell.config, cell.queries, ROWS) == 72 * 1000
    join = specs.load_cell("tpch_sf10.join")
    q14 = join.queries[0]
    # lineitem: l_partkey 8 + l_extendedprice 8 + l_discount 8 +
    # l_shipdate 4; part: p_partkey 8 + p_type 4
    assert bytes_needed.query_bytes(join.config, q14.reads, ROWS) \
        == 28 * 1000 + 12 * 40
