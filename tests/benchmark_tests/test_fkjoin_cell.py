"""The configuration ``tpch_sf1_fkjoin`` and its cell
``tpch_sf1_fkjoin.csr_join`` (PR 27): TPC-H Q4 and Q13 over the spec's
``O_CUSTKEY`` population.  At SF0.01 the engine answers what the pandas
reference wrote, under the join leg XLA:CPU picks and under the one a
TPU picks (the CSR ``starts`` table); the committed SF1 answers have
the spec's shape; the cell runs end to end in the rehearsal; the four
readers the cell brings read what they say, and the expansion's bytes
depend on the rows emitted and on nothing of the program's."""

import json
import os

import pytest

import bench_rehearsal as rehearsal
from benchmark import expand_bytes, loadgen, run as bench_run, scopes, specs
from benchmark import tables
from benchmark.reference import rows_match

CELL = "tpch_sf1_fkjoin.csr_join"
CONFIG = "tpch_sf1_fkjoin"
READERS = {r.NAME: r for r in bench_run.layer_metric_readers()}
NEW = ("join_index_ms", "join_expand_ms", "expand_retries_per_pass",
       "join_expand_roofline_pct")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench_fkjoin")))


def _catalog(config, root):
    from presto_tpu.catalog import Catalog

    mem, rows, _ = tables.load(config, root)
    catalog = Catalog()
    catalog.register("mem", mem)
    return catalog, rows


@pytest.fixture(scope="module")
def loaded(checkout):
    """The SF0.01 tables through the benchmark's own load path."""
    root = os.path.join(checkout, "benchmark")
    config = specs.read_json(root, "configs", CONFIG + ".json")
    return _catalog(config, root) + (config, root)


def _runner(catalog):
    """A registry of its own: a build traced under a forced leg must
    neither reuse nor leak into another test's programs."""
    from presto_tpu.exec.programs import ProgramRegistry
    from presto_tpu.runner import QueryRunner

    return QueryRunner(catalog, programs=ProgramRegistry())


@pytest.fixture(params=[None, True], ids=["leg_cpu_picks", "csr_leg"])
def leg(request, monkeypatch):
    """None: what the backend picks (XLA:CPU: no ``starts`` table);
    True: the chip's.  Yields the forced value and the count of builds
    that took the table."""
    from presto_tpu.ops import join

    taken = []

    def profitable():
        taken.append(join.resolve_direct_join())
        return taken[-1]

    monkeypatch.delenv("PRESTO_TPU_DIRECT_JOIN", raising=False)
    join.set_direct_join_override(request.param)
    monkeypatch.setattr(join, "_direct_table_profitable", profitable)
    yield request.param, taken
    join.set_direct_join_override(None)


@pytest.mark.parametrize("name", ["q04", "q13"])
def test_engine_answers_what_the_reference_wrote(loaded, leg, name):
    catalog, _, config, root = loaded
    forced, taken = leg
    query = specs.load_query(root, config, name)
    assert query.expected, "make_expected.py wrote no rows"
    result = _runner(catalog).execute(query.sql)
    mismatch = rows_match.mismatch(
        [tuple(r) for r in result.rows], query.expected, query.ordered)
    assert mismatch is None, mismatch
    # the build is not a primary key: it reached the sorted leg's gate
    assert taken and all(t is bool(forced) for t in taken)
    assert (result.expanded_rows > 0) == (name == "q13")


def test_committed_q04_answer_is_there_and_sane():
    stored = specs.read_json(specs.ROOT, "expected", CONFIG, "q04.json")
    assert stored["config"] == CONFIG and stored["query"] == "q04"
    assert [r[0] for r in stored["rows"]] == [
        "1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
    counts = [r[1] for r in stored["rows"]]
    assert max(counts) <= 1.05 * min(counts) and min(counts) > 10000


def test_committed_q13_answer_opens_with_the_orderless_third():
    stored = specs.read_json(specs.ROOT, "expected", CONFIG, "q13.json")
    assert stored["config"] == CONFIG and stored["query"] == "q13"
    rows = stored["rows"]
    assert rows[0][0] == 0 and 49000 <= rows[0][1] <= 51000
    assert sum(r[1] for r in rows) == 150000
    assert [r[1] for r in rows] == sorted((r[1] for r in rows), reverse=True)


def test_the_cell_names_its_own_generator_and_query_directory():
    """Two traps (ISSUE 27): ``make_expected`` runs every ``.sql`` of
    a configuration's query directory, and ``tables.cache_dir`` keys
    the host columns by generator name."""
    cell = specs.load_cell(CELL)
    other = specs.read_json(specs.ROOT, "configs", "tpch_sf1.json")
    assert cell.config["queries"] != other["queries"]
    assert tables.cache_dir(cell.config) != tables.cache_dir(other)
    assert [q.name for q in cell.queries] == ["q04", "q13"]
    assert set(other["tables"]["orders"]) < set(cell.config["tables"]["orders"])


def test_the_mix_is_laid_out_as_the_older_mixes_are():
    """ISSUE 27 fixed the traffic before any code: the seed rotates
    the list's start, as in ``join_agg``.  On the chip q13's time
    depends on which statement opened the process (PERF.md, PR 27);
    the mix does not pick the start that hides it."""
    cell = specs.load_cell(CELL)
    mix = cell.traffic
    older = specs.read_json(specs.ROOT, "traffic", "join_agg.json")
    assert mix["queries"] == ["q04", "q13"]
    for key in ("loop", "clients", "rotate_start_by_seed",
                "trailing_spaces"):
        assert mix[key] == older[key], key
    opens = [loadgen.Statements(mix, cell.queries, seed).order[0].name
             for seed in (2147483998, 2147483999)]
    assert opens == ["q04", "q13"]


@pytest.fixture(scope="module")
def rehearsed(checkout):
    """One untraced and one traced run of the cell at SF0.01."""
    return [rehearsal.run_cell(checkout, CELL, trace=t, seconds=3.0,
                               seed=2147483999) for t in (0, 1)]


def test_the_cell_runs_end_to_end(rehearsed):
    result = rehearsal.last_line(rehearsed[0])
    assert set(result) == rehearsal.RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert set(result["metrics"]) == {"setup_s", "pass_p50_ms", "rows_per_s"}
    observations = json.loads(rehearsed[0].stdout.strip().splitlines()[-2])
    assert set(observations["per_query_p50"]) == {"q04", "q13"}
    assert observations["window_counters"] == {
        "programs": 0, "persistent_hits": 0, "persistent_misses": 0}


def test_the_cell_runs_traced_and_prints_its_retries(rehearsed):
    result = rehearsal.last_line(rehearsed[1])
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    # q13's one customer page expands past its own capacity: one retry
    assert metrics["expand_retries_per_pass"] == {"value": 1, "unit": "count"}
    assert metrics["host_reads_per_pass"]["value"] >= 4
    # XLA:CPU's trace has no device plane: no scope metric, no roofline
    assert not {"join_index_ms", "join_expand_ms",
                "join_expand_roofline_pct"} & set(metrics)
    programs = {name.split("/")[0]
                for name, _ in result["breakdown"]["device_ops"]}
    assert "jit_join_build" in programs


def test_manifest_lists_the_four_metrics_for_the_cell_alone():
    with open(os.path.join(rehearsal.REPO, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        entry = listed[name]
        assert entry["workloads"] == READERS[name].WORKLOADS == [CELL]
        assert entry["unit"] == READERS[name].UNIT
    assert listed["expand_retries_per_pass"]["layer"] == "Executor"
    assert listed["expand_retries_per_pass"]["source"] == "program_counter"
    assert listed["join_expand_roofline_pct"]["moves"] == "rows_per_s"
    assert listed["join_expand_roofline_pct"]["better"] == "higher"


# -- the readers, on a pass made by hand -----------------------------------

Q13_ROWS = 1_547_000
ROW_COUNTS = {"customer": 150_000, "orders": 1_500_000,
              "lineitem": 6_001_215, "part": 200_000}


def _made_run(monkeypatch, stats13, ops, peaks=True):
    """A ``run.Run`` of the cell with one traced pass of one second:
    q04 then q13 with the given final-page stats, and ``ops`` as chip
    0's scoped operations."""
    queries = [loadgen.QueryRecord("q04", 1, 0.0, 0.0, True, stats={}),
               loadgen.QueryRecord("q13", 2, 0.0, 0.0, True,
                                   stats=dict(stats13))]
    one = loadgen.Pass(0, 1, 0.0, queries)
    run = bench_run.Run(
        specs.load_cell(CELL), {},
        {"hbm_bytes_per_s": 819e9} if peaks else None, ROW_COUNTS, {}, [one],
        {}, trace=type("T", (), {"stands_in": False})(), traced=[one])
    run.__dict__["pass_intervals"] = [(0.0, 1.0)]
    monkeypatch.setattr(scopes, "for_run", lambda r: ops)
    return run


OPS = [scopes.ScopedOp(0.00, 0.10, "jit_join_build",
                       ("op:JoinBuild", "join:index")),
       scopes.ScopedOp(0.10, 0.12, "jit_join_build", ("op:JoinBuild",)),
       scopes.ScopedOp(0.20, 0.25, "jit_join_probe",
                       ("op:Join", "join:lookup")),
       scopes.ScopedOp(0.25, 0.45, "jit_join_probe",
                       ("op:Join", "join:expand")),
       scopes.ScopedOp(0.50, 0.60, "jit_join_probe", ())]  # the while


def test_readers_read_their_scopes_and_counters(monkeypatch):
    run = _made_run(monkeypatch,
                    {"expandRetries": 1, "expandedRows": Q13_ROWS}, OPS)
    assert READERS["join_index_ms"].read(run) == pytest.approx(100.0)
    assert READERS["join_expand_ms"].read(run) == pytest.approx(200.0)
    assert READERS["expand_retries_per_pass"].read(run) == 1
    need = expand_bytes.pass_bytes(run.cell.config, run.passes[0].queries,
                                   ROW_COUNTS)
    # c_custkey and o_orderkey written and read per emitted row, both
    # key columns once
    assert need == 2 * Q13_ROWS * 16 + 150_000 * 8 + 1_500_000 * 8
    assert READERS["join_expand_roofline_pct"].read(run) == pytest.approx(
        100.0 * need / 819e9 / 0.2)
    assert READERS["join_expand_roofline_pct"].read(run) < 1.0


def test_readers_say_nothing_of_a_program_without_scope_or_counter(
        monkeypatch):
    """The parent of PR 27 under these files: no counter in the stats,
    no ``join:`` scope but ``join:lookup`` in the trace."""
    bare = [scopes.ScopedOp(o.start, o.end, o.module, o.scopes[:1])
            for o in OPS]
    run = _made_run(monkeypatch, {"hostReads": 4}, bare)
    assert READERS["expand_retries_per_pass"].read(run) is None
    assert READERS["join_expand_roofline_pct"].read(run) is None
    assert READERS["join_index_ms"].read(run) == 0.0
    assert READERS["join_expand_ms"].read(run) == 0.0
    # ... and of a rehearsal: no scoped operations, no peak
    run = _made_run(monkeypatch,
                    {"expandRetries": 1, "expandedRows": Q13_ROWS}, None,
                    peaks=False)
    assert READERS["join_index_ms"].read(run) is None
    assert READERS["join_expand_roofline_pct"].read(run) is None
    assert READERS["expand_retries_per_pass"].read(run) == 1


def test_expand_bytes_count_rows_not_buffers(loaded, checkout):
    """The same q13 over customer pages of another size expands
    through other capacities and other retries; the rows emitted, and
    so the bytes, are the same."""
    catalog, rows, config, root = loaded
    small = dict(config, split_rows=512)
    small_catalog, small_rows = _catalog(small, root)
    assert small_rows == rows
    sql = specs.load_query(root, config, "q13").sql
    a = _runner(catalog).execute(sql)
    b = _runner(small_catalog).execute(sql)
    assert a.rows == b.rows
    assert a.expand_retries == 1 and b.expand_retries == 3
    assert a.expanded_rows == b.expanded_rows > rows["orders"]
    for res in (a, b):
        assert expand_bytes.query_bytes(
            config, "q13", res.expanded_rows, rows, root
        ) == 2 * a.expanded_rows * 16 + 8 * (rows["customer"] + rows["orders"])
    assert expand_bytes.query_bytes(config, "q04", 10 ** 6, rows, root) == 0
