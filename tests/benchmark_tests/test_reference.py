"""The plain reference (benchmark/reference/): ``make_expected.py`` at
SF0.01 writes answers the engine matches, a wrong expected file makes a
run say ``"correct": false``, and the comparison holds its tolerances."""

import json
import os
from decimal import Decimal

import pytest

import bench_rehearsal as rehearsal
from benchmark import specs, tables
from benchmark.reference import rows_match

QUERIES = ["q01", "q03", "q06", "q14"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench_ref")))


@pytest.fixture(scope="module")
def engine(checkout):
    """The SF0.01 tables through the benchmark's own load path, behind a
    QueryRunner in this process."""
    from presto_tpu.catalog import Catalog
    from presto_tpu.runner import QueryRunner

    root = os.path.join(checkout, "benchmark")
    config = specs.read_json(root, "configs", "tpch_sf1.json")
    mem, rows, _ = tables.load(config, root)
    catalog = Catalog()
    catalog.register("mem", mem)
    return QueryRunner(catalog), config, root, rows


@pytest.mark.parametrize("name", QUERIES)
def test_make_expected_writes_what_the_engine_answers(engine, name):
    runner, config, root, _ = engine
    query = specs.load_query(root, config, name)
    assert query.expected, "make_expected.py wrote no rows"
    result = runner.execute(query.sql)
    mismatch = rows_match.mismatch(
        [tuple(r) for r in result.rows], query.expected, query.ordered)
    assert mismatch is None, mismatch


def test_load_path_keeps_what_load_from_keeps(engine):
    """``tables.load`` builds its pages itself; the resident tables must
    be the ones ``MemoryConnector.load_from`` makes."""
    from presto_tpu.connectors.memory import MemoryConnector

    _, config, root, rows = engine
    conn = tables.generator_for(config, root)
    theirs = MemoryConnector()
    for table, wanted in config["tables"].items():
        theirs.load_from(conn, table, columns=list(wanted))
    ours, _, _ = tables.load(config, root)
    for table in config["tables"]:
        assert ours.schema(table) == theirs.schema(table)
        assert ours.num_splits(table) == theirs.num_splits(table)
        assert ours.row_count(table) == theirs.row_count(table) == rows[table]
        assert ours.primary_key(table) == theirs.primary_key(table)
        assert ours.sort_order(table) == theirs.sort_order(table)
        assert ours.bucketing(table) == theirs.bucketing(table)
        for column, _ in ours.schema(table):
            assert ours.column_domain(table, column) \
                == theirs.column_domain(table, column)
        for s in range(ours.num_splits(table)):
            a, b = ours.page_for_split(table, s), theirs.page_for_split(table, s)
            assert a.capacity == b.capacity
            assert (a.row_mask == b.row_mask).all()
            for x, y in zip(a.blocks, b.blocks):
                assert x.data.dtype == y.data.dtype
                assert (x.data == y.data).all() and (x.valid == y.valid).all()


def test_second_load_maps_the_cached_columns(engine):
    _, config, root, rows = engine
    directory = tables.cache_dir(config, root)
    assert os.path.exists(os.path.join(directory, "lineitem.json"))
    before = os.path.getmtime(os.path.join(directory, "lineitem.0.l_shipdate.npy"))
    _, again, _ = tables.load(config, root)
    assert again == rows
    assert os.path.getmtime(
        os.path.join(directory, "lineitem.0.l_shipdate.npy")) == before


def test_a_wrong_expected_file_fails_the_run(checkout):
    path = os.path.join(checkout, "benchmark", "expected", "tpch_sf10",
                        "q06.json")
    with open(path) as f:
        good = f.read()
    wrong = json.loads(good)
    wrong["rows"][0][0] *= 1.0001
    try:
        with open(path, "w") as f:
            json.dump(wrong, f)
        result = rehearsal.last_line(
            rehearsal.run_cell(checkout, "tpch_sf10.scan_agg"))
    finally:
        with open(path, "w") as f:
            f.write(good)
    assert result["correct"] is False
    # every q6 of the window failed, no q1 did
    assert result["attempted"] >= 2
    assert 1 <= result["failed"] <= (result["attempted"] + 1) // 2
    # a pass with a wrong answer is not a pass: nothing to time
    assert set(result["metrics"]) == {"setup_s"}


@pytest.mark.parametrize("config", ["tpch_sf1", "tpch_sf10"])
def test_committed_expected_answers_are_there(config):
    rows = {}
    for name in QUERIES:
        stored = specs.read_json(specs.ROOT, "expected", config, name + ".json")
        assert stored["config"] == config and stored["query"] == name
        rows[name] = stored["rows"]
    assert len(rows["q01"]) == 4 and len(rows["q03"]) == 10
    assert len(rows["q06"]) == 1 and len(rows["q14"]) == 1
    assert 15.0 < rows["q14"][0][0] < 18.0  # promo revenue, percent
    revenues = [r[1] for r in rows["q03"]]
    assert revenues == sorted(revenues, reverse=True)


@pytest.mark.parametrize("actual,expected,ordered,same", [
    ([(1, "a")], [(1, "a")], True, True),
    ([(1, "a")], [(1, "b")], True, False),
    ([(1,), (2,)], [(2,), (1,)], False, True),
    ([(1,), (2,)], [(2,), (1,)], True, False),
    ([(1.0,)], [(1.0 + 5e-10,)], True, True),      # rel 1e-9
    ([(1.0,)], [(1.0 + 5e-6,)], True, False),         # past abs 1e-6
    ([(1e12,)], [(1e12 + 500.0,)], True, True),    # rel 1e-9 of 1e12
    ([(1e12,)], [(1e12 + 5000.0,)], True, False),
    ([(Decimal("25.45"),)], [(25.4541,)], True, True),   # half a cent
    ([(Decimal("25.45"),)], [(25.456,)], True, False),
    ([(Decimal("517872347.8532"),)], [(517872347.8532,)], True, True),
    ([(None,)], [(None,)], True, True),
    ([(None,)], [(0.0,)], True, False),
    ([(1,)], [(1,), (2,)], True, False),
])
def test_rows_match_tolerances(actual, expected, ordered, same):
    assert (rows_match.mismatch(actual, expected, ordered) is None) == same


def test_decode_rows_makes_decimals_of_the_protocol_strings():
    columns = [{"name": "a", "type": "decimal(36,4)"},
               {"name": "b", "type": "bigint"}, {"name": "c", "type": "varchar"}]
    assert rows_match.decode_rows(columns, [("12.3400", 7, "x"),
                                           (None, None, None)]) == [
        (Decimal("12.3400"), 7, "x"), (None, None, None)]
