"""The configuration ``tpcds_sf10`` and its cell
``tpcds_sf10.star_join`` (PR 37): TPC-DS Q3 and Q7 over the
``store_sales`` star, one unfiltered fact chain probing two and four
filtered primary-key dimension builds in a row.  At SF0.01, through the
benchmark's own load path, the engine answers what the pandas reference
writes, with ``item`` and ``promotion`` at the spec's SF10 sizes as
``generators/tpcds.py`` sets them and once at the connector's own; each
fact chain has the probes the plan says and compacts where
``_compact_at`` says; the committed configuration is the one ISSUE 37
names and its stored answers have the queries' shape; the cell runs end
to end in the rehearsal and prints every general metric; the four
readers the cell brings read what they say, and the first probes' bytes
depend on row counts and stored widths and on nothing of the
program's."""

import inspect
import json
import os

import pytest

import bench_rehearsal as rehearsal
import manifest_shape as shape
from benchmark import loadgen, run as bench_run, scopes, specs
from benchmark import star_probe_bytes, tables
from benchmark.reference import make_expected, pandas_tpcds, rows_match

CELL = "tpcds_sf10.star_join"
CONFIG = "tpcds_sf10"
OLD_CELL = "tpch_sf1.join_agg"
QUERIES = ("ds_q03", "ds_q07")
READERS = {r.NAME: r for r in bench_run.layer_metric_readers()}
NEW = ("star_probe_first_ms", "star_probe_rest_ms", "chain_probes_per_pass",
       "star_probe_roofline_pct")
#: query -> (probes of its fact chain, its program)
CHAINS = {
    "ds_q03": (2, "chain_leaf_probe_probe_agg_k3a1_compact_in_probe0"),
    "ds_q07": (4, "chain_leaf_probe_probe_probe_probe_agg_k1a4"
                  "_compact_in_probe0"),
}


#: customer_demographics in the test's copy: a hundredth of the cross
#: product, as SF0.01's fact table is a thousandth of SF10's.  Whole, it
#: outgrows the fact table and the planner orders ds_q07's probes
#: otherwise (``generators/tpcds.py``)
CD_ROWS = 19208


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The rehearsal's copy with ``cd_rows`` set in this configuration
    and its answers made again."""
    tmp = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench_star")))
    root = os.path.join(tmp, "benchmark")
    path = os.path.join(root, "configs", CONFIG + ".json")
    config = specs.read_json(root, "configs", CONFIG + ".json")
    assert "cd_rows" not in config
    with open(path, "w") as f:
        json.dump(dict(config, cd_rows=CD_ROWS), f)
    rehearsal.make_expected(root, CONFIG)
    return tmp


def _loaded(config, root):
    """(runner over the configuration's tables through
    ``tables.load``, rows per table, the reference's answers from the
    same generator)."""
    from presto_tpu.catalog import Catalog
    from presto_tpu.exec.programs import ProgramRegistry
    from presto_tpu.runner import QueryRunner

    mem, rows, _ = tables.load(config, root)
    catalog = Catalog()
    catalog.register("mem", mem)
    frames = make_expected.load_frames(tables.generator_for(config, root),
                                       config["tables"])
    want = {q: pandas_tpcds.PROGRAMS[q](frames) for q in QUERIES}
    return QueryRunner(catalog, programs=ProgramRegistry()), rows, want


@pytest.fixture(scope="module")
def star(checkout):
    """The SF0.01 star with ``item`` and ``promotion`` at the sizes
    the committed configuration names."""
    root = os.path.join(checkout, "benchmark")
    config = specs.read_json(root, "configs", CONFIG + ".json")
    assert config["dimension_rows"] == {"item": 102000, "promotion": 500}
    return _loaded(config, root) + (config, root)


@pytest.fixture(scope="module")
def connectors_own(star):
    """The same star with the dimensions left at the connector's sizes
    (SF1's, at every scale factor); another split size, so another
    column cache."""
    config = {k: v for k, v in star[3].items() if k != "dimension_rows"}
    config["split_rows"] = rehearsal.TINY_SPLIT_ROWS // 2
    return _loaded(config, star[4])


def _answer(runner, root, config, name, pad=""):
    query = specs.load_query(root, config, name)
    result = runner.execute(query.sql + pad)
    rows = rows_match.decode_rows(
        [{"type": repr(t)} for t in result.types],
        [tuple(r) for r in result.rows])
    return query, result, rows


@pytest.mark.parametrize("name", QUERIES)
def test_engine_answers_what_the_reference_wrote(star, name):
    runner, rows, want, config, root = star
    assert rows["item"] == 102000 and rows["promotion"] == 500
    assert rows["customer_demographics"] == CD_ROWS
    query, result, got = _answer(runner, root, config, name)
    assert query.ordered and query.expected == want[name]  # the stored file
    assert got, "no row at SF0.01"
    assert rows_match.mismatch(got, query.expected, True) is None
    assert result.chain_probes == CHAINS[name][0]
    # one store_sales page in four splits, each compacted, none again
    assert (result.compacted_pages, result.compact_fallback_pages) == (4, 0)


@pytest.mark.parametrize("name", QUERIES)
def test_the_override_changes_the_groups_and_nothing_breaks(
        star, connectors_own, name):
    """Without ``dimension_rows`` the generator returns the connector
    as it is: ``item`` 18,000 and ``promotion`` 300.  The engine and the
    reference still agree, on another answer."""
    runner, rows, want = connectors_own
    assert rows["item"] == 18000 and rows["promotion"] == 300
    assert {t: n for t, n in rows.items()
            if t not in ("item", "promotion")} == \
        {t: n for t, n in star[1].items() if t not in ("item", "promotion")}
    _, result, got = _answer(runner, star[4], star[3], name)
    assert got and rows_match.mismatch(got, want[name], True) is None
    assert want[name] != star[2][name]
    assert result.chain_probes == CHAINS[name][0]


@pytest.mark.parametrize("name", QUERIES)
def test_each_fact_chain_probes_in_a_row_and_compacts_in_its_first(
        star, name, monkeypatch):
    """The leaf is the bare ``store_sales`` scan, every stage up to
    the partial aggregation a probe of a filtered primary-key build,
    and the one compaction sits where ``_compact_at`` puts it: inside
    probe 0, k from the join's estimated rows over the scan's."""
    from presto_tpu.exec import chain as chain_mod
    from presto_tpu.planner.plan import TableScanNode

    runner, _, _, config, root = star
    ex = runner.executor
    lowered, lower = [], ex._lower

    def recording(node, compact_k=None):
        lowered.append((node, lower(node, compact_k)))
        return lowered[-1][1]

    monkeypatch.setattr(ex, "_lower", recording)
    _answer(runner, root, config, name, pad=" ")
    (node, chain), = [(n, c) for n, c in lowered if c.probes]
    probes, program = CHAINS[name]
    assert chain.name() == program and chain.probes == probes
    assert isinstance(chain.leaf, TableScanNode)
    assert chain.leaf.handle.table == "store_sales"
    kinds = [s.kind for s in chain.stages]
    assert kinds == (["lookup", "compact", "fetch"]
                     + ["probe"] * (probes - 1) + ["agg_partial"])
    whole = lower(node, 0)
    assert [s.kind for s in whole.stages] == \
        ["probe"] * probes + ["agg_partial"]
    i, k, inside = chain_mod._compact_at(whole.leaf, list(whole.stages), None)
    assert (i, inside) == (0, True) and k >= chain_mod.COMPACT_MIN_K[True]
    assert chain.stages[1].params.k == k
    # every build is a dimension's primary key, filtered but item's in
    # ds_q07 (the one that only names the groups)
    assert all(j.unique_build and j.kind == "inner" for j in chain.joins)


def test_the_committed_configuration_is_the_one_the_issue_names():
    cell = specs.load_cell(CELL)
    config = cell.config
    assert (config["scale_factor"], config["split_rows"]) == (10, 1 << 23)
    assert config["dimension_rows"] == {"item": 102000, "promotion": 500}
    assert "cd_rows" not in config
    assert (config["generator"], config["queries"]) == ("tpcds", "tpcds")
    assert list(config["reduced"]) == ["scale_factor"]
    assert "SF100" in config["reduced"]["scale_factor"][0]
    assert config["layouts"]["1"] == specs.read_json(
        specs.ROOT, "configs", "tpch_sf1_fkjoin.json")["layouts"]["1"]
    assert len(config["source"]) <= 200 and "table 3-2" in config["source"]
    assert {t: sorted(c) for t, c in config["tables"].items()} == {
        "store_sales": sorted([
            "ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_promo_sk",
            "ss_quantity", "ss_list_price", "ss_sales_price",
            "ss_ext_sales_price", "ss_coupon_amt"]),
        "item": sorted(["i_item_sk", "i_item_id", "i_brand_id", "i_brand",
                        "i_manufact_id"]),
        "date_dim": ["d_date_sk", "d_moy", "d_year"],
        "customer_demographics": sorted([
            "cd_demo_sk", "cd_gender", "cd_marital_status",
            "cd_education_status"]),
        "promotion": ["p_channel_email", "p_channel_event", "p_promo_sk"]}
    # stored bytes: 8 for a bigint or a decimal(12,2), 4 for a code
    conn = tables.generator_for(config)
    for table, columns in config["tables"].items():
        types = dict(conn.schema(table))
        for column, width in columns.items():
            assert width == (4 if types[column].is_string else 8), column
    assert conn.row_count("store_sales") == 28_800_000
    assert conn.num_splits("store_sales") == 4
    assert [conn.num_splits(t) for t in config["tables"]
            if t != "store_sales"] == [1, 1, 1, 1]
    assert (conn.row_count("item"), conn.row_count("promotion")) == \
        (102000, 500)
    # the sizes reach what is drawn from them, not the row count alone
    assert conn.column_domain("store_sales", "ss_item_sk") == (1, 102000)
    assert len(conn.dictionary_for("item", "i_item_id")) == 102000
    assert cell.chips == 1 and len(cell.traffic["queries"]) == 2
    from tests.tpcds_queries import QUERIES as TEXT

    for q in cell.queries:  # the repository's own text, to the letter
        assert q.sql == TEXT[int(q.name[len("ds_q"):])].strip()


def test_the_mix_is_laid_out_as_the_older_mixes_are():
    cell = specs.load_cell(CELL)
    mix = cell.traffic
    older = specs.read_json(specs.ROOT, "traffic", "join_agg.json")
    assert mix["queries"] == list(QUERIES)
    for key in ("loop", "clients", "rotate_start_by_seed",
                "trailing_spaces"):
        assert mix[key] == older[key], key
    opens = [loadgen.Statements(mix, cell.queries, seed).order[0].name
             for seed in (2147483998, 2147483999)]
    assert opens == list(QUERIES)


def test_committed_answers_have_the_queries_shape():
    q03 = specs.read_json(specs.ROOT, "expected", CONFIG, "ds_q03.json")
    q07 = specs.read_json(specs.ROOT, "expected", CONFIG, "ds_q07.json")
    assert (q03["config"], q07["config"]) == (CONFIG, CONFIG)
    rows = q03["rows"]
    assert len(rows) == 100  # the LIMIT cuts: about 97 brands a year
    assert [r[0] for r in rows] == sorted(r[0] for r in rows)
    assert rows[0][0] == 1998 and all(r[2] == f"brand#{r[1]}" for r in rows)
    first_year = [r[3] for r in rows if r[0] == 1998]
    assert first_year == sorted(first_year, reverse=True)
    rows = q07["rows"]
    assert len(rows) == 100
    ids = [r[0] for r in rows]
    assert ids == sorted(ids) and len(set(ids)) == 100
    # 48 thousand of the 102,000 items have a row: the first hundred
    # by name end near item 200
    assert ids[0].startswith("AAAAAAAA000000") and ids[-1] < "AAAAAAAA00000400"
    assert all(1 <= r[1] <= 100 and r[4] <= r[2] for r in rows)


@pytest.fixture(scope="module")
def rehearsed(checkout):
    """One untraced and one traced run of the cell at SF0.01, and a
    traced run of an older cell to compare the metric names with."""
    runs = [rehearsal.run_cell(checkout, CELL, trace=t, seconds=3.0,
                               seed=2147483999) for t in (0, 1)]
    return runs + [rehearsal.run_cell(checkout, OLD_CELL, trace=1,
                                      seconds=3.0)]


def test_the_cell_runs_end_to_end(rehearsed):
    result = rehearsal.last_line(rehearsed[0])
    assert set(result) == rehearsal.RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert result["compared"]["answers_wrong"]["value"] == 0
    assert set(result["metrics"]) == {"setup_s", "pass_p50_ms", "rows_per_s"}
    observations = json.loads(rehearsed[0].stdout.strip().splitlines()[-2])
    assert set(observations["per_query_p50"]) == set(QUERIES)
    assert observations["warm_up_order"] == list(QUERIES)
    assert observations["queries_per_pass"] == list(QUERIES)[::-1]  # odd
    assert observations["row_counts"]["item"] == 102000
    assert observations["window_counters"] == {
        "programs": 0, "persistent_hits": 0, "persistent_misses": 0}


def test_the_cell_runs_traced_and_prints_every_general_metric(rehearsed):
    result = rehearsal.last_line(rehearsed[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    metrics = result["metrics"]
    assert metrics["chain_probes_per_pass"] == {"value": 6, "unit": "count"}
    assert metrics["compact_fallback_pages_per_pass"]["value"] == 0
    # neither query multiplies, divides or sums into limbs
    assert metrics["arith_checked_per_pass"]["value"] == 0
    assert metrics["host_reads_per_pass"]["value"] >= 10
    # XLA:CPU's trace has no device plane: no scope metric, no roofline
    assert not {"star_probe_first_ms", "star_probe_rest_ms",
                "star_probe_roofline_pct", "op_join_probe_ms"} & set(metrics)
    # what an older cell prints of the general metrics, this one prints
    # without having been listed anywhere
    manifest = shape.load(rehearsal.REPO)
    general = {m["name"] for m in manifest["per_layer"]
               if "workloads" not in m}
    old = set(rehearsal.last_line(rehearsed[2])["metrics"])
    assert old & general and old & general <= set(metrics)
    assert set(metrics) - general == {"chain_probes_per_pass"}
    # the ten costliest operations, whichever they are on XLA:CPU
    assert all(name.startswith("jit_") and seconds > 0
               for name, seconds in result["breakdown"]["device_ops"])


@pytest.mark.parametrize("name", NEW)
def test_manifest_and_reader_list_the_cell_for_its_own_four(name):
    """The probes of a star's fact chain are this configuration's
    mechanism: reader and manifest carry the same list, the cell in it,
    and every check of the committed pair passes."""
    manifest = shape.load(rehearsal.REPO)
    shape.every_check(manifest, specs.ROOT)
    shape.is_listed_for(manifest, specs.ROOT, name, CELL)
    entry = shape.entry_of(manifest, name)
    assert entry["workloads"] == [CELL] == READERS[name].WORKLOADS
    assert entry["unit"] == READERS[name].UNIT
    assert (entry["layer"], entry["source"]) == (
        ("Executor", "program_counter") if name == "chain_probes_per_pass"
        else ("Kernels", "device_trace"))
    assert (entry["moves"], entry["better"]) == (
        ("rows_per_s", "higher") if name == "star_probe_roofline_pct"
        else ("pass_p50_ms", "lower"))
    cell = shape.entry_of(manifest, CELL, "workloads")
    assert cell == specs.read_json(specs.ROOT, "workloads", CELL + ".json")
    assert len(cell["why"]) <= 200


# -- the readers, on a pass made by hand -----------------------------------

ROW_COUNTS = {"store_sales": 28_800_000, "item": 102_000,
              "date_dim": 73_049, "customer_demographics": 1_920_800,
              "promotion": 500}
Q03, Q07 = (f"jit_{CHAINS[q][1]}" for q in QUERIES)
OPS = [scopes.ScopedOp(0.00, 0.02, "jit_join_build", ("op:JoinBuild",)),
       scopes.ScopedOp(0.05, 0.20, Q03, ("op:Join", "probe:0", "join:lookup")),
       scopes.ScopedOp(0.20, 0.24, Q03, ("op:Filter", "filter:compact")),
       scopes.ScopedOp(0.24, 0.25, Q03, ("op:Join", "probe:0")),
       scopes.ScopedOp(0.25, 0.26, Q03, ("op:Join", "probe:1", "join:lookup")),
       scopes.ScopedOp(0.26, 0.30, Q03, ("op:Aggregation", "agg:sort")),
       scopes.ScopedOp(0.40, 0.58, Q07, ("op:Join", "probe:0", "join:lookup")),
       scopes.ScopedOp(0.58, 0.64, Q07, ("op:Filter", "filter:compact")),
       scopes.ScopedOp(0.64, 0.65, Q07, ("op:Join", "probe:0")),
       scopes.ScopedOp(0.65, 0.67, Q07, ("op:Join", "probe:1")),
       scopes.ScopedOp(0.67, 0.68, Q07, ("op:Join", "probe:2", "join:lookup")),
       scopes.ScopedOp(0.68, 0.70, Q07, ("op:Join", "probe:3")),
       scopes.ScopedOp(0.70, 0.90, Q07, ("op:Aggregation",))]
COUNTED = ({"chainProbes": 2, "hostReads": 9},
           {"chainProbes": 4, "hostReads": 13})


def _made_run(monkeypatch, stats, ops, peaks=True):
    """A ``run.Run`` of the cell with one traced pass of one second:
    ds_q03 then ds_q07 with the given final-page stats, and ``ops`` as
    chip 0's scoped operations."""
    queries = [loadgen.QueryRecord(q, i, 0.0, 0.0, True, stats=dict(s))
               for i, (q, s) in enumerate(zip(QUERIES, stats), 1)]
    one = loadgen.Pass(0, 1, 0.0, queries)
    run = bench_run.Run(
        specs.load_cell(CELL), {},
        {"hbm_bytes_per_s": 819e9} if peaks else None, ROW_COUNTS, {}, [one],
        {}, trace=type("T", (), {"stands_in": False})(), traced=[one])
    run.__dict__["pass_intervals"] = [(0.0, 1.0)]
    monkeypatch.setattr(scopes, "for_run", lambda r: ops)
    return run


def test_readers_read_their_scopes_and_counters(monkeypatch):
    run = _made_run(monkeypatch, COUNTED, OPS)
    first = READERS["star_probe_first_ms"].read(run)
    rest = READERS["star_probe_rest_ms"].read(run)
    assert first == pytest.approx(150 + 10 + 180 + 10)
    assert rest == pytest.approx(10 + 20 + 10 + 20)
    # the compaction inside probe 0 is the filter's, not the probe's
    assert READERS["op_filter_project_ms"].read(run) == pytest.approx(100.0)
    assert first + rest == pytest.approx(READERS["op_join_probe_ms"].read(run))
    assert READERS["chain_probes_per_pass"].read(run) == 6
    # 8 bytes a fact row a query, and each first dimension's key column
    need = 8 * (2 * 28_800_000 + 102_000 + 1_920_800)
    assert star_probe_bytes.pass_bytes(
        run.cell.config, run.cell.queries, ROW_COUNTS) == need
    share = READERS["star_probe_roofline_pct"].read(run)
    assert share == pytest.approx(100.0 * need / 819e9 / 0.35)
    assert 0 < share < 1.0


def test_readers_say_nothing_of_a_program_without_scope_or_counter(
        monkeypatch):
    """The parent of PR 37 under these files: no ``chainProbes`` in the
    stats, no ``probe:`` scope in the trace; the general readers read
    what they read."""
    bare = [scopes.ScopedOp(o.start, o.end, o.module,
                            tuple(s for s in o.scopes
                                  if not s.startswith("probe:")))
            for o in OPS]
    run = _made_run(monkeypatch, ({"hostReads": 9}, {"hostReads": 13}), bare)
    for name in NEW:
        assert READERS[name].read(run) is None, name
    assert READERS["op_join_probe_ms"].read(run) == pytest.approx(410.0)
    # ... and of a rehearsal: no scoped operations, no peak
    run = _made_run(monkeypatch, COUNTED, None, peaks=False)
    for name in NEW:
        assert (READERS[name].read(run) is None) == \
            (name != "chain_probes_per_pass"), name
    # a four-chip cell or a device without a peak has no such roofline
    run = _made_run(monkeypatch, COUNTED, OPS, peaks=False)
    assert READERS["star_probe_roofline_pct"].read(run) is None
    assert READERS["star_probe_first_ms"].read(run) == pytest.approx(350.0)


def test_star_probe_bytes_count_rows_and_stored_widths_only():
    """Rows from the load, widths from the configuration's ``tables``,
    the two key columns from the sidecar: nothing of a run, so the
    count is the same whatever implements the lookup, whatever the page
    size, and whichever dimension a later plan probes first (every key
    of the star is 8 bytes wide)."""
    config = specs.load_cell(CELL).config
    assert list(inspect.signature(star_probe_bytes.query_bytes).parameters) \
        == ["config", "query", "row_counts", "root"]
    q03 = star_probe_bytes.query_bytes(config, "ds_q03", ROW_COUNTS)
    q07 = star_probe_bytes.query_bytes(config, "ds_q07", ROW_COUNTS)
    assert q03 == 8 * (28_800_000 + 102_000)
    assert q07 == 8 * (28_800_000 + 1_920_800)
    assert (q03 + q07) / 819e9 * 1e3 == pytest.approx(0.5824, abs=1e-4)  # ms
    for other in (dict(config, split_rows=1 << 20),
                  dict(config, scale_factor=1), dict(config, generator="x")):
        assert star_probe_bytes.query_bytes(other, "ds_q03", ROW_COUNTS) == q03
    double = {t: 2 * n for t, n in ROW_COUNTS.items()}
    assert star_probe_bytes.query_bytes(config, "ds_q07", double) == 2 * q07
    narrow = json.loads(json.dumps(config))
    narrow["tables"]["store_sales"]["ss_item_sk"] = 4
    assert star_probe_bytes.query_bytes(narrow, "ds_q03", ROW_COUNTS) == \
        4 * 28_800_000 + 8 * 102_000
    # a query whose sidecar names no star has no such bytes
    tpch = specs.load_cell(OLD_CELL).config
    assert star_probe_bytes.query_bytes(
        tpch, "q03", {"lineitem": 6_001_215}) == 0
