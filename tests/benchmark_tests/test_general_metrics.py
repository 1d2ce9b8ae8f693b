"""PR 35: general metrics carry no list of cells, anywhere.

``op_join_probe_ms``, ``op_join_build_ms``, ``agg_sort_ms`` and
``hbm_roofline_pct`` report in every cell, those later PRs add too: 0
where a traced pass ran nothing under the reader's scope, nothing where
the run cannot say.  On passes made by hand, as ``test_fkjoin_cell.py``
makes them, with each cell's row counts from its own configuration's
generator.  The per-cell checks are functions of ``(cell, root)``, so
``test_a_cell_enters_as_files.py`` runs them over a grown copy too.
Also here: ``scopes.operations`` drops the control flow around the
operations of a line, and ``tables.load`` takes a connector that
declares neither sort order nor bucketing.
"""

import functools
import os

import pytest

import bench_rehearsal as rehearsal
import manifest_shape as shape
from benchmark import bytes_needed, loadgen, run as bench_run, scopes, specs
from benchmark import tables, xplane

MANIFEST = shape.load(rehearsal.REPO)
CELLS = shape.cells(MANIFEST)
READERS = shape.readers(specs.ROOT)
#: reader -> a scope path under which it finds its time
SCOPED = {
    "op_join_probe_ms": ("op:Join", "join:lookup"),
    "op_join_build_ms": ("op:JoinBuild",),
    "agg_sort_ms": ("op:Aggregation", "agg:sort"),
}
DELISTED = sorted(SCOPED) + ["hbm_roofline_pct"]
PEAK = 819e9


@functools.lru_cache(maxsize=None)
def row_counts(config: str, root: str = specs.ROOT) -> dict:
    """Rows of each resident table of the configuration at its scale,
    as its own generator counts them: a configuration a later PR adds
    brings its own tables and counts."""
    loaded = specs.read_json(root, "configs", config + ".json")
    conn = tables.generator_for(loaded, root)
    return {table: conn.row_count(table) for table in loaded["tables"]}


def _made_run(monkeypatch, cell, ops, chips=None, peaks=True,
              root=specs.ROOT, busy=None):
    """A ``run.Run`` of ``cell`` with one traced pass of one second,
    ``ops`` as chip 0's scoped operations and chip 0 busy for the first
    half of the pass (``busy``: chip -> seconds from the pass's start,
    where given)."""
    loaded = specs.load_cell(cell, root)
    if chips is not None:
        loaded = specs.Cell(loaded.name, chips, loaded.config, loaded.traffic,
                            loaded.layout, loaded.queries)
    one = loadgen.Pass(0, 1, 0.0, [
        loadgen.QueryRecord(q.name, i, 0.0, 0.0, True, stats={})
        for i, q in enumerate(loaded.queries)])
    trace = xplane.Trace({c: [xplane.Op(0.0, s, "fusion", "jit_f")]
                          for c, s in (busy or {0: 0.5}).items()},
                         {}, {}, False)
    run = bench_run.Run(
        loaded, {}, {"hbm_bytes_per_s": PEAK} if peaks else None,
        row_counts(loaded.config["name"], root), {}, [one], {}, trace=trace,
        traced=[one])
    run.__dict__["pass_intervals"] = [(0.0, 1.0)]
    monkeypatch.setattr(scopes, "for_run", lambda r: ops)
    return run


@pytest.mark.parametrize("name", DELISTED)
def test_no_list_in_reader_or_manifest(name):
    shape.is_general(MANIFEST, specs.ROOT, name)
    assert "very cell" in READERS[name].__doc__  # "Every cell ..."


def scope_reader_reports(monkeypatch, name, cell, root=specs.ROOT):
    """Whatever the cell: the scope's time where a pass ran under it,
    0.0 on a scoped pass without it, None on a trace without scopes."""
    reader = shape.readers(root)[name]
    mine = [scopes.ScopedOp(0.10, 0.35, "jit_f", SCOPED[name]),
            scopes.ScopedOp(0.40, 0.50, "jit_g", ("op:Filter",))]
    run = _made_run(monkeypatch, cell, mine, root=root)
    assert reader.read(run) == pytest.approx(250.0)
    others = [scopes.ScopedOp(0.10, 0.35, "jit_f", ("op:TopN",)),
              scopes.ScopedOp(0.40, 0.50, "jit_g", ())]
    assert reader.read(_made_run(monkeypatch, cell, others, root=root)) == 0.0
    assert reader.read(_made_run(monkeypatch, cell, None, root=root)) is None


def hbm_roofline_reports(monkeypatch, cell, root=specs.ROOT):
    """The reader decides by what it finds: a peak on record, a device
    plane; on N chips it divides by N peaks.  Never 0: nothing where it
    cannot say."""
    reader = shape.readers(root)["hbm_roofline_pct"]

    def made(**kw):
        return _made_run(monkeypatch, cell, None, root=root, **kw)

    run = made()
    need = bytes_needed.pass_bytes(run.cell.config, run.cell.queries,
                                   run.row_counts)
    assert need > 0
    assert reader.read(run) == pytest.approx(
        100.0 * need / (run.cell.chips * PEAK) / 0.5)
    for chips in (1, 4):  # the cell's own count or not
        assert reader.read(made(chips=chips)) == pytest.approx(
            100.0 * need / (chips * PEAK) / 0.5)
    assert reader.read(made(peaks=False)) is None
    stood_in = made()
    stood_in.trace.stands_in = True  # the CPU rehearsal: no device plane
    assert reader.read(stood_in) is None
    idle = made()
    idle.trace.chips[0] = []
    assert reader.read(idle) is None


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", sorted(SCOPED))
def test_scope_reader_reports_in_every_cell(monkeypatch, name, cell):
    scope_reader_reports(monkeypatch, name, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_hbm_roofline_reports_in_every_one_chip_cell_with_a_peak(
        monkeypatch, cell):
    """Every cell of the manifest, and the same cell taken on four
    chips: there the value is the mesh's, over four peaks."""
    hbm_roofline_reports(monkeypatch, cell)


def test_hbm_roofline_on_a_mesh_reads_the_busiest_chip(monkeypatch):
    """Four chips busy 0.5, 0.8, 0.2 and 0.0 s of the pass: the time is
    the busiest one's, the peak four chips'."""
    reader = READERS["hbm_roofline_pct"]
    run = _made_run(monkeypatch, "tpch_sf1.mesh_join", None,
                    busy={0: 0.5, 1: 0.8, 2: 0.2, 3: 0.0})
    assert run.cell.chips == 4
    assert run.busy_s_per_pass() == [0.5]  # chip 0's, as before
    assert run.busiest_s_per_pass() == [0.8]
    need = bytes_needed.pass_bytes(run.cell.config, run.cell.queries,
                                   run.row_counts)
    assert reader.read(run) == pytest.approx(100.0 * need / (4 * PEAK) / 0.8)
    # on one chip the busiest is chip 0, to the bit
    one = _made_run(monkeypatch, "tpch_sf1.join_agg", None)
    assert one.busiest_s_per_pass() == one.busy_s_per_pass() == [0.5]


# -- control flow in an ``XLA Ops`` line -------------------------------------

JOIN = "jit(join_probe)/op:Join/join:expand/gather:"
AGG = "jit(agg_fold)/op:Aggregation/agg:sort/sort:"


def test_an_enclosing_event_without_tf_op_is_dropped():
    """A ``while`` as long as its body: two scoped children inside it,
    one scoped event after it."""
    events = [(1000, 900, "", 7), (1000, 400, JOIN, 7), (1500, 400, JOIN, 7),
              (2000, 100, AGG, 8)]
    assert scopes.operations(events) == events[1:]
    # whatever order the line lists them in
    assert scopes.operations(events[::-1]) == events[1:]
    assert scopes.operations(events[1:] + events[:1]) == events[1:]


@pytest.mark.parametrize("events,kept", [
    # a conditional around a while around the body: both go
    ([(0, 1000, "", 1), (100, 800, "", 1), (100, 300, JOIN, 1),
      (500, 400, JOIN, 1)], [2, 3]),
    # an operation XLA gave no op_name, with nothing inside it, stays
    # (it is what ``device_unscoped_pct`` counts)
    ([(0, 100, "", 1), (100, 50, JOIN, 1), (150, 10, "", 1)], [0, 1, 2]),
    # an event that carries a tf_op is an operation, whatever follows
    ([(0, 100, JOIN, 1), (10, 20, AGG, 1)], [0, 1]),
    # the last event of a line encloses nothing
    ([(0, 100, JOIN, 1), (100, 900, "", 1)], [0, 1]),
    # an overlap that is no enclosure (ends later) drops nothing
    ([(0, 100, "", 1), (50, 100, JOIN, 1)], [0, 1]),
    ([], []),
])
def test_operations_keeps_what_is_an_operation(events, kept):
    assert scopes.operations(events) == [events[i] for i in kept]


# -- an ``.xplane.pb`` written by hand ---------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One field in wire format: a varint for an int, length-delimited
    for bytes or text."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(data)) + data


def _xspace(ops) -> bytes:
    """An ``XSpace`` with one ``/device:TPU:0`` plane whose ``XLA Ops``
    line holds ``ops``, ``(offset_ps, duration_ps, tf_op)``, all of
    program 7, ``jit_f(7)`` on the ``XLA Modules`` line.  Field numbers
    as ``benchmark/scopes.py`` lists them."""
    texts = sorted({tf_op for _, _, tf_op in ops})
    stat_meta = _field(5, _field(1, 1) + _field(2, _field(1, 1)
                                                + _field(2, "tf_op"))) \
        + _field(5, _field(1, 2) + _field(2, _field(1, 2)
                                          + _field(2, "program_id")))
    event_meta = b""
    for i, tf_op in enumerate(texts, start=1):
        stats = _field(5, _field(1, 2) + _field(3, 7))
        if tf_op:
            stats += _field(5, _field(1, 1) + _field(5, tf_op))
        event_meta += _field(4, _field(1, i) + _field(2, _field(1, i)
                             + _field(2, f"%op.{i} = f32[] op()") + stats))
    module_id = len(texts) + 1
    event_meta += _field(4, _field(1, module_id) + _field(
        2, _field(1, module_id) + _field(2, "jit_f(7)")))
    ops_line = _field(2, xplane.OPS_LINE) + _field(3, 0) + b"".join(
        _field(4, _field(1, texts.index(tf_op) + 1) + _field(2, offset)
               + _field(3, duration))
        for offset, duration, tf_op in ops)
    end = max(o + d for o, d, _ in ops)
    modules_line = _field(2, xplane.MODULES_LINE) + _field(3, 0) + _field(
        4, _field(1, module_id) + _field(2, 0) + _field(3, end))
    plane = _field(2, xplane.DEVICE_PLANE + "0") + _field(3, ops_line) \
        + _field(3, modules_line) + event_meta + stat_meta
    return _field(1, plane)


MS = 10 ** 9  # picoseconds


def test_read_drops_a_while_around_its_body(tmp_path):
    body = [(1 * MS, 4 * MS, JOIN), (5 * MS, 4 * MS, JOIN)]
    after = (10 * MS, 2 * MS, AGG)
    bare = (12 * MS, 1 * MS, "")
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(_xspace([(1 * MS, 9 * MS, "")] + body + [after, bare]))
    ops = scopes.read(str(path))
    assert [(round(o.start * 1e3, 6), round(o.end * 1e3, 6), o.module,
             o.scopes) for o in ops] == [
        (1.0, 5.0, "jit_f", ("op:Join", "join:expand")),
        (5.0, 9.0, "jit_f", ("op:Join", "join:expand")),
        (10.0, 12.0, "jit_f", ("op:Aggregation", "agg:sort")),
        (12.0, 13.0, "jit_f", ())]
    # the body counts once: 1 ms of 11 busy is under no scope, not 10 of 20
    busy = scopes.busy_s(ops, 0.0, 1.0)
    assert busy == pytest.approx(0.011)
    bare_s = scopes.busy_s([o for o in ops if not o.scopes], 0.0, 1.0)
    assert bare_s / busy == pytest.approx(1 / 11)
    # the same line without the while reads the same
    path.write_bytes(_xspace(body + [after, bare]))
    assert scopes.read(str(path)) == ops


# -- a connector as the program has it ---------------------------------------

def test_tables_load_takes_a_connector_without_sort_order_or_bucketing(
        tmp_path):
    """A generator file that returns ``connectors.tpcds.Tpcds`` itself,
    no subclass: it has neither ``sort_order`` nor ``bucketing``, which
    ``tables.load`` reads as none, as ``MemoryConnector.load_from``
    does, and the resident tables are the ones ``load_from`` makes."""
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.connectors.tpcds import Tpcds

    assert not hasattr(Tpcds, "sort_order")
    assert not hasattr(Tpcds, "bucketing")
    root = tmp_path / "benchmark"
    (root / "generators").mkdir(parents=True)
    (root / "generators" / "tpcds_plain.py").write_text(
        "def connector(config):\n"
        "    from presto_tpu.connectors.tpcds import Tpcds\n\n"
        "    return Tpcds(sf=float(config['scale_factor']),\n"
        "                 split_rows=int(config['split_rows']))\n")
    config = {"name": "tpcds_tiny", "generator": "tpcds_plain",
              "scale_factor": 0.01, "split_rows": 8192,
              "tables": {
                  "store_sales": {"ss_sold_date_sk": 8, "ss_item_sk": 8,
                                  "ss_ext_sales_price": 8},
                  "item": {"i_item_sk": 8, "i_brand_id": 8, "i_brand": 4},
                  "promotion": {"p_promo_sk": 8, "p_channel_email": 4}}}
    conn = tables.generator_for(config, str(root))
    assert type(conn) is Tpcds
    ours, rows, _ = tables.load(config, str(root))
    assert rows == {"store_sales": 28800, "item": 18000, "promotion": 300}
    assert os.path.isdir(tables.cache_dir(config, str(root)))
    theirs = MemoryConnector()
    for table, wanted in config["tables"].items():
        theirs.load_from(conn, table, columns=list(wanted))
        assert ours.schema(table) == theirs.schema(table)
        assert ours.primary_key(table) == theirs.primary_key(table)
        assert ours.sort_order(table) is None is theirs.sort_order(table)
        assert ours.bucketing(table) is None is theirs.bucketing(table)
        assert ours.num_splits(table) == theirs.num_splits(table)
        for column in wanted:
            assert ours.column_domain(table, column) == \
                theirs.column_domain(table, column)
